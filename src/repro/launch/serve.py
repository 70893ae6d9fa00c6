"""Serving driver: the MARS engine over either backend.

    # simulated paper-scale serving (H100 x Qwen3-Coder-30B, ILR-2):
    PYTHONPATH=src python -m repro.launch.serve --backend sim \
        --policy mars --regime ILR-2 --rate 0.2 --sessions 32

    # live engine at published width (Qwen2.5-3B, bf16, paged KV, real
    # tools) on one TPU; the Pallas kernels run compiled there:
    PYTHONPATH=src python -m repro.launch.serve --backend jax --sessions 4

    # the same path on the CPU: reduced toy config, kernels' jnp oracles
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
        --backend jax --reduced --sessions 4

The live path keeps its compile cache where ``JAX_COMPILATION_CACHE_DIR``
says, or at ``<repo>/.jax_cache`` (see ``use_compile_cache``).
``chip_smoke.py`` at the repository root drives ``serve_live`` on the chip
and checks what comes out.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import List, Tuple

import numpy as np

from repro.configs.registry import get_config
from repro.core.events import EventBus
from repro.core.goodput import summarize
from repro.engine.backend import SimBackend
from repro.engine.engine import Engine, EngineConfig, run_live, run_sim
from repro.engine.tools import RealToolExecutor
from repro.models import perf_model as pm
from repro.workloads.generator import WorkloadSpec, describe, generate


def serve_sim(*, policy: str, regime: str, rate: float, n_sessions: int,
              hw_name: str = "h100", model: str = "qwen3", seed: int = 0,
              alpha: float = 3.0, verbose: bool = True):
    if model == "qwen3":
        from repro.configs.qwen3_coder_30b import CONFIG as cfg, CONTEXT_LIMIT
    else:
        from repro.configs.gpt_oss_120b import CONFIG as cfg, CONTEXT_LIMIT
    hw = pm.HW[hw_name]
    kv_budget = hw.hbm_bytes - 2.1 * cfg.param_count()   # weights + overhead
    blocks = int(kv_budget / pm.kv_cache_bytes(cfg, 1) / 32)
    spec = WorkloadSpec(regime=regime, arrival_rate=rate,
                        n_sessions=n_sessions, seed=seed,
                        max_context=CONTEXT_LIMIT, slo_alpha=alpha)
    sessions = generate(spec, cfg, hw)
    backend = SimBackend(cfg, hw)
    eng = Engine(EngineConfig(total_kv_blocks=blocks, block_size=32,
                              token_budget=8192, max_decode_batch=64,
                              decode_granularity=8, cpu_slots=8),
                 policy, backend)
    finished, horizon = run_sim(eng, sessions, max_time=2e5)
    stats = summarize(finished, horizon)
    if verbose:
        print(f"[serve-sim] {policy} {regime} rate={rate}: "
              f"fin={stats['n_finished']}/{n_sessions} "
              f"mean={stats['latency'].mean:.1f}s p95={stats['latency'].p95:.1f}s "
              f"goodput(a=3)={stats['goodput'][3.0]*1e3:.2f} m req/s")
    return stats, eng


# page pool of the published-width live path on one 16 GB TPU v5e, sized
# from the compiled mixed step's memory_analysis() at Qwen2.5-3B in bf16:
# 6.17 GB of weights + 2.42 GB of pool (2,048 pages + scratch, 36,864
# bytes per token) as arguments, <= 3.2 GB of temporaries at its largest
# buckets (the layer scan's stacked pool outputs are most of them)
CHIP_PAGES = 2048
REDUCED_PAGES = 256


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory and
    return it. Call before the first compile. ``JAX_COMPILATION_CACHE_DIR``,
    when set, is read by JAX itself and wins; otherwise the cache lives at
    ``<repo>/.jax_cache`` — a fixed path, because the path is part of each
    entry's key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def agentic_sessions(n_sessions: int, prompt_range: Tuple[int, int]):
    """Three-round agent loops arriving 0.5 s apart: a repository-scale
    first prompt then a terminal tool, a 512-token follow-up then a file
    edit, a 256-token follow-up and the answer (32 + 32 + 64 generated
    tokens)."""
    from repro.core.session import Round, make_session
    rng = np.random.default_rng(0)
    lo, hi = prompt_range
    return [make_session(0.5 * i, [
                Round(int(rng.integers(lo, hi + 1)), 32, "terminal", 0.3),
                Round(512, 32, "file_editor", 0.15),
                Round(256, 64, None, 0.0)], ideal_time=1.0)
            for i in range(n_sessions)]


def serve_live(*, arch: str = "qwen2.5-3b", policy: str = "mars",
               n_sessions: int = 4, reduced: bool = False,
               verbose: bool = True) -> Tuple[List, Engine]:
    """Serve ``agentic_sessions`` live: ``Engine`` + paged ``JaxBackend``
    in bf16 with real tools, mixed scheduler. ``reduced`` swaps in the
    family's toy config, a small pool and short prompts (CPU runs). Raises
    ``TimeoutError`` unless every session finished within 900 s. Returns
    (finished sessions, engine)."""
    import jax.numpy as jnp
    from repro.engine.jax_runner import JaxBackend
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    pages = REDUCED_PAGES if reduced else CHIP_PAGES
    prompt_range = (64, 160) if reduced else (4096, 8192)
    backend = JaxBackend(cfg, max_slots=n_sessions, total_pages=pages,
                         dtype=jnp.bfloat16)
    bus = EventBus()
    tools = RealToolExecutor(cpu_slots=2, bus=bus)
    eng = Engine(EngineConfig(
        total_kv_blocks=pages, block_size=32, token_budget=4096,
        max_decode_batch=8, cpu_slots=2), policy, backend, bus=bus,
        tool_exec=tools)
    timeout = 900.0
    try:
        finished, _ = run_live(eng, agentic_sessions(n_sessions, prompt_range),
                               timeout=timeout)
    finally:
        tools.shutdown()
        backend.close()
    if verbose:
        for s in finished:
            gen = len(s.meta.get("generated", []))
            print(f"[serve-live] sid={s.sid} e2e={s.e2e_latency:.2f}s "
                  f"tokens={gen} ttfts={[round(t, 3) for t in s.ttfts]}")
    if len(finished) < n_sessions:
        raise TimeoutError(f"{len(finished)}/{n_sessions} sessions finished "
                           f"within {timeout} s")
    return finished, eng


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["sim", "jax"], default="sim")
    ap.add_argument("--policy", default="mars")
    ap.add_argument("--regime", default="ILR-1")
    ap.add_argument("--rate", type=float, default=0.2)
    ap.add_argument("--sessions", type=int, default=24)
    ap.add_argument("--hw", default="h100")
    ap.add_argument("--model", default="qwen3")
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="live backend: the arch's toy config (CPU runs)")
    args = ap.parse_args(argv)
    if args.backend == "sim":
        serve_sim(policy=args.policy, regime=args.regime, rate=args.rate,
                  n_sessions=args.sessions, hw_name=args.hw, model=args.model)
    else:
        use_compile_cache()
        serve_live(arch=args.arch, policy=args.policy,
                   n_sessions=args.sessions, reduced=args.reduced)


if __name__ == "__main__":
    main()
