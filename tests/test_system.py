"""End-to-end behaviour tests for the full MARS system: paper-level claims
reproduced at test scale (latency ordering, TTFT advantage, ablations, KV
dynamics), plus the live-JAX engine and training loop."""
import numpy as np
import pytest

from repro.configs.qwen3_coder_30b import CONFIG as QWEN3, CONTEXT_LIMIT
from repro.core.goodput import summarize
from repro.engine.backend import SimBackend
from repro.engine.engine import Engine, EngineConfig, run_sim
from repro.models.perf_model import H100
from repro.workloads.generator import WorkloadSpec, describe, generate


def _run(policy, n=16, rate=0.25, regime="ILR-2", seed=4, blocks=9500):
    spec = WorkloadSpec(regime=regime, arrival_rate=rate, n_sessions=n,
                        seed=seed, max_context=CONTEXT_LIMIT)
    sessions = generate(spec, QWEN3, H100)
    eng = Engine(EngineConfig(total_kv_blocks=blocks, cpu_slots=16),
                 policy, SimBackend(QWEN3, H100))
    finished, horizon = run_sim(eng, sessions, max_time=1e5)
    return summarize(finished, horizon), eng


def test_workload_matches_paper_regimes():
    """ILR prompt volumes grow monotonically ~125K->263K (paper Fig. 6)."""
    means = []
    for regime in ("ILR-1", "ILR-2", "ILR-3", "ILR-4"):
        spec = WorkloadSpec(regime=regime, arrival_rate=0.2, n_sessions=64,
                            seed=0, max_context=CONTEXT_LIMIT)
        d = describe(generate(spec, QWEN3, H100))
        means.append(d["mean_prompt_tokens"])
        assert d["mean_ideal_s"] > 100.0         # tool-dominated ideal times
    assert means == sorted(means)
    assert 90_000 < means[0] < 160_000
    assert 180_000 < means[3] < 280_000


def test_mars_beats_request_oblivious_baselines_e2e():
    """Headline claim at test scale: MARS mean latency < FCFS and Autellix,
    and its per-round TTFT tail is several times better."""
    mars, _ = _run("mars")
    fcfs, _ = _run("fcfs")
    autx, _ = _run("autellix")
    assert mars["latency"].mean < fcfs["latency"].mean
    assert mars["latency"].mean < autx["latency"].mean
    assert mars["ttft"].p95 * 2.0 < fcfs["ttft"].p95


def test_mars_beats_tool_aware_baselines_on_goodput():
    mars, _ = _run("mars", regime="ILR-1", rate=0.2)
    cont, _ = _run("continuum-dy", regime="ILR-1", rate=0.2)
    assert mars["goodput"][3.0] >= cont["goodput"][3.0]


def test_ablations_degrade_mars():
    """Paper Fig. 13: removing any component should not improve MARS."""
    full, _ = _run("mars", n=16)
    worst = 0.0
    for variant in ("mars-no-ctrl", "mars-no-coord", "mars-no-cosched"):
        v, _ = _run(variant, n=16)
        worst = max(worst, v["latency"].mean)
        assert v["latency"].mean >= 0.9 * full["latency"].mean
    assert worst > full["latency"].mean          # at least one clearly hurts


def test_kv_dynamics_mars_suppresses_late_evictions():
    """Paper Fig. 3A: MARS reclaims early (arrival spike) and suppresses
    evictions late, vs FCFS churning throughout."""
    _, eng_m = _run("mars", n=16, rate=0.4)
    evs = [e for e in eng_m.bus.log if e.kind in ("evict", "preempt")]
    horizon = max(e.t for e in eng_m.bus.log)
    early = sum(e.data.get("blocks", 1) for e in evs if e.t < 0.5 * horizon)
    late = sum(e.data.get("blocks", 1) for e in evs if e.t >= 0.5 * horizon)
    assert early + late == 0 or late <= early


def test_live_jax_engine_end_to_end():
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.core.events import EventBus
    from repro.core.session import Round, make_session
    from repro.engine.engine import run_live
    from repro.engine.jax_runner import JaxBackend
    from repro.engine.tools import RealToolExecutor
    cfg = get_config("llama3.2-1b").reduced()
    backend = JaxBackend(cfg, max_slots=4, max_len=256)
    bus = EventBus()
    tools = RealToolExecutor(cpu_slots=2, bus=bus)
    eng = Engine(EngineConfig(total_kv_blocks=4 * 255 // 32, block_size=32,
                              token_budget=128, max_decode_batch=4,
                              decode_granularity=4, cpu_slots=2),
                 "mars", backend, bus=bus, tool_exec=tools)
    ss = [make_session(0.02 * i, [Round(48, 8, "t", 0.05), Round(24, 6, None, 0.0)],
                       ideal_time=1.0) for i in range(3)]
    finished, _ = run_live(eng, ss, timeout=120)
    tools.shutdown()
    assert len(finished) == 3
    for s in finished:
        assert len(s.meta.get("generated", [])) == 14
        assert len(s.ttfts) == 2


def test_serve_live_reduced_serves_every_session():
    """The chip smoke's serving path at the toy size: paged layout, every
    session finishes all three rounds with its 32 + 32 + 64 tokens."""
    from repro.launch.serve import serve_live
    finished, eng = serve_live(n_sessions=2, reduced=True, verbose=False)
    assert eng.backend.layout == "paged"
    assert sorted(len(s.meta["generated"]) for s in finished) == [128, 128]
    assert all(len(s.ttfts) == 3 for s in finished)


def test_chip_smoke_exits_nonzero_without_tpu():
    """On the CPU the chip smoke refuses to run and prints no result."""
    import os
    import subprocess
    import sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_training_loss_decreases():
    from repro.launch.train import train
    losses, _ = train("llama3.2-1b", reduced=True, steps=30, seq_len=64,
                      batch=4, verbose=False)
    assert len(losses) == 30
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_train_checkpoint_restart_is_exact():
    """Fault tolerance: crash + resume reproduces the uninterrupted run."""
    import shutil
    import tempfile
    from repro.launch.train import train
    d = tempfile.mkdtemp()
    try:
        full, _ = train("llama3.2-1b", steps=12, seq_len=32, batch=2,
                        verbose=False)
        part, _ = train("llama3.2-1b", steps=12, stop_after=6, seq_len=32,
                        batch=2, ckpt_dir=d, ckpt_every=6, verbose=False)
        resumed, _ = train("llama3.2-1b", steps=12, seq_len=32, batch=2,
                           ckpt_dir=d, resume=True, ckpt_every=100,
                           verbose=False)
        np.testing.assert_allclose(resumed, full[6:], rtol=1e-5, atol=1e-6)
    finally:
        shutil.rmtree(d, ignore_errors=True)
