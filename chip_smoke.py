"""Chip smoke: the live serving path at published width on one TPU.

    python chip_smoke.py

Serves Qwen2.5-3B at its published widths and full depth (36 layers), in
bf16, through ``repro.launch.serve.serve_live``: ``Engine`` + the paged
``JaxBackend``, one fused ``lm_mixed_paged`` dispatch per tick, with the
Pallas kernels compiled. Phases, in order:

1. device: the first JAX device must be a TPU;
2. kernels: each attention kernel on the served path, compiled at the
   served shapes, against its jnp oracle in ``kernels/ref.py``;
3. model: one 2,048-token prefill chunk of the full model through the
   gather-free ``lm_prefill_paged`` (paged flash kernel) against
   ``lm_prefill_paged_gather`` (gather, then ``lm_step``'s XLA attention);
4. serve: 4 three-round agent sessions with real tools under MARS and the
   mixed scheduler; every session must finish with its 128 tokens.

Findings are printed line by line. The last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase, or a host without a TPU, exits nonzero without it. One
process; it starts none. Times printed here are bring-up output, not
benchmark figures.
"""
from __future__ import annotations

import gc
import json
import sys
import time
import traceback
from pathlib import Path

# bf16 tolerances, stated before any chip run. Kernel outputs are convex
# combinations of N(0, 1) values rounded to bf16 (one ulp near 1 is 2^-7);
# the interpret-mode tests hold the same 2e-2.
KERNEL_TOL = 2e-2
# Random-init logits are ~N(0, 1). Two attention paths over 36 bf16 layers
# drift apart by bf16 rounding; a reduced-width 36-layer CPU run measured
# 0.08. Top-1 must agree wherever the reference's margin exceeds 2 * tol
# (no flip is possible there if the bound holds).
MODEL_LOGIT_TOL = 0.25
N_SESSIONS = 4
TOKENS_PER_SESSION = 32 + 32 + 64
HQ, HKV, D, PAGE = 16, 2, 128, 32     # Qwen2.5-3B attention widths


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Counts backend compiles (cache loads included) and their seconds."""

    def __init__(self, jax):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.programs, self.seconds, self.cache_hits

    def since(self, snap) -> str:
        n, s, h = self.snapshot()
        return (f"programs={n - snap[0]} seconds={s - snap[1]:.3f} "
                f"cache_hits={h - snap[2]}")


def compiled_call(fn, *args):
    """Compile ``fn`` for ``args``, check a Pallas kernel is in it, run it."""
    compiled = fn.lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in its HLO")
    return compiled(*args)


def phase_kernels(jax, jnp, np):
    from repro.kernels import ref
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_fused)
    from repro.kernels.paged_flash_attention import paged_flash_attention
    bf = jnp.bfloat16
    rng = np.random.default_rng(0)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    P, B, maxp = 1025, 4, 256                       # 8,192-token contexts
    kp = jax.random.normal(ks[0], (P, PAGE, HKV, D), bf)
    vp = jax.random.normal(ks[1], (P, PAGE, HKV, D), bf)

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    # decode: B lanes over disjoint pages (a lane writes only pages it
    # owns, as the engine's leases guarantee), ragged lengths
    table = rng.permutation(P - 1)[:B * maxp].reshape(B, maxp)
    lengths = rng.integers(1, maxp * PAGE + 1, B).astype(np.int32)
    q = jax.random.normal(ks[2], (B, HQ, D), bf)
    table_j, lengths_j = jnp.asarray(table, jnp.int32), jnp.asarray(lengths)
    out = compiled_call(paged_attention, q, kp, vp, table_j, lengths_j)
    e = err(out, ref.paged_attention_ref(q, kp, vp, table_j, lengths_j))
    print(f"kernel paged_attention B={B} pages={maxp}: max_err={e:.6f} "
          f"tol={KERNEL_TOL}")
    check(e <= KERNEL_TOL, "paged_attention outside tolerance")

    # fused decode write: the slot is sequence index lengths - 1
    kn = jax.random.normal(ks[3], (B, HKV, D), bf)
    vn = jax.random.normal(ks[4], (B, HKV, D), bf)
    wp = jnp.asarray(table[np.arange(B), (lengths - 1) // PAGE], jnp.int32)
    wo = jnp.asarray((lengths - 1) % PAGE, jnp.int32)
    o, kp2, vp2 = compiled_call(paged_attention_fused, q, kp, vp, table_j,
                                lengths_j, kn, vn, wp, wo)
    kref = kp.at[wp, wo].set(kn)
    vref = vp.at[wp, wo].set(vn)
    e = err(o, ref.paged_attention_ref(q, kref, vref, table_j, lengths_j))
    pool_e = max(err(kp2, kref), err(vp2, vref))
    print(f"kernel paged_attention_fused B={B} pages={maxp}: max_err={e:.6f} "
          f"tol={KERNEL_TOL} pool_write_err={pool_e}")
    check(e <= KERNEL_TOL, "paged_attention_fused outside tolerance")
    check(pool_e == 0.0, "paged_attention_fused wrote the wrong pool slots")

    # chunked prefill: a 2,048-token chunk after a 4,096-token prefix
    Sq, q_off, Np = 2048, 4096, 256
    ftable = jnp.asarray(
        np.concatenate([rng.permutation(P - 1)[:Np - 1], [P - 1]])[None],
        jnp.int32)
    kv_len = jnp.asarray([q_off + Sq], jnp.int32)
    qoff = jnp.asarray([q_off], jnp.int32)
    qf = jax.random.normal(ks[5], (1, HQ, Sq, D), bf)
    out = compiled_call(paged_flash_attention, qf, kp, vp, ftable, kv_len,
                        qoff)
    e = err(out, ref.paged_flash_attention_ref(qf, kp, vp, ftable, kv_len,
                                               qoff))
    print(f"kernel paged_flash_attention Sq={Sq} q_offset={q_off} "
          f"pages={Np}: max_err={e:.6f} tol={KERNEL_TOL}")
    check(e <= KERNEL_TOL, "paged_flash_attention outside tolerance")


def phase_model(jax, jnp, np):
    from repro.configs.registry import get_config
    from repro.models import model_zoo
    from repro.models.transformer import (PagedKVCache, lm_prefill_paged,
                                          lm_prefill_paged_gather)
    cfg = get_config("qwen2.5-3b")
    params = jax.jit(lambda k: model_zoo.init(cfg, k, jnp.bfloat16))(
        jax.random.PRNGKey(1))
    C = 2048
    n = 2 * C // PAGE                      # a prefix chunk, then the chunk
    npages = n + 8
    rng = np.random.default_rng(1)
    table = np.concatenate([rng.permutation(npages)[:n], [npages]])
    table = table.astype(np.int32)         # last entry: the scratch page
    cache = PagedKVCache.zeros(cfg, npages + 1, PAGE, jnp.bfloat16)
    toks = rng.integers(2, cfg.vocab_size, 2 * C).astype(np.int32)

    def chunk(i):
        pos = np.arange(i * C, (i + 1) * C, dtype=np.int32)
        return (jnp.asarray(toks[None, i * C:(i + 1) * C]),
                jnp.asarray(pos[None]), jnp.asarray(table),
                jnp.asarray(table[pos // PAGE]), jnp.asarray(pos % PAGE))

    kernel = jax.jit(lambda p, c, *a: lm_prefill_paged(cfg, p, c, *a))

    @jax.jit
    def compare(p, c, toks, pos, tab, wp, wo, kv_len):
        lk, _ = lm_prefill_paged(cfg, p, c, toks, pos, tab, wp, wo, kv_len)
        lg, _ = lm_prefill_paged_gather(cfg, p, c, toks, pos, tab, wp, wo)
        lk, lg = lk[0], lg[0]
        top2 = jax.lax.top_k(lg, 2)[0]
        margin = top2[:, 0] - top2[:, 1]
        same = jnp.argmax(lk, -1) == jnp.argmax(lg, -1)
        return (jnp.max(jnp.abs(lk - lg)), jnp.mean(same),
                jnp.all(same | (margin <= 2 * MODEL_LOGIT_TOL)),
                jnp.max(jnp.abs(lg)))

    _, cache = kernel(params, cache, *chunk(0), jnp.int32(C))
    diff, agree, clear_agree, scale = (
        float(x) for x in compare(params, cache, *chunk(1), jnp.int32(2 * C)))
    print(f"model {cfg.name} prefill chunk={C} after prefix={C}: paged "
          f"flash vs gather max_logit_diff={diff:.6f} tol={MODEL_LOGIT_TOL} "
          f"top1_agree={agree:.6f} max_abs_logit={scale:.6f}")
    check(diff <= MODEL_LOGIT_TOL, "model logits outside tolerance")
    check(clear_agree, "top-1 flipped where the reference margin is clear")


def phase_serve(jax, jnp, np, compile_log):
    from repro.core import events as ev
    from repro.launch.serve import serve_live
    snap = compile_log.snapshot()
    t0 = time.monotonic()
    finished, eng = serve_live(n_sessions=N_SESSIONS)
    wall = time.monotonic() - t0
    b = eng.backend
    cfg = b.cfg
    impl = b._impl
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"params={cfg.param_count():,} dtype={jnp.dtype(b.dtype).name} "
          f"layout={b.layout} pages={impl.total_pages} page={impl.page}")
    check(b.layout == "paged", f"layout is {b.layout}, not paged")
    check(len(finished) == N_SESSIONS,
          f"{len(finished)}/{N_SESSIONS} sessions finished")
    gens = [len(s.meta.get("generated", [])) for s in finished]
    print(f"serve: sessions={len(finished)} generated={gens} "
          f"wall_s={wall:.3f} (bring-up output, not a benchmark figure)")
    check(all(g == TOKENS_PER_SESSION for g in gens),
          f"expected {TOKENS_PER_SESSION} tokens per session, got {gens}")
    counts = eng.bus.counts
    drops = sum(1 for e in eng.bus.log
                if e.kind == ev.EVICT and e.data.get("reason") == "tool_free")
    print(f"retention: pin={counts.get(ev.PIN, 0)} "
          f"offload={counts.get(ev.SWAP_OUT, 0)} drop={drops} "
          f"swap_in={counts.get(ev.SWAP_IN, 0)}")
    st = b.dispatch_stats
    print(f"dispatch: mixed_calls={st['mixed_calls']} "
          f"mixed_step_shapes={impl._mixed_fn._cache_size()} "
          f"compile {compile_log.since(snap)}")

    # the compiled mixed step at its largest buckets holds the kernels
    i32 = jnp.int32

    def spec(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    P, C, Np, B, maxp = 1, 2048, 512, 4, 512
    args = [spec((P, 1, C)), spec((P, 1, C)), spec((P, Np)), spec((P, C)),
            spec((P, C)), spec((P,)), spec((P,)), spec((B,)), spec((B,)),
            spec((B, maxp)), spec((B,)), spec((B,)), spec((B,))]
    shapes = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                          (b.params, impl.cache))
    text = impl._mixed_fn.lower(*shapes, *args).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    print(f"mixed step P={P} C={C} Np={Np} B={B} maxp={maxp}: "
          f"tpu_custom_call count={n_calls}")
    check(n_calls > 0, "no Pallas kernel in the compiled mixed step")


def main() -> int:
    repo = Path(__file__).resolve().parent
    if not (repo / "src" / "repro").is_dir():
        print("chip_smoke: no src/repro beside this script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(repo / "src"))
    from repro.launch.serve import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    print(f"compile cache: {cache_dir}")
    compile_log = CompileLog(jax)
    t_all = time.monotonic()
    for name, run in (("kernels", lambda: phase_kernels(jax, jnp, np)),
                      ("model", lambda: phase_model(jax, jnp, np)),
                      ("serve", lambda: phase_serve(jax, jnp, np,
                                                    compile_log))):
        snap = compile_log.snapshot()
        t0 = time.monotonic()
        try:
            run()
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: phase {name} failed", file=sys.stderr)
            return 1
        gc.collect()
        print(f"phase {name}: ok wall_s={time.monotonic() - t0:.3f} "
              f"compile {compile_log.since(snap)}")
    print(f"all phases: wall_s={time.monotonic() - t_all:.3f} "
          f"compile programs={compile_log.programs} "
          f"seconds={compile_log.seconds:.3f} "
          f"cache_hits={compile_log.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
