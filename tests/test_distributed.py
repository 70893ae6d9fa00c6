"""Distributed substrate tests: sharding rules, EP-vs-local MoE numerics,
distributed train step equivalence, checkpoint restore, cluster router."""
import os
import shutil
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.distributed import checkpoint as ckpt
from repro.distributed import sharding as sh
from repro.distributed.router import ClusterRouter, RouterConfig
from repro.models import model_zoo

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

class _FakeMesh:
    """Shape/axis-name stand-in so spec rules can be checked without devices."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_always_divisible(arch):
    """Every emitted PartitionSpec must evenly divide its dim on the
    production mesh shape — for all 10 archs (full-scale shapes)."""
    cfg = get_config(arch)
    mesh = _FakeMesh({"data": 16, "model": 16})
    params = jax.eval_shape(lambda k: model_zoo.init(cfg, k, jnp.bfloat16), KEY)
    specs = sh.param_specs(cfg, mesh, params)

    def check(leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (arch, spec, leaf.shape)

    jax.tree.map(check, params, specs)


def _shards_of(spec, mesh):
    shards = 1
    for ax in spec:
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            shards *= mesh.shape[a]
    return shards


@pytest.mark.parametrize("arch", ["gemma2-27b", "dbrx-132b", "llava-next-34b"])
def test_param_bytes_per_device_fit_v5e(arch):
    """bf16 params (TP/EP) + f32 Adam moments (additionally data-sharded,
    ZeRO-1) per chip must fit well under v5e's 16 GB."""
    cfg = get_config(arch)
    mesh = _FakeMesh({"data": 16, "model": 16})
    params = jax.eval_shape(lambda k: model_zoo.init(cfg, k, jnp.bfloat16), KEY)
    specs = sh.param_specs(cfg, mesh, params)
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    total = 0.0
    for leaf, spec in zip(jax.tree.leaves(params),
                          jax.tree.leaves(specs, is_leaf=is_spec)):
        n = np.prod(leaf.shape)
        total += n / _shards_of(spec, mesh) * 2          # bf16 params
        mspec = sh.opt_moment_spec(spec, leaf.shape, mesh)
        total += 2 * n / _shards_of(mspec, mesh) * 4     # f32 mu + nu
    assert total < 12e9, f"{arch}: {total/1e9:.1f} GB/chip"


def test_moe_ep_matches_local():
    """Expert-parallel MoE (shard_map + all_to_all) == single-device MoE."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.configs.registry import get_config
from repro.models.layers import init_moe, moe_ffn, moe_ffn_ep_local, ParallelCtx
from repro.models.transformer import _moe_block

import dataclasses
cfg = get_config("dbrx-132b").reduced()
# disable capacity drops: EP capacities are shard-local, so with drops the
# two paths legitimately differ; without drops they must agree exactly.
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=float(cfg.moe.num_experts) / cfg.moe.top_k))
assert cfg.moe.num_experts % 2 == 0
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
p = init_moe(cfg, jax.random.PRNGKey(1), jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model), jnp.float32)
want = moe_ffn(cfg, p, x)
pctx = ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model", ep_axis="data")
lp = {"moe": p}
with mesh:
    got = jax.jit(lambda lp, x: _moe_block(cfg, lp, x, pctx))(lp, x)
err = float(jnp.max(jnp.abs(got - want)))
# capacity-dispatch order can differ at shard boundaries; tolerance loose
assert err < 5e-4, err
# gradient correctness through shard_map + all_to_all
g1 = jax.grad(lambda p_: jnp.sum(moe_ffn(cfg, p_, x) ** 2))(p)
with mesh:
    g2 = jax.jit(jax.grad(
        lambda p_: jnp.sum(_moe_block(cfg, {"moe": p_}, x, pctx) ** 2)))(p)
for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3)
print("EP-OK")
"""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "EP-OK" in out.stdout, out.stdout + out.stderr


def test_distributed_train_step_matches_single_device():
    """jit train_step on a (2,2) mesh == single device, same inputs."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_config
from repro.distributed.steps import build_train_step
from repro.models import model_zoo
from repro.train.optimizer import init_opt

cfg = get_config("llama3.2-1b").reduced()
params = model_zoo.init(cfg, jax.random.PRNGKey(0), jnp.float32)
opt_state = init_opt(params)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 1, cfg.vocab_size)
batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
fn_m = build_train_step(cfg, mesh, remat=False)
with mesh:
    p_m, o_m, m_m = jax.jit(fn_m)(params, opt_state, batch)

mesh1 = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                          ("data", "model"))
fn_1 = build_train_step(cfg, mesh1, remat=False)
with mesh1:
    p_1, o_1, m_1 = jax.jit(fn_1)(params, opt_state, batch)
np.testing.assert_allclose(float(m_m["loss"]), float(m_1["loss"]),
                           rtol=1e-4, atol=1e-4)
for a, b in zip(jax.tree.leaves(p_m), jax.tree.leaves(p_1)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=5e-4, rtol=5e-3)
print("DIST-OK")
"""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "DIST-OK" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_gc():
    cfg = get_config("llama3.2-1b").reduced()
    params = model_zoo.init(cfg, KEY, jnp.float32)
    d = tempfile.mkdtemp()
    try:
        for step in (1, 2, 3, 4, 5):
            ckpt.save(d, params, step=step, keep=2)
        assert ckpt.latest_step(d) == 5
        assert len([x for x in os.listdir(d) if x.startswith("step_")]) == 2
        restored, step = ckpt.restore(d, params)
        assert step == 5
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_checkpoint_async_and_atomicity():
    d = tempfile.mkdtemp()
    try:
        tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}
        th = ckpt.save(d, tree, step=7, async_=True)
        th.join()
        got, step = ckpt.restore(d, tree)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(got["b"]["c"]), np.ones((3, 3)))
        assert not any(x.endswith(".tmp") for x in os.listdir(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_engine_snapshot_restore():
    from repro.configs.qwen3_coder_30b import CONFIG as QWEN3
    from repro.engine.backend import SimBackend
    from repro.engine.engine import Engine, EngineConfig, run_sim
    from repro.models.perf_model import H100
    from repro.workloads.generator import WorkloadSpec, generate
    spec = WorkloadSpec(regime="ILR-1", arrival_rate=1.0, n_sessions=6, seed=2,
                        max_context=250_000)
    sessions = generate(spec, QWEN3, H100)
    eng = Engine(EngineConfig(total_kv_blocks=9000), "mars",
                 SimBackend(QWEN3, H100))
    for s in sessions:
        eng.submit(s)
    now = 0.0
    for _ in range(60):                       # run partway, then "crash"
        el, _ = eng.tick(now)
        now += max(el, 0.05)
    snap = ckpt.snapshot_engine(eng)
    eng2 = Engine(EngineConfig(total_kv_blocks=9000), "mars",
                  SimBackend(QWEN3, H100))
    n = ckpt.restore_engine(eng2, snap)
    assert n == len(snap["waiting"]) + len(snap["active"])
    finished, _ = run_sim(eng2, [], max_time=1e5)
    assert len(finished) == n                 # all recovered sessions complete


# ---------------------------------------------------------------------------
# cluster router
# ---------------------------------------------------------------------------

def _mini_engine():
    from repro.configs.qwen3_coder_30b import CONFIG as QWEN3
    from repro.engine.backend import SimBackend
    from repro.engine.engine import Engine, EngineConfig
    from repro.models.perf_model import H100
    return Engine(EngineConfig(total_kv_blocks=9000), "mars",
                  SimBackend(QWEN3, H100))


def test_router_failover_requeues_sessions():
    from repro.core.session import Round, make_session
    r = ClusterRouter(RouterConfig(heartbeat_timeout=5.0))
    e1, e2 = _mini_engine(), _mini_engine()
    r.register("a", e1, now=0.0)
    r.register("b", e2, now=0.0)
    ss = [make_session(0.0, [Round(1000, 8, None, 0.0)], ideal_time=1.0)
          for _ in range(6)]
    for s in ss:
        r.heartbeat("a", kv_utilization=0.1, tool_backlog=0,
                     active_sessions=len(e1.waiting), step_latency=0.1, now=0.0)
        r.heartbeat("b", kv_utilization=0.1, tool_backlog=0,
                     active_sessions=len(e2.waiting), step_latency=0.1, now=0.0)
        r.place(s, now=0.0)
    placed_a = len(e1.waiting)
    assert placed_a + len(e2.waiting) == 6
    # replica a dies: heartbeat only from b
    r.heartbeat("b", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=10.0)
    failed = r.check_failures(now=10.0)
    assert failed == ["a"]
    assert len(r.requeued) == placed_a
    n = r.dispatch_requeued(now=10.0)
    assert n == placed_a
    assert len(e2.waiting) + len(e2.active) + len(e2.rejected) == 6


def test_router_straggler_drain_and_affinity():
    from repro.core.session import Round, make_session
    r = ClusterRouter(RouterConfig(straggler_factor=2.0))
    e1, e2, e3 = _mini_engine(), _mini_engine(), _mini_engine()
    for rid, e in (("a", e1), ("b", e2), ("c", e3)):
        r.register(rid, e, now=0.0)
        r.heartbeat(rid, kv_utilization=0.2, tool_backlog=0, active_sessions=0,
                    step_latency=0.1, now=0.0)
    # c becomes 5x slower than the median
    for _ in range(20):
        r.heartbeat("c", kv_utilization=0.2, tool_backlog=0, active_sessions=0,
                    step_latency=0.5, now=1.0)
    drained = r.update_stragglers(now=1.0)
    assert drained == ["c"]
    s = make_session(0.0, [Round(1000, 8, None, 0.0)], ideal_time=1.0)
    rid = r.place(s, now=1.0)
    assert rid in ("a", "b")
    # affinity: same session returns to its home replica
    rid2 = r.place(s, now=2.0)
    assert rid2 == rid


@pytest.mark.parametrize("path", ["drain", "failure"])
def test_router_resets_kv_accounting_on_leave_and_failure(path):
    """Sessions handed back by a dying/draining replica must not carry
    phantom block accounting into their next placement — the new engine's
    refcount invariants would trip on the stale kv_blocks."""
    from repro.core.session import KVState, Round, make_session
    r = ClusterRouter(RouterConfig(heartbeat_timeout=5.0))
    e1 = _mini_engine()
    r.register("a", e1, now=0.0)
    r.heartbeat("a", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=0.0)
    s = make_session(0.0, [Round(200_000, 16, None, 0.0)], ideal_time=1.0)
    assert r.place(s, now=0.0) == "a"
    # run a few ticks so the session holds blocks mid-prefill
    now = 0.0
    for _ in range(3):
        el, _ = e1.tick(now)
        now += max(el, 0.05)
    assert s.kv_blocks > 0 and s.resident_len > 0
    if path == "drain":
        moved = r.leave("a", now=1.0)
        assert s in moved
    else:
        failed = r.check_failures(now=100.0)
        assert failed == ["a"]
        assert s in r.requeued
        moved = r.requeued
    for m in moved:
        assert m.kv_blocks == 0 and m.resident_len == 0
        assert m.kv_state == KVState.NONE
    # the old engine is detached too: no stale membership or leases, so a
    # heartbeat-recovered replica can keep ticking without tripping
    assert s not in e1.active and s not in e1.waiting
    assert e1.blocks.free == e1.blocks.total
    e1.check_invariants()
    e1.tick(2.0)
    # re-placement on a fresh replica keeps the new invariants intact
    e2 = _mini_engine()
    r.register("b", e2, now=101.0)
    r.heartbeat("b", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=101.0)
    assert r.place(s, now=101.0) == "b"
    e2.tick(0.0)
    e2.check_invariants()


def test_router_leave_drops_host_tier_entries():
    """Draining a replica must clear engine-side host-tier occupancy for the
    sessions handed back — a reused engine would otherwise carry orphaned
    host entries and trip its host-occupancy invariant."""
    from repro.core.session import KVState, Round, make_session
    r = ClusterRouter()
    e1 = _mini_engine()
    r.register("a", e1, now=0.0)
    r.heartbeat("a", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=0.0)
    s = make_session(0.0, [Round(20_000, 16, None, 0.0)], ideal_time=1.0)
    assert r.place(s, now=0.0) == "a"
    now = 0.0
    for _ in range(3):
        el, _ = e1.tick(now)
        now += max(el, 0.05)
    assert s.kv_blocks > 0
    # demote to the host tier, as pin revocation under pressure would
    assert e1._offload_kv(s, now)
    assert e1.host.holds(s.sid) and s.meta.get("host_tier")
    moved = r.leave("a", now=now + 1.0)
    assert s in moved
    assert s.kv_state == KVState.NONE and "host_tier" not in s.meta
    assert not e1.host.holds(s.sid)
    assert e1.host.used_blocks == 0
    e1.check_invariants()


def test_router_failover_cancels_inflight_tools():
    """A session detached mid-tool must not be resumed by the old
    (heartbeat-recovered) replica: its queued/running tool is cancelled,
    so the replica ticking past the tool's end leaves the session — now
    owned by another replica — untouched."""
    from repro.core.session import Phase, Round, make_session
    r = ClusterRouter(RouterConfig(heartbeat_timeout=5.0))
    e1 = _mini_engine()
    r.register("a", e1, now=0.0)
    r.heartbeat("a", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=0.0)
    s = make_session(0.0, [Round(2_000, 8, "t", 50.0),
                           Round(1_000, 8, None, 0.0)], ideal_time=1.0)
    assert r.place(s, now=0.0) == "a"
    now = 0.0
    while s.phase != Phase.TOOL and now < 100.0:
        el, _ = e1.tick(now)
        now += max(el, 0.05)
    assert s.phase == Phase.TOOL
    round_before = s.cur_round
    assert r.check_failures(now=100.0) == ["a"]
    assert s in r.requeued
    # recovered replica ticks past the tool's completion time
    for t in (101.0, 160.0, 200.0):
        e1.tick(t)
    assert s.cur_round == round_before and s.phase == Phase.TOOL
    assert e1.tools.active == 0
    e1.check_invariants()


def test_router_midtool_victim_completes_on_new_replica():
    """A session evacuated mid-tool has already finished its round's decode
    quantum; re-placement must reset the round progress so the new replica
    re-decodes and re-runs the cancelled tool — without the reset the
    session lands in DECODING with decoded == decode_tokens, a 0-token
    quantum no batch picks up and no timer finishes (livelock)."""
    from repro.core.session import Phase, Round, make_session
    r = ClusterRouter(RouterConfig(heartbeat_timeout=5.0))
    e1 = _mini_engine()
    r.register("a", e1, now=0.0)
    r.heartbeat("a", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=0.0)
    s = make_session(0.0, [Round(2_000, 16, "t", 50.0),
                           Round(1_000, 8, None, 0.0)], ideal_time=1.0)
    assert r.place(s, now=0.0) == "a"
    now = 0.0
    while s.phase != Phase.TOOL and now < 100.0:
        el, _ = e1.tick(now)
        now += max(el, 0.05)
    assert s.phase == Phase.TOOL
    assert s.decoded == s.rounds[0].decode_tokens   # quantum complete
    assert r.check_failures(now=100.0) == ["a"]
    assert s in r.requeued
    assert s.decoded == 0 and not s.first_token_seen
    e2 = _mini_engine()
    r.register("b", e2, now=100.0)
    r.heartbeat("b", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=100.0)
    assert r.dispatch_requeued(now=100.0) == 1
    assert r.session_home[s.sid] == "b"
    from repro.engine.engine import run_sim
    finished, _ = run_sim(e2, [], max_time=1e4)
    assert s in finished                            # re-decode + re-run tool
    # per-round TTFT stays one entry per round: the stale entry measured on
    # the dead replica was dropped with the round-progress reset
    assert len(s.ttfts) == len(s.rounds)
    e2.check_invariants()


def test_router_elastic_join_leave():
    r = ClusterRouter()
    e1 = _mini_engine()
    r.register("a", e1, now=0.0)
    r.heartbeat("a", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=0.0)
    from repro.core.session import Round, make_session
    s = make_session(0.0, [Round(1000, 8, None, 0.0)], ideal_time=1.0)
    assert r.place(s, now=0.0) == "a"
    moved = r.leave("a", now=1.0)
    assert s in moved
    assert r.place(s, now=1.0) is None       # no replicas left
    e2 = _mini_engine()
    r.register("b", e2, now=2.0)
    r.heartbeat("b", kv_utilization=0.1, tool_backlog=0, active_sessions=0,
                step_latency=0.1, now=2.0)
    assert r.place(s, now=2.0) == "b"


# ---------------------------------------------------------------------------
# cross-replica prefix reuse (radix digests in heartbeats)
# ---------------------------------------------------------------------------

def _family_session(n_shared=8, n_tail=0, fam="fam", tag=None):
    from repro.core.session import Round, make_session
    s = make_session(0.0, [Round(32 * (n_shared + n_tail), 8, None, 0.0)],
                     ideal_time=1.0)
    s.meta["prefix_hashes"] = [((fam, i), 32) for i in range(n_shared)] + \
        [((tag, i), 32) for i in range(n_tail)]
    return s


def _digest_for(fam="fam", blocks=8, depth=8, hits=0, queries=0):
    from repro.kvcache.radix import chunk_key_digest
    return {"v": 1, "indexed_blocks": blocks, "queries": queries,
            "hits": hits, "hit_tokens": 0,
            "anchors": {chunk_key_digest((fam, 0)): {
                "blocks": blocks, "depth": depth,
                "hits": hits, "queries": queries,
                "hit_rate": hits / max(1, queries)}}}


def _beat(r, rid, *, util=0.1, digest=None, now=0.0):
    r.heartbeat(rid, kv_utilization=util, tool_backlog=0, active_sessions=0,
                step_latency=0.1, radix_digest=digest, now=now)


def test_router_prefix_match_pulls_family_spill_guard_overrides():
    """A replica advertising the session's anchor wins placement despite a
    mild load disadvantage; past the spill threshold the pull is off and
    the family overflows by plain pressure score."""
    r = ClusterRouter(RouterConfig())
    for rid in ("a", "b"):
        r.register(rid, None, now=0.0)
    _beat(r, "a", util=0.05)
    _beat(r, "b", util=0.25, digest=_digest_for())   # warmer but has the prefix
    s = _family_session()
    assert r.place(s, now=0.0) == "b"
    # hot home: same digest, utilization past prefix_spill_kv -> overflow
    s2 = _family_session()
    _beat(r, "b", util=r.cfg.prefix_spill_kv + 0.02, digest=_digest_for())
    assert r.place(s2, now=1.0) == "a"


def test_router_empty_digest_scores_neutrally():
    """No digest, an empty-anchor digest, and a non-matching digest must
    all produce the identical score — digest-blind replicas are never
    penalized (or favored) for what they don't advertise."""
    r = ClusterRouter(RouterConfig())
    r.register("a", None, now=0.0)
    _beat(r, "a", util=0.2)
    s = _family_session()
    ra = r.replicas["a"]
    base = r._score(ra, s)
    _beat(r, "a", util=0.2, digest={"v": 0, "anchors": {}})
    assert r._score(ra, s) == base
    _beat(r, "a", util=0.2, digest=_digest_for(fam="other"))
    assert r._score(ra, s) == base
    # and a session with no prefix metadata is unaffected by a rich digest
    from repro.core.session import Round, make_session
    plain = make_session(0.0, [Round(256, 8, None, 0.0)], ideal_time=1.0)
    _beat(r, "a", util=0.2)
    base_plain = r._score(ra, plain)
    _beat(r, "a", util=0.2, digest=_digest_for())
    assert r._score(ra, plain) == base_plain


def test_router_failure_clears_stale_digest():
    """A failed replica's advertised prefix state died with its pool: the
    digest is invalidated with the failure, so requeued sessions are
    re-placed by load, not by a ghost index."""
    r = ClusterRouter(RouterConfig(heartbeat_timeout=5.0))
    e1, e2 = _mini_engine(), _mini_engine()
    r.register("a", e1, now=0.0)
    r.register("b", e2, now=0.0)
    _beat(r, "a", util=0.1, digest=_digest_for())
    _beat(r, "b", util=0.1)
    s = _family_session()
    assert r.place(s, now=0.0) == "a"
    _beat(r, "b", util=0.1, now=10.0)            # only b stays alive
    assert r.check_failures(now=10.0) == ["a"]
    assert r.replicas["a"].radix_digest is None
    assert s in r.requeued
    assert r.dispatch_requeued(now=10.0) == 1
    assert r.session_home[s.sid] == "b"


def test_router_reregistered_replica_starts_digest_clean():
    r = ClusterRouter(RouterConfig())
    r.register("a", None, now=0.0)
    _beat(r, "a", util=0.1, digest=_digest_for())
    assert r.replicas["a"].radix_digest is not None
    r.leave("a", now=1.0)
    assert "a" not in r.replicas                 # digest gone with the replica
    r.register("a", None, now=2.0)
    assert r.replicas["a"].radix_digest is None
    # an omitted-digest heartbeat keeps it clean (refresh-wholesale)
    _beat(r, "a", util=0.1, now=2.5)
    assert r.replicas["a"].radix_digest is None
    s = _family_session()
    ra = r.replicas["a"]
    assert r._prefix_match_frac(ra, s) == 0.0


def test_router_cluster_prefix_stats_aggregates_alive_digests():
    r = ClusterRouter(RouterConfig(heartbeat_timeout=5.0))
    for rid in ("a", "b", "c"):
        r.register(rid, None, now=0.0)
    _beat(r, "a", digest=_digest_for(fam="f1", hits=3, queries=4))
    _beat(r, "b", digest=_digest_for(fam="f2", hits=1, queries=2))
    _beat(r, "c")                                # digest-blind
    stats = r.cluster_prefix_stats()
    assert set(stats["replicas"]) == {"a", "b"}
    assert stats["cluster_prefix_hits"] == 4
    assert stats["cluster_prefix_queries"] == 6
    assert stats["cluster_prefix_hit_rate"] == pytest.approx(4 / 6)
    # a dead replica's digest leaves the aggregate with the failure
    _beat(r, "b", digest=_digest_for(fam="f2", hits=1, queries=2), now=10.0)
    assert set(r.check_failures(now=10.0)) == {"a", "c"}
    stats = r.cluster_prefix_stats()
    assert set(stats["replicas"]) == {"b"}       # a/c failed
    assert stats["cluster_prefix_hit_rate"] == pytest.approx(1 / 2)


def test_router_digest_placement_co_locates_family_end_to_end():
    """Two live engines behind the router: once the builder's replica
    advertises the family anchor, siblings land there and attach to the
    shared blocks instead of recomputing them."""
    r = ClusterRouter(RouterConfig())
    engines = {"a": _mini_engine(), "b": _mini_engine()}
    for rid, e in engines.items():
        r.register(rid, e, now=0.0)
        _beat(r, rid, util=0.0)
    builder = _family_session(n_shared=16, n_tail=2, tag="t0")
    home = r.place(builder, now=0.0)
    now = 0.0
    for _ in range(8):                           # build + index the prefix
        for rid, e in engines.items():
            el, _ = e.tick(now)
            _beat(r, rid, util=e.telem.kv_utilization,
                  digest=e.radix_digest(), now=now)
            now += max(el, 0.05)
    assert engines[home].radix.inserted_blocks >= 16
    sibs = [_family_session(n_shared=16, n_tail=2, tag=f"t{i+1}")
            for i in range(3)]
    for s in sibs:
        assert r.place(s, now=now) == home
    for _ in range(30):
        el, _ = engines[home].tick(now)
        now += max(el, 0.05)
        if all(s.meta.get("radix_hit") for s in sibs):
            break
    assert all(s.meta.get("radix_hit") for s in sibs)
    assert engines[home].prefix_hit_tokens >= 3 * 16 * 32
    for e in engines.values():
        e.check_invariants()
