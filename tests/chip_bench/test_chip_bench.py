"""CPU checks of the chip benchmark in ``benchmarks/chip`` (no chip needed).

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_bench

Traffic is deterministic per seed and keeps to its clips; every cell finds
its files by name; the trace reduction and the operation/byte counters give
hand-counted answers; the reference agrees with the program's own float32
forward pass; the harness core serves a tiny configuration end to end and
its check passes, and the fp8 control and a step broken underneath fail it;
the measured path refuses a host without a TPU. Every configuration names
an architecture module under ``arch/``; the weights and the served
reference's logits match values pinned before the architecture moved there.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import counts  # noqa: E402
import harness  # noqa: E402
import model as bench_model  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import train  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

TINY = {
    "name": "tiny", "source": "test", "reference": "dense_gqa",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 1024, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "attention_bias": True,
    "hidden_act": "silu", "torch_dtype": "bfloat16", "reduced": [],
    "limits": {"max_logit_gap": 0.05},
    "engine": {"pages": 64, "page_size": 32, "token_budget": 1024,
               "max_decode_batch": 4, "cpu_slots": 2, "policy": "mars"},
}
TINY_CHAT = {
    "name": "tiny-chat", "loop": "closed", "clients": 4, "warm_in_s": 1.0,
    "sizes_seed": 0, "pool": 32, "rounds": [1, 1],
    "prompt_tokens": {"median": 48, "sigma": 0.5, "min": 20, "max": 96},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "sample": {"max_requests": 16, "min_tokens": 60},
    "warm": {"packs": [1, 2], "longest_from": 64},
}


# --- traffic ---------------------------------------------------------------

# an agent mix in the shape of ``repro.workloads.generator.TOOL_KINDS``:
# open-loop sessions of several rounds, each but the last ending in a tool
TINY_AGENT = {
    "name": "tiny-agent", "loop": "open", "sessions_per_s": 2.0,
    "warm_in_s": 1.0, "sizes_seed": 1, "pool": 16, "rounds": [2, 4],
    "prompt_tokens": {"median": 64, "sigma": 0.5, "min": 32, "max": 128},
    "append_tokens": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "tools": {"scale": 0.1, "kinds": {"build": [0.6, 2.0, 0.5, 20.0, 0.4],
                                      "search": [0.9, 1.0, 0.4, 6.0, 0.5]}},
    "sample": {"max_requests": 4, "min_tokens": 20},
    "warm": {"packs": [1], "longest_from": 64},
}
SPECS = {**{p.stem: traffic.load(p.stem)
            for p in (HERE / "traffic").glob("*.json")
            if traffic.load(p.stem).get("kind") != "train"},
         "tiny-agent": TINY_AGENT}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_traffic_deterministic_and_clipped(name):
    spec = SPECS[name]
    a = traffic.requests(spec, 2**40 + 7, 64)
    b = traffic.requests(spec, 2**40 + 7, 64)
    c = traffic.requests(spec, 3, 64)

    def shapes(reqs):
        return [[(r.append, r.output, r.tool_kind, r.tool_s)
                 for r in q.rounds] for q in reqs]

    # every seed has the same work in the same order; the seed draws the
    # token ids only
    assert shapes(a) == shapes(b) == shapes(c)
    assert [q.arrival for q in a] == [q.arrival for q in c]
    assert np.array_equal(a[5].tokens(0, 1000), b[5].tokens(0, 1000))
    assert not np.array_equal(a[5].tokens(0, 1000), c[5].tokens(0, 1000))
    n = spec["pool"]
    assert shapes(a)[n:] == shapes(a)[:64 - n]
    # sizes stand at the quantiles: half the prompts on each side of the
    # median, clipped to the bounds
    d = spec["prompt_tokens"]
    prompts = sorted(q.rounds[0].append for q in a[:n])
    assert prompts[n // 2 - 1] <= d["median"] <= prompts[n // 2]
    lo, hi = spec["rounds"]
    for q in a:
        assert lo <= len(q.rounds) <= hi
        for i, r in enumerate(q.rounds):
            d = spec["prompt_tokens"] if i == 0 else spec["append_tokens"]
            assert d["min"] <= r.append <= d["max"]
            assert (spec["output_tokens"]["min"] <= r.output
                    <= spec["output_tokens"]["max"])
            assert (r.tool_kind is None) == (i == len(q.rounds) - 1)
            assert (r.tool_s > 0) == (r.tool_kind is not None)
        assert q.tokens(0, 1000).min() >= 2
    if spec["loop"] == "open":
        arrivals = [q.arrival for q in a]
        assert arrivals == sorted(arrivals) and arrivals[0] > 0


# --- the benchmark's files -------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_its_files(cell):
    w = run.load_cell(cell, BENCH)
    c = bench_model.load(w["config"])
    spec = traffic.load(w["traffic"])
    cfg = next(x for x in BENCH["configs"] if x["name"] == w["config"])
    assert cfg["file"] == f"benchmarks/chip/configs/{w['config']}.json"
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    _resolves_its_arch(c)
    if spec.get("kind") == "train":
        assert train.opt_config(c).lr > 0
        assert spec["batch"] >= 2 and spec["seq_len"] > 0
        assert set(c["limits"]) == {"loss_gap", "grad_norm_gap",
                                    "change_norm_gap"}
    else:
        assert harness.warm_ticks(c, spec)
    for trace in (False, True):
        ms = run.cell_metrics(BENCH, w, trace)
        assert ms
        for m in ms:
            assert callable(run.reader(m["name"]))
    assert "setup_s" in [m["name"] for m in run.cell_metrics(BENCH, w,
                                                              False)]


ARCH_FUNCTIONS = ("program_config", "layer_weights", "layer_matmul_params",
                  "layer")


def _resolves_its_arch(c):
    a = bench_model.arch(c)
    assert a.__name__ == f"arch.{c['reference']}"
    assert (HERE / "arch" / f"{c['reference']}.py").is_file()
    for fn in ARCH_FUNCTIONS:
        assert callable(getattr(a, fn)), fn
    assert a.program_config(c).n_layers == c["num_hidden_layers"]
    assert a.layer_matmul_params(c) > 0


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (HERE / "configs").glob("*.json")))
def test_every_config_resolves_its_arch(name):
    _resolves_its_arch(bench_model.load(name))


def test_unknown_arch_fails_with_the_path_it_expected():
    c = dict(TINY, name="nowhere", reference="no_such_arch")
    expected = str(HERE / "arch" / "no_such_arch.py")
    with pytest.raises(ValueError, match="no_such_arch") as e:
        bench_model.arch(c)
    assert expected in str(e.value)


def test_device_info_reports_both_peaks_of_the_fullest_chips():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, in_use, reserved):
            self.st = {"peak_bytes_in_use": in_use,
                       "peak_bytes_reserved": reserved}

        def memory_stats(self):
            return self.st
    devs = [Dev(5, 40), Dev(7, 30), Dev(99, 99)]
    info = run.device_info(devs, 2)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 2,
                    "memory_peak_bytes": 7,
                    "memory_reserved_peak_bytes": 40}


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# --- counters --------------------------------------------------------------

def test_counters_match_hand_counts():
    H, Hkv, Dh, L = 4, 2, 16, 2
    f, b = counts.decode_attention(TINY, [10, 3])
    assert f == L * 4 * H * Dh * 13
    assert b == L * 2 * ((2 * Hkv * Dh * 9 + 2 * H * Dh + 2 * Hkv * Dh)
                         + (2 * Hkv * Dh * 2 + 2 * H * Dh + 2 * Hkv * Dh))
    # 3 queries at positions 5, 6, 7 see 6 + 7 + 8 keys
    f, b = counts.prefill_attention(TINY, [(5, 3)])
    assert f == L * 4 * H * Dh * 21
    assert b == L * 2 * (2 * Hkv * Dh * 8 + 2 * H * Dh * 3)
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert bench_model.arch(TINY).layer_matmul_params(TINY) == per_layer
    flops = counts.step_flops(TINY, [(5, 3)], [10])
    assert flops == (2 * L * per_layer * 4 + 2 * 64 * TINY["vocab_size"] * 2
                     + L * 4 * H * Dh * (21 + 10))


# --- trace reduction -------------------------------------------------------

def _ev(name, start, dur):
    return trace_reduce.Event(name, start, dur)


def test_trace_reduction_known_answers():
    ops = [_ev("fusion", 0.0, 1.0), _ev("paged_attention_fused.5", 0.5, 1.0),
           _ev("copy", 3.0, 1.0), _ev("while.2", 3.5, 0.2)]
    assert trace_reduce.union([(0, 1), (0.5, 1.5), (3, 4)]) == [(0, 1.5),
                                                                 (3, 4)]
    assert trace_reduce.busy_seconds(ops) == pytest.approx(2.5)
    assert trace_reduce.idle_gaps(ops, 0.0, 5.0) == [(1.5, 3.0), (4.0, 5.0)]
    assert [e.name for e in trace_reduce.matching(
        ops, ("paged_attention_fused",))] == ["paged_attention_fused.5"]
    assert [trace_reduce.is_container(e) for e in ops] == [False, False,
                                                           False, True]
    assert trace_reduce.short_name(
        "%while.2 = (s32[]) while(%t), body=%b") == "while.2"
    host = [_ev("bench.tick", 1.4, 1.5), _ev("bench.run_batch", 2.5, 0.4)]
    assert trace_reduce.label_gap((1.5, 3.0), host).startswith("tick host")
    assert trace_reduce.label_gap((4.0, 5.0), host).startswith("between")


def test_roofline_and_mfu_readers():
    from metrics import _common, paged_decode_roofline, step_mfu
    pk = peaks.peaks("TPU v5 lite")
    # 819e6 bytes take 1 ms at peak: 2 ms of kernel time is 50 %
    assert _common.roofline(1.0, 819e6, 2e-3, pk) == pytest.approx(50.0)
    assert _common.roofline(197e9, 0.0, 4e-3, pk) == pytest.approx(25.0)
    assert _common.roofline(0.0, 0.0, 1.0, pk) is None
    tick = harness.TickRec(0.0, 1.0, packs=((0, 3),), lanes=(10, 3))
    w = type("W", (), {"c": TINY})()
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [_ev("paged_attention_fused.3", 0.0, 1e-6)]},
        modules={"/device:TPU:0": [_ev("jit__mixed", 0.0, 2e-6)]}, host=[])
    ctx = {"trace": {"ticks": [tick]}, "trace_data": tr, "peaks": pk}
    f, b = counts.decode_attention(TINY, [10, 3])
    assert paged_decode_roofline.read(w, ctx) == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 1e-6)
    flops = counts.step_flops(TINY, [(0, 3)], [10, 3])
    assert step_mfu.read(w, ctx) == pytest.approx(
        100 * flops / 197e12 / 2e-6)
    assert step_mfu.read(w, {"trace": None, "peaks": pk}) is None


# --- reference -------------------------------------------------------------

def test_layer_weights_redraw_exactly():
    import jax
    p = bench_model.make_params(TINY, 2**33 + 5)
    key = bench_model.root_key(2**33 + 5)
    one = reference.layer_getter(TINY)(key, 1)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(
            jax.tree.map(lambda x: x[1], p["layers"]))):
        assert np.array_equal(np.asarray(a), np.asarray(b, np.float32))


def test_reference_matches_program_forward_in_f32():
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import lm_forward
    seed = 99
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     bench_model.make_params(TINY, seed))
    cfg = bench_model.arch(TINY).program_config(TINY)
    toks = np.random.default_rng(0).integers(2, 256, 70).astype(np.int32)
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        prog = np.asarray(lm_forward(cfg, p, jnp.asarray(toks[None]))[0])
    finally:
        jax.config.update("jax_default_matmul_precision", old)
    pos = np.array([0, 31, 69])
    ref = reference.logits_at(TINY, seed, [toks], [pos])[None][0]
    assert np.max(np.abs(prog[pos] - ref)) < 2e-4
    assert reference.widest_gap(ref, ref.argmax(-1)) == 0.0


# Pinned on the tree before the architecture moved into ``arch/``
# (``dense_gqa``): the weights must come out bit for bit, the served
# reference's logits within 1e-6 of each row's largest.
WEIGHT_DIGESTS = {
    ("float32", True):
        "eb7ae37d496ad11f0eb6ca49755ca5ffd477c55bf56a6d9479f581b7404d2ff0",
    ("float32", False):
        "c96c0355cd013077eaf50f7716cca935c6aecb18c330d12e53437e2a42dcfb99",
    ("bfloat16", True):
        "d0268ec568ad30f3497beab2f6235904e11024559b212ee64dc161be5aeca717",
    ("bfloat16", False):
        "fee233a1f875412a3bbe213518b972169cadaeeb065c0e92f443556c5af27b08",
}
LOGIT_COLUMNS = [0, 1, 7, 100, 500, 777, 1023]
# (values at LOGIT_COLUMNS, largest magnitude, norm) at positions 0, 31, 69
PINNED_LOGITS = {
    None: [
        ([-1.4404207468032837, -0.03387024998664856, -0.31965988874435425,
          0.004857867956161499, -0.1966381072998047, 0.5339019894599915,
          -0.10468155145645142], 2.9799156188964844, 31.36590003967285),
        ([-0.8426394462585449, 1.2275207042694092, 0.2518796920776367,
          0.36710724234580994, -2.3695573806762695, 1.4645558595657349,
          1.2374111413955688], 3.1048357486724854, 33.04469680786133),
        ([-0.11119922995567322, -0.5495830178260803, 1.994350552558899,
          2.0797977447509766, -1.6015658378601074, 0.8254010677337646,
          0.1128140315413475], 3.35341739654541, 33.160640716552734)],
    "fp8": [
        ([-1.4081964492797852, -0.03934553265571594, -0.3442038595676422,
          -0.06531476974487305, -0.2839580774307251, 0.5901947021484375,
          -0.11226196587085724], 2.9604601860046387, 31.300485610961914),
        ([-0.9019074440002441, 1.2323389053344727, 0.3663560152053833,
          0.3933665156364441, -2.2445452213287354, 1.4740355014801025,
          1.21414315700531], 3.0731983184814453, 32.791221618652344),
        ([-0.003672271966934204, -0.572869062423706, 2.193364143371582,
          2.1878859996795654, -1.4293105602264404, 0.7815207242965698,
          0.1338692605495453], 3.2449893951416016, 33.75417709350586)],
}


def tree_digest(tree):
    """sha256 over every leaf's path, dtype, shape and bytes."""
    import hashlib
    import jax
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype,tied", sorted(WEIGHT_DIGESTS))
def test_weights_match_their_pinned_digest(dtype, tied):
    import jax.numpy as jnp
    if tied:
        c, seed = TINY, 2**33 + 5
    else:
        c = dict(TINY, tie_word_embeddings=False, attention_bias=False)
        seed = 7
    p = bench_model.make_params(c, seed, jnp.dtype(dtype))
    assert tree_digest(p) == WEIGHT_DIGESTS[dtype, tied]


def test_served_reference_logits_match_their_pinned_values():
    toks = np.random.default_rng(0).integers(2, 256, 70).astype(np.int32)
    out = reference.logits_at(TINY, 99, [toks], [np.array([0, 31, 69])],
                              (None, "fp8"))
    for q, rows in PINNED_LOGITS.items():
        for got, (vals, top, norm) in zip(out[q][0], rows):
            assert np.max(np.abs(got[LOGIT_COLUMNS] - vals)) <= 1e-6 * top
            assert abs(np.max(np.abs(got)) - top) <= 1e-6 * top
            assert abs(np.linalg.norm(got) - norm) <= 1e-6 * norm


# --- the harness core at a tiny size ---------------------------------------

class _Device:
    """Stands in for the chip in ``run.measure``: the look for a TPU is
    skipped, the rest of the run is driven on the CPU."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def _measure(seed, fault=None, seconds=4.0):
    """``run.measure`` of the tiny cell; ``fault`` wraps the fused step
    that the backend's step program calls."""
    import argparse
    import jax
    from unittest import mock
    from repro.engine import jax_runner
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    cell = {"name": "tiny.tiny-chat", "chips": 1}
    step = jax_runner.lm_mixed_paged
    with mock.patch.object(jax_runner, "lm_mixed_paged",
                           fault(step) if fault else step):
        return run.measure(args, cell, TINY, TINY_CHAT, jax, [_Device()],
                           harness.CompileLog(jax))


class _Clock:
    """Advances 5 ms at each reading, so a tiny run does the same work
    however busy the machine is."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 5e-3
        return self.t


def _window(seed, seconds=2.5):
    import jax
    system = harness.System(TINY, seed)
    system.warm(harness.warm_ticks(TINY, TINY_CHAT))
    loop = harness.Loop(system, TINY_CHAT, seed, clock=_Clock())
    loop.run_until(loop.base + TINY_CHAT["warm_in_s"])
    w = harness.run_window(loop, seconds, harness.CompileLog(jax))
    loop.restore()
    system.close()
    return w


def test_harness_serves_tiny_config():
    from metrics import (itl_mean_ms, itl_p50_ms, itl_p99_ms,
                         output_tok_per_s, ttft_mean_s)
    w = _window(5)
    attempted, failed = harness.counts(w)
    assert attempted > 0 and failed == 0
    sample = harness.sample_for_check(w, 5)
    assert sample
    for rec in sample:
        seq, pos, toks, complete = harness.check_sequence(
            rec, TINY["vocab_size"])
        assert complete
        # each served token is compared at the position before it
        assert len(toks) == rec.req.rounds[0].output
        assert pos[0] == rec.req.rounds[0].append - 1
        assert np.array_equal(seq[pos[1:]], toks[:-1])
    for m in (itl_mean_ms, itl_p50_ms, itl_p99_ms, output_tok_per_s,
              ttft_mean_s):
        assert m.read(w, {}) > 0
    assert itl_p50_ms.read(w, {}) <= itl_p99_ms.read(w, {})


def _greedy(seed, rec):
    """The reference's own greedy tokens for each round of ``rec``."""
    ids = list(rec.req.tokens(0, TINY["vocab_size"]))
    out = []
    for _ in range(rec.req.rounds[0].output):
        seq = np.asarray(ids, np.int32)
        lg = reference.logits_at(TINY, seed, [seq],
                                 [np.array([len(ids) - 1])])[None][0]
        ids.append(int(lg[0].argmax()))
        out.append(ids[-1])
    return out


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_check_passes_the_reference_and_fails_its_fp8_control(seed):
    """Served tokens that are the reference's own greedy choice pass the
    check; the control (the reference in fp8, read at the same positions
    and judged by the same checks and limit) fails it."""
    w = _window(seed)
    for rec in harness.sample_for_check(w, seed):
        rec.rounds[0].tokens = _greedy(seed, rec)
    checks, _sample = run.check(TINY, w, seed, controls=("fp8",))
    assert run.checks_pass(checks["served"]), checks
    assert checks["served"]["max_logit_gap"]["value"] == 0.0
    assert not run.checks_pass(checks["fp8"]), checks


def _alter_token(step):
    def fused(*a):
        p_next, d_next, cache = step(*a)
        return p_next, (d_next + 1) % TINY["vocab_size"], cache
    return fused


def _state_unchanged(step):
    def fused(cfg, params, cache, *a):
        p_next, d_next, _ = step(cfg, params, cache, *a)
        return p_next, d_next, cache
    return fused


def _half_batch(step):
    def fused(*a):
        p_next, d_next, cache = step(*a)
        half = d_next.shape[0] // 2
        return p_next, d_next.at[half:].set(0), cache
    return fused


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch])
def test_run_with_a_broken_step_is_not_correct(fault):
    result = _measure(5, fault)
    assert result["correct"] is False, result["checks"]
    assert list(result)[-1] == "checks"


def test_measured_path_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", cell, "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_fp8_rounding_matches_the_format():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    x = (rng.normal(size=50000) * np.exp(rng.uniform(-12, 6, 50000)))
    x = np.clip(x, -448, 448).astype(np.float32)
    ours = np.asarray(reference._e4m3(jnp, jnp.asarray(x)))
    cast = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))
    assert np.array_equal(ours, cast)
