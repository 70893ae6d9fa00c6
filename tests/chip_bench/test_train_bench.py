"""CPU checks of the chip benchmark's training cells (no chip needed).

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_bench

Batches are deterministic per seed and differ row by row; the operation
count and the trace reduction give hand-counted answers; at a tiny size
the program's train step agrees with the float32 reference, its three-pass
control fails the check, and a run driven through ``train.measure`` with
the step broken underneath (state unchanged, half of the batch left out)
reads ``correct`` false. The reference's readings match a digest pinned
before the architecture moved into ``arch/``; an architecture module in a
new file elsewhere is enough for a correct run; a traced run reads the
layer-scope split into its four metrics; and a new part scope and a new
step output reach new metric readers with no benchmark file edited.
"""
import argparse
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import calibrate_train  # noqa: E402
import counts  # noqa: E402
import harness  # noqa: E402
import model as bench_model  # noqa: E402
import peaks  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import train  # noqa: E402
import train_reference  # noqa: E402

TINY = {
    "name": "tiny", "source": "test", "reference": "dense_gqa",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 1024, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "attention_bias": True,
    "hidden_act": "silu", "torch_dtype": "bfloat16", "reduced": [],
    "train": {"param_dtype": "float32", "matmul_precision": "highest",
              "remat": True,
              "optimizer": {"lr": 3e-4, "betas": [0.9, 0.95], "eps": 1e-8,
                            "weight_decay": 0.1, "warmup_steps": 4,
                            "total_steps": 10000, "grad_clip": 1.0}},
    # CPU readings at this size (seeds 1 and 2**33 + 7): the program's gaps
    # are at most 1.3e-7 / 6.0e-7 / 1.6e-6, the three-pass control's at
    # least 1.3e-7 / 2.2e-6 / 8.7e-6; the control fails on the gradient and
    # change norms
    "limits": {"loss_gap": 1e-5, "grad_norm_gap": 1.5e-6,
               "change_norm_gap": 4e-6},
}
JOB = {"name": "tiny-train", "kind": "train", "batch": 4, "seq_len": 64,
       "ahead_s": 0.5}


def test_batches_deterministic_and_distinct():
    a = train.batch(JOB, 1024, 2**40 + 3, 5)
    b = train.batch(JOB, 1024, 2**40 + 3, 5)
    c = train.batch(JOB, 1024, 2**40 + 3, 6)
    d = train.batch(JOB, 1024, 7, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    toks, tgts = a
    assert toks.shape == tgts.shape == (4, 64) and toks.dtype == np.int32
    assert np.array_equal(toks[:, 1:], tgts[:, :-1])
    assert 0 <= toks.min() and toks.max() < 1024
    rows = [r.tobytes() for x in (a, c, d) for r in x[0]]
    assert len(set(rows)) == len(rows)


def test_train_flops_hand_count():
    # tiny: 2 layers of 64*64 + 2*64*32 + 64*64 + 3*64*128 = 36,864 weights
    # in products, a 64 x 1024 head; 4 rows of 64 tokens
    dense = 2.0 * (2 * 36864 + 64 * 1024) * 256
    attn = 4.0 * 4 * 16 * (4 * 64 * 65 / 2) * 2
    assert counts.train_step_flops(TINY, 4, 64) == pytest.approx(
        3 * (dense + attn))


def _ev(name, start, dur):
    return trace_reduce.Event(name, start, dur)


def test_train_trace_reduction_and_readers():
    from metrics import (train_dispatch_ms, train_idle_frac, train_mfu,
                         train_tokens_per_s)
    span = train.STEP_SPAN
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [_ev("fusion.1", 0.2, 0.5),
                               _ev("fusion.2", 0.9, 0.3),
                               _ev("while.3", 0.2, 1.0)]},
        modules={"/device:TPU:0": [_ev("jit_train_step", 0.2, 0.4),
                                   _ev("jit_train_step", 0.8, 0.4),
                                   _ev("jit_other", 0.0, 0.1)]},
        host=[_ev(span, 0.0, 0.1), _ev(span, 0.75, 0.1)])
    dev_t, breakdown, mods = train.reduce_trace(tr)
    # window 0.0 .. 1.2; busy 0.2 .. 1.2 less nothing (the loop covers it)
    assert dev_t["window_s"] == pytest.approx(1.2)
    assert dev_t["busy_s"] == pytest.approx(1.0)
    assert mods == {"seconds": pytest.approx(0.8), "count": 2}
    assert breakdown["device_ops"][0] == ["fusion.1", 0.5]
    assert [g[1] for g in breakdown["idle_gaps"]] == [pytest.approx(0.2)]
    pk = peaks.peaks("TPU v5 lite")
    w = train.TrainWindow(TINY, JOB, t_open=1.0, t_close=3.0, steps=10,
                          dispatch_s=0.05)
    ctx = {"device_time": dev_t, "step_modules": mods, "peaks": pk}
    assert train_tokens_per_s.read(w, ctx) == pytest.approx(10 * 256 / 2.0)
    assert train_dispatch_ms.read(w, ctx) == pytest.approx(5.0)
    assert train_idle_frac.read(w, ctx) == pytest.approx(0.2 / 1.2)
    assert train_mfu.read(w, ctx) == pytest.approx(
        100 * 2 * counts.train_step_flops(TINY, 4, 64) / 197e12 / 0.8)
    assert train_mfu.read(w, {"peaks": pk}) is None


@pytest.fixture(scope="module")
def references():
    return {seed: train_reference.readings(TINY, JOB, seed)
            for seed in (1, 2**33 + 7)}


# pinned on the tree before the architecture moved into ``arch/``
READINGS_DIGEST = (
    "b7f849a042fa612f2b470cc0640e9b088b3e5225ae378d6c95fd7e13e9cdb4fd")


def test_reference_readings_match_their_pinned_digest(references):
    """The reference that decides ``correct`` does the same arithmetic, bit
    for bit: losses, gradient and change norms of seed 1."""
    import hashlib
    r = references[1]
    h = hashlib.sha256()
    for a in (np.asarray(r["loss"], np.float64), r["grad"], r["change"]):
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == READINGS_DIGEST


@pytest.mark.parametrize("seed", [1, 2**33 + 7])
def test_program_passes_and_the_control_fails(seed, references):
    """The program's first steps agree with the float32 reference; the
    reference in three-pass bf16, in the program's place, fails."""
    ref = references[seed]
    tr = train.Trainer(TINY, JOB)
    tr.start(seed)
    got = tr.check_steps()
    ch = train.checks(TINY, got, ref)
    assert train.checks_pass(ch), ch
    assert train.leaf_mask(ref).all()
    ctl = train.checks(TINY, train_reference.readings(TINY, JOB, seed,
                                                      "high"), ref)
    assert not train.checks_pass(ctl), ctl


class _Device:
    """Stands in for the chip in ``train.measure``: the look for a TPU is
    skipped, the rest of the run is driven on the CPU."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


BENCH = {"end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}


def _measure(seed, wrap=None, c=TINY, trace=0, bench=BENCH):
    import jax
    args = argparse.Namespace(seed=seed, seconds=1.5, trace=trace)
    cell = {"name": f"{c['name']}.tiny-train", "chips": 1}
    return train.measure(args, cell, c, JOB, [_Device()],
                         harness.CompileLog(jax), run, bench, wrap=wrap)


def test_train_run_is_correct_and_reports_its_metrics():
    result = _measure(3)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "checks"


def _state_unchanged(fn):
    def step(params, opt_state, batch_):
        _p, _o, m = fn(params, opt_state, batch_)
        return params, opt_state, m
    return step


@pytest.mark.parametrize("fault", [_state_unchanged,
                                   calibrate_train.half_batch])
def test_train_run_with_a_broken_step_is_not_correct(fault):
    result = _measure(3, wrap=fault)
    assert result["correct"] is False, result["checks"]


def test_a_new_architecture_needs_only_new_files(tmp_path, monkeypatch):
    """An architecture module under a new name, outside the benchmark's
    files, and a configuration that names it: a whole run is correct."""
    (tmp_path / "arch").mkdir()
    (tmp_path / "arch" / "dense_gqa_elsewhere.py").write_text(
        "from arch.dense_gqa import (layer, layer_matmul_params,\n"
        "                            layer_weights, program_config)\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    c = dict(TINY, name="tiny-elsewhere", reference="dense_gqa_elsewhere")
    assert Path(bench_model.arch(c).__file__).parent == tmp_path / "arch"
    result = _measure(3, c=c)      # the seed of the dense run above
    assert result["correct"] is True, result["checks"]


SCOPE_METRICS = ("train_vocab_ms", "train_attn_ms", "train_mlp_ms",
                 "train_adamw_ms")


def test_traced_run_reads_the_scope_split_of_the_compiled_step(
        tmp_path, monkeypatch):
    """With ``--trace 1`` the run splits the traced steps by the scopes
    named in the compiled step's HLO, compiling nothing for it, and the
    four scope metrics read that split. (A CPU trace has no device ops, so
    the split itself is stood in for.)"""
    import jax
    import scopes
    split = {("embed", "", "forward"): 1e-3, ("head", "", "backward"): 2e-3,
             ("attn", "", "forward"): 3e-3, ("mlp", "", "backward"): 4e-3,
             ("adamw", "", "forward"): 5e-3,
             ("unscoped", "", "forward"): 6e-3}
    seen = {}
    log = harness.CompileLog(jax)
    step_hlo = scopes.step_hlo

    def hlo(tr):
        n0 = log.programs
        text = step_hlo(tr)
        seen["compiles"] = log.programs - n0
        return text

    def by_scope(trace, op_names):
        seen["op_names"] = op_names
        return split
    monkeypatch.setattr(scopes, "step_hlo", hlo)
    monkeypatch.setattr(scopes, "by_scope", by_scope)
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    bench = {"end_to_end": [], "per_layer": [
        {"name": n, "unit": "ms"} for n in SCOPE_METRICS]}
    result = _measure(6, trace=1, bench=bench)
    assert result["correct"] is True, result["checks"]
    assert seen["compiles"] == 0
    found = {scopes.scope_of(v)[0] for v in seen["op_names"].values()}
    assert set(scopes.LAYERS) <= found
    assert {k: v["value"] for k, v in result["metrics"].items()} == {
        "train_vocab_ms": pytest.approx(3.0),
        "train_attn_ms": pytest.approx(3.0),
        "train_mlp_ms": pytest.approx(4.0),
        "train_adamw_ms": pytest.approx(5.0)}


READER_SPLIT = {
    ("embed", "", "forward"): 0.001, ("head", "", "backward"): 0.010,
    ("loss", "", "forward"): 0.002, ("attn", "", "backward"): 0.004,
    ("attn", "", "recompute"): 0.003, ("mlp", "", "forward"): 0.020,
    ("mlp", "", "backward"): 0.030, ("mlp", "", "recompute"): 0.0005,
    ("adamw", "", "forward"): 0.005, ("unscoped", "", "forward"): 0.5}


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_readers(name):
    import importlib
    import scopes
    read = importlib.import_module(f"metrics.{name}").read
    want = {"train_vocab_ms": 13.0, "train_attn_ms": 7.0,
            "train_mlp_ms": 50.5, "train_adamw_ms": 5.0}[name]
    assert read(None, {"scope_split": READER_SPLIT}) == pytest.approx(want)
    assert read(None, {"scope_split": READER_SPLIT}) == pytest.approx(
        scopes.group_ms(READER_SPLIT)[name])
    assert read(None, {}) is None
    assert read(None, {"scope_split": None}) is None
    assert read(None, {"scope_split": {("unscoped", "", "forward"): 0.5}}
                ) is None


def test_recompute_reader():
    from metrics import train_recompute_ms
    read = train_recompute_ms.read
    assert read(None, {"scope_split": READER_SPLIT}) == pytest.approx(3.5)
    assert read(None, {}) is None
    assert read(None, {"scope_split": None}) is None
    assert read(None, {"scope_split": {("attn", "", "backward"): 0.5}}
                ) is None


def test_step_counters_are_the_mean_of_each_scalar_but_the_loss():
    outs = [{"loss": 5.0, "grad_norm": np.float32(2.0), "lr": 1e-4,
             "hist": np.ones(3)},
            {"loss": 4.0, "grad_norm": np.float32(4.0), "lr": 2e-4,
             "hist": np.ones(3)}]
    assert train.step_counters(outs) == {"grad_norm": pytest.approx(3.0),
                                         "lr": pytest.approx(1.5e-4)}
    assert train.step_counters([]) == {}


def test_a_new_part_scope_and_counter_need_only_new_files(
        tmp_path, monkeypatch, capsys):
    """A stand-in program change (the MLP's body under a part scope that
    the program declares in ``PARTS``, and one more scalar among the
    step's outputs), an architecture module and two metric readers in a
    new directory: a traced run is correct and reports both readings. The
    compiled step's HLO names the part under ``mlp``. (A CPU trace has no
    device ops, so the split is stood in for: a millisecond for each
    instruction of the compiled step, by its ``op_name``.)"""
    import jax
    import jax.numpy as jnp
    import scopes
    from repro.distributed import steps as program_steps
    from repro.models import transformer
    from repro.obs import scopes as program_scopes

    monkeypatch.setattr(program_scopes, "PARTS", ("experts",), raising=False)
    mlp = transformer.mlp

    def mlp_in_part(*a, **k):
        with jax.named_scope("experts"):
            return mlp(*a, **k)
    monkeypatch.setattr(transformer, "mlp", mlp_in_part)
    build = program_steps.build_train_step

    def build_with_counter(*a, **k):
        step = build(*a, **k)

        def counted(params, opt_state, batch_):
            params, opt_state, m = step(params, opt_state, batch_)
            m["tokens_routed"] = jnp.asarray(batch_["tokens"].size,
                                             jnp.float32)
            return params, opt_state, m
        return counted
    monkeypatch.setattr(program_steps, "build_train_step", build_with_counter)

    (tmp_path / "arch").mkdir()
    (tmp_path / "arch" / "dense_gqa_parts.py").write_text(
        "from arch.dense_gqa import (layer, layer_matmul_params,\n"
        "                            layer_weights, program_config)\n")
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "expert_part_ms.py").write_text(
        "from metrics._common import split_ms\n\n\n"
        "def read(w, ctx):\n"
        "    return split_ms(ctx, \"mlp\", \"experts\")\n")
    (tmp_path / "metrics" / "tokens_routed.py").write_text(
        "def read(w, ctx):\n"
        "    return ctx.get(\"step_counters\", {}).get(\"tokens_routed\")\n")
    monkeypatch.syspath_prepend(str(tmp_path))

    seen = {}

    def by_scope(trace, op_names):
        seen["keys"] = keys = [scopes.scope_of(v) for v in op_names.values()]
        out = {}
        for k in keys:
            out[k] = out.get(k, 0.0) + 1e-3
        return out
    monkeypatch.setattr(scopes, "by_scope", by_scope)
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    c = dict(TINY, name="tiny-parts", reference="dense_gqa_parts")
    bench = {"end_to_end": [], "per_layer": [
        {"name": "expert_part_ms", "unit": "ms"},
        {"name": "tokens_routed", "unit": "tokens"}]}
    result = _measure(3, c=c, trace=1, bench=bench)
    assert result["correct"] is True, result["checks"]
    n_part = sum(1 for k in seen["keys"] if k[:2] == ("mlp", "experts"))
    assert {ph for sc, pt, ph in seen["keys"] if pt == "experts"} == {
        "forward", "backward", "recompute"}
    assert {sc for sc, pt, _ in seen["keys"] if pt} == {"mlp"}
    assert {k: v["value"] for k, v in result["metrics"].items()} == {
        "expert_part_ms": pytest.approx(float(n_part)),
        "tokens_routed": pytest.approx(JOB["batch"] * JOB["seq_len"])}
    line = next(ln for ln in capsys.readouterr().err.splitlines()
                if "step_counters" in ln)
    assert all(k in line for k in ("grad_norm", "lr", "tokens_routed"))
    assert "loss" not in line
