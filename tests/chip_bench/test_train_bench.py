"""CPU checks of the chip benchmark's training cells (no chip needed).

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_bench

Batches are deterministic per seed and differ row by row; the operation
count and the trace reduction give hand-counted answers; at a tiny size
the program's train step agrees with the float32 reference, its three-pass
control fails the check, and a run driven through ``train.measure`` with
the step broken underneath (state unchanged, half of the batch left out)
reads ``correct`` false.
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


def _measure(seed, wrap=None):
    import jax
    args = argparse.Namespace(seed=seed, seconds=1.5, trace=0)
    cell = {"name": "tiny.tiny-train", "chips": 1}
    return train.measure(args, cell, TINY, JOB, [_Device()],
                         harness.CompileLog(jax), run, BENCH, wrap=wrap)


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
