"""CPU checks of ``benchmarks/chip/scopes.py``, the train step's device
time by the program's layer scopes (no chip needed).

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_bench/test_scope_split.py
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import scopes  # noqa: E402
import trace_reduce  # noqa: E402
import train  # noqa: E402

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
}
JOB = {"name": "tiny-train", "kind": "train", "batch": 2, "seq_len": 32,
       "ahead_s": 0.5}


def test_layer_names_are_the_programs():
    from repro.obs import scopes as program_scopes
    assert scopes.LAYERS is program_scopes.LAYERS
    assert scopes.parts() == tuple(getattr(program_scopes, "PARTS", ()))
    assert {n for g in scopes.GROUPS.values() for n in g} <= set(
        program_scopes.LAYERS)


@pytest.mark.parametrize("op_name,expect", [
    ("jit(train_step)/jvp(embed)/gather", ("embed", "", "forward")),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "mlp/add_any", ("mlp", "", "backward")),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/dot_general", ("attn", "", "recompute")),
    ("jit(train_step)/transpose(jvp(head))/dot_general",
     ("head", "", "backward")),
    ("jit(train_step)/jvp(head)/dot_general;jit(train_step)/jvp(loss)/exp",
     ("loss", "", "forward")),
    ("jit(train_step)/adamw/mul", ("adamw", "", "forward")),
    ("jit(_mixed)/decode/while/body/closed_call/mlp/dot_general",
     ("mlp", "", "forward")),
    ("jit(train_step)/jvp(loss_fn)/mlp_fused/embedding",
     ("unscoped", "", "forward")),
    ("jit(train_step)/jvp(mlp)/experts/dot_general", ("mlp", "", "forward")),
    ("", ("unscoped", "", "forward")),
])
def test_scope_of(op_name, expect):
    assert scopes.scope_of(op_name) == expect


PARTS = ("route", "experts")


@pytest.mark.parametrize("op_name,expect", [
    ("jit(train_step)/jvp(mlp)/experts/dot_general",
     ("mlp", "experts", "forward")),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "mlp/experts/dot_general", ("mlp", "experts", "backward")),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/experts/dot_general",
     ("mlp", "experts", "recompute")),
    ("jit(train_step)/jvp(mlp)/route/experts/sort", ("mlp", "experts",
                                                    "forward")),
    ("jit(train_step)/jvp(mlp)/route/argsort", ("mlp", "route", "forward")),
    ("jit(train_step)/jvp(mlp)/dot_general", ("mlp", "", "forward")),
    # a part's name before any layer's is not a part of that layer
    ("jit(train_step)/experts/jvp(attn)/dot_general",
     ("attn", "", "forward")),
    ("jit(train_step)/experts/dot_general", ("unscoped", "", "forward")),
    ("jit(train_step)/jvp(mlp)/experts_fused/dot_general",
     ("mlp", "", "forward")),
])
def test_scope_of_parts(op_name, expect, monkeypatch):
    from repro.obs import scopes as program_scopes
    monkeypatch.setattr(program_scopes, "PARTS", PARTS, raising=False)
    assert scopes.parts() == PARTS
    assert scopes.scope_of(op_name) == expect


def _ev(name, start, dur):
    return trace_reduce.Event(name, start, dur)


def test_by_scope_sums_ops_inside_step_programs_per_step():
    names = {"fusion.1": "jit(train_step)/jvp(embed)/gather",
             "fusion.2": "jit(train_step)/transpose(jvp(head))/dot_general",
             "fusion.3": "jit(train_step)/adamw/sub",
             "copy.4": "jit(train_step)/while/body/copy",
             "fusion.5": "jit(other)/mlp/dot_general"}
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [
            _ev("fusion.1", 0.00, 0.10),      # step 1
            _ev("while.9", 0.10, 0.30),       # container: skipped
            _ev("fusion.2", 0.10, 0.20),
            _ev("fusion.3", 0.30, 0.05),
            _ev("copy.4", 0.35, 0.01),        # no layer: unscoped
            _ev("fusion.6", 0.36, 0.02),      # not in the map: unscoped
            _ev("fusion.5", 0.45, 0.03),      # in another program
            _ev("fusion.1", 0.50, 0.10),      # step 2
            _ev("fusion.2", 0.60, 0.20),
            _ev("fusion.3", 0.80, 0.05),
            _ev("fusion.1", 1.20, 0.10)]},    # after the traced steps
        modules={"/device:TPU:0": [_ev("jit_train_step", 0.0, 0.40),
                                   _ev("jit_other", 0.44, 0.05),
                                   _ev("jit_train_step", 0.5, 0.40)]},
        host=[])
    split = scopes.by_scope(tr, names)
    assert split == {
        ("embed", "", "forward"): pytest.approx(0.10),
        ("head", "", "backward"): pytest.approx(0.20),
        ("adamw", "", "forward"): pytest.approx(0.05),
        ("unscoped", "", "forward"): pytest.approx(0.03 / 2),
    }
    groups = scopes.group_ms(split)
    assert groups == {"train_vocab_ms": pytest.approx(300.0),
                      "train_attn_ms": 0.0, "train_mlp_ms": 0.0,
                      "train_adamw_ms": pytest.approx(50.0)}
    tr.modules = {"/device:TPU:0": [_ev("jit_other", 0.44, 0.05)]}
    assert scopes.by_scope(tr, names) is None


SPLIT_WITH_PARTS = {("mlp", "experts", "forward"): 0.010,
                    ("mlp", "route", "forward"): 0.002,
                    ("mlp", "", "forward"): 0.001,
                    ("mlp", "experts", "recompute"): 0.004,
                    ("mlp", "experts", "backward"): 0.020,
                    ("attn", "", "recompute"): 0.003,
                    ("attn", "", "backward"): 0.006,
                    ("head", "", "forward"): 0.030,
                    ("loss", "", "forward"): 0.001,
                    ("adamw", "", "forward"): 0.005,
                    ("unscoped", "", "forward"): 0.007}


def test_group_ms_sums_every_part_and_phase():
    """A split with parts and a recompute phase gives the four groups the
    numbers of the same split with neither."""
    flat = {}
    for (sc, _, ph), s in SPLIT_WITH_PARTS.items():
        ph = "backward" if ph == "recompute" else ph
        flat[(sc, "", ph)] = flat.get((sc, "", ph), 0.0) + s
    assert scopes.group_ms(SPLIT_WITH_PARTS) == pytest.approx(
        scopes.group_ms(flat))
    assert scopes.group_ms(SPLIT_WITH_PARTS) == pytest.approx(
        {"train_vocab_ms": 31.0, "train_attn_ms": 9.0,
         "train_mlp_ms": 37.0, "train_adamw_ms": 5.0})


@pytest.mark.parametrize("scope,part,phase,expect", [
    (None, None, "recompute", 7.0),
    ("mlp", None, None, 37.0),
    ("mlp", "experts", None, 34.0),
    ("mlp", "experts", "forward", 10.0),
    ("mlp", "", None, 1.0),
    ("attn", None, "backward", 6.0),
    ("unscoped", None, None, 7.0),
    (None, None, None, 89.0),
    ("embed", None, None, None),
    ("attn", "experts", None, None),
])
def test_layer_ms(scope, part, phase, expect):
    got = scopes.layer_ms(SPLIT_WITH_PARTS, scope, part, phase)
    assert got == (None if expect is None else pytest.approx(expect))


def test_op_names_from_the_compiled_train_step():
    """The program's compiled step, read as the chip run reads it where the
    trace carries no ``op_name``: every layer's name is found, and the
    layers' ``jax.checkpoint`` regions recompute some of their work."""
    tr = train.Trainer(TINY, JOB)
    tr.start(5)
    names = scopes.op_names_from_hlo(scopes.step_hlo(tr))
    found = {scopes.scope_of(v) for v in names.values()}
    for layer in scopes.LAYERS:
        assert (layer, "", "forward") in found, layer
    for layer in ("attn", "mlp", "head", "loss"):
        assert (layer, "", "backward") in found, layer
    assert ("adamw", "", "backward") not in found
    assert {sc for sc, _, ph in found if ph == "recompute"} == {"attn",
                                                                "mlp"}
    assert all(not k.startswith("%") and " " not in k for k in names)
