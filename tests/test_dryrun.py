"""Dry-run / roofline plumbing tests: the HLO collective parser, skip rules,
and a real lower+compile of one cell on a small host-device mesh."""
import os
import subprocess
import sys

import pytest

from repro.configs.registry import ARCH_IDS, cell_is_supported
from repro.configs.shapes import SHAPES
from repro.launch.dryrun import collective_bytes


def test_collective_parser_counts_output_shapes():
    hlo = """
  %ar = bf16[128,256]{1,0} all-reduce(bf16[128,256] %x), replica_groups={}
  %ag.1 = f32[64]{0} all-gather(f32[16] %y), dimensions={0}
  %noise = bf16[4,4]{1,0} add(bf16[4,4] %a, bf16[4,4] %b)
  %a2a = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-to-all(bf16[8,8] %p, bf16[8,8] %q)
  %done = f32[64]{0} all-reduce-done(f32[64] %ar2)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 256 * 2
    assert out["all-gather"] == 64 * 4
    assert out["all-to-all"] == 2 * 8 * 8 * 2
    assert out["total"] == out["all-reduce"] + out["all-gather"] + out["all-to-all"]


def test_skip_rules_match_assignment():
    skipped = [a for a in ARCH_IDS if not cell_is_supported(a, "long_500k")[0]]
    assert set(skipped) == {"internlm2-20b", "qwen2.5-3b", "llama3.2-1b",
                            "whisper-tiny", "llava-next-34b", "dbrx-132b",
                            "granite-moe-3b-a800m"}
    for a in ("gemma2-27b", "rwkv6-1.6b", "zamba2-1.2b"):
        assert cell_is_supported(a, "long_500k")[0]
    for a in ARCH_IDS:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert cell_is_supported(a, s)[0]


def test_dryrun_cell_compiles_on_small_mesh():
    """Full-scale llama decode_32k lowers+compiles on a 2x4 host mesh and
    reports flops/bytes/collectives (subprocess: needs 8 host devices)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.dryrun import run_cell
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
r = run_cell("llama3.2-1b", "decode_32k", mesh=mesh, verbose=False)
assert r["status"] == "ok", r.get("error")
assert r["flops"] > 0 and r["collectives"]["total"] >= 0
print("CELL-OK")
"""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "CELL-OK" in out.stdout, out.stdout + out.stderr


def test_roofline_analysis_fields():
    from repro.launch.roofline import analyze
    rec = {"status": "ok", "arch": "llama3.2-1b", "shape": "train_4k",
           "n_devices": 256, "flops": 5e13, "bytes_accessed": 5e12,
           "collectives": {"total": 7e10}}
    row = analyze(rec)
    assert row["dominant"] in ("compute", "memory", "collective")
    assert row["terms_s"]["memory"] == pytest.approx(5e12 / 819e9)
    assert 0 < row["useful_ratio"] < 2
    assert row["lever"]
