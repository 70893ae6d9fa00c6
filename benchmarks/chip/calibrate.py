#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the served tokens' widest logit
gap and the fp8 control's, over many seeds, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --seconds 25 [--out cal.jsonl]

Set-up (weights, program start-up, the warm ticks) is paid once; each seed
then gets its own weights, a fresh engine, the mix's warm-in and a window
of ``--seconds`` through the same timed path as ``run.py``, and the
reference check of the sample ``run.py`` would draw. The control is the
reference in fp8 (``reference.py``), read at the same positions and judged
by the same checks at the cell's limit. One JSON line per seed. Not part
of a benchmark run.
"""
import argparse
import gc
import json
import sys
import time

import run  # sets up sys.path

import harness  # noqa: E402
import model as bench_model  # noqa: E402
import traffic as bench_traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.load_cell(args.workload, bench)
    c = bench_model.load(cell["config"])
    spec = bench_traffic.load(cell["traffic"])
    try:
        jax, devs = run.setup_jax(cell["chips"])
    except run.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 3
    log = harness.CompileLog(jax)
    seeds = [int(s) for s in args.seeds.split(",")]
    system = harness.System(c, seeds[0])
    system.warm(harness.warm_ticks(c, spec))
    run.log("set-up done")
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(seeds):
        if i:
            system.backend.params = bench_model.make_params(c, seed)
        loop = harness.Loop(system, spec, seed)
        loop.run_until(loop.base + spec["warm_in_s"])
        w = harness.run_window(loop, args.seconds, log)
        loop.restore()
        system.close_engine()
        system.backend.params = None
        del loop
        gc.collect()
        t0 = time.monotonic()
        checks, sample = run.check(c, w, seed, controls=("fp8",))
        row = {"workload": cell["name"], "seed": seed,
               "served_gap": checks["served"]["max_logit_gap"]["value"],
               "control_fp8_gap": checks["fp8"]["max_logit_gap"]["value"],
               "correct": run.checks_pass(checks["served"]),
               "control_correct": run.checks_pass(checks["fp8"]),
               "checks": checks, "sampled": len(sample),
               "compiles_in_window": w.compiles,
               "reference_s": time.monotonic() - t0}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
