#!/usr/bin/env python3
"""Readings for a training cell's correctness limits, over many seeds, in
one process.

    python3 benchmarks/chip/calibrate_train.py --workload <cell> \
        --seeds 11,12,13 --control-seeds 11,12,13 --fault-seeds 11,12,13 \
        [--out readings.jsonl]

The program's step is built once; each seed gets fresh state and the
first steps that ``run.py`` checks, then the float32 reference. On the
control seeds the reference in three-pass bf16 (``train_reference``,
``quant="high"``) stands in the program's place; on the fault seeds the
program's step runs with half of each batch left out and the mean taken
over the rest. Each is compared with the reference by the cell's own
checks and limits. One JSON line per seed and reading. Not part of a
benchmark run.
"""
import argparse
import json
import sys
import time

import run  # sets up sys.path

import harness  # noqa: E402
import model as bench_model  # noqa: E402
import traffic as bench_traffic  # noqa: E402
import train  # noqa: E402
import train_reference  # noqa: E402


def half_batch(fn):
    """The step with half of each batch left out: the mean over the rest."""
    def step(params, opt_state, batch_):
        return fn(params, opt_state,
                  {k: v[:v.shape[0] // 2] for k, v in batch_.items()})
    return step


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.load_cell(args.workload, bench)
    c = bench_model.load(cell["config"])
    job = bench_traffic.load(cell["traffic"])
    try:
        jax, _devs = run.setup_jax(cell["chips"])
    except run.NoChip as e:
        print(f"calibrate_train.py: {e}", file=sys.stderr)
        return 3
    harness.CompileLog(jax)
    out = open(args.out, "a") if args.out else None

    def emit(row):
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    def program(tr, seed):
        tr.start(seed)
        t = time.monotonic()
        got = tr.check_steps()
        tr.free()
        return got, time.monotonic() - t

    prog = train.Trainer(c, job)
    fault = train.Trainer(c, job, wrap=half_batch) if args.fault_seeds \
        else None
    for seed in _seeds(args.seeds):
        got, secs = program(prog, seed)
        t = time.monotonic()
        ref = train_reference.readings(c, job, seed)
        ref_s = time.monotonic() - t
        ch = train.checks(c, got, ref)
        emit({"workload": cell["name"], "seed": seed, "reading": "program",
              "checks": ch, "correct": train.checks_pass(ch),
              "loss": got["loss"], "ref_loss": ref["loss"],
              "step_s": got["step_s"], "program_s": secs,
              "reference_s": ref_s,
              "leaves_left_out": [
                  n for n, k in zip(train.leaf_names(
                      jax.eval_shape(lambda: bench_model.build_params(
                          c, bench_model.root_key(0), "float32"))),
                      train.leaf_mask(ref)) if not k]})
        if seed in _seeds(args.control_seeds):
            t = time.monotonic()
            ctl = train_reference.readings(c, job, seed, "high")
            ch = train.checks(c, ctl, ref)
            emit({"workload": cell["name"], "seed": seed,
                  "reading": "control_high", "checks": ch,
                  "correct": train.checks_pass(ch),
                  "seconds": time.monotonic() - t})
        if fault is not None and seed in _seeds(args.fault_seeds):
            got, _ = program(fault, seed)
            ch = train.checks(c, got, ref)
            emit({"workload": cell["name"], "seed": seed,
                  "reading": "fault_half_batch", "checks": ch,
                  "correct": train.checks_pass(ch)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
