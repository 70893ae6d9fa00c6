#!/usr/bin/env python3
"""Device time of the train step by the program's layer scopes.

The program names its device work with ``jax.named_scope``
(``repro.obs.scopes``): ``embed``, ``attn``, ``mlp``, ``head``, ``loss`` and
``adamw``. Each name lands in the ``op_name`` metadata of the HLO
instructions traced under it. A TPU v5e trace's op events carry no
``op_name``, only the instruction's name, so the names are read from the
compiled step's optimized HLO text (``op_names_from_hlo``). ``by_scope``
sums the device seconds of the ``XLA Ops`` events that run inside a
``train_step`` program event, by scope and phase, per step. Run as a script
on the chip, it traces a few steps of a training cell and prints that
split, one JSON object:

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \
        [--seconds 4]

Not part of a benchmark run.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

LAYERS = ("embed", "attn", "mlp", "head", "loss", "adamw")
UNSCOPED = "unscoped"
STEP_MODULE = "train_step"

# per-layer readings of the train step: device ms per step, both phases
GROUPS = {
    "train_vocab_ms": ("embed", "head", "loss"),
    "train_attn_ms": ("attn",),
    "train_mlp_ms": ("mlp",),
    "train_adamw_ms": ("adamw",),
}

_NAME = re.compile(r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])" % "|".join(LAYERS))
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*\bop_name="([^"]*)"',
                     re.M)


def scope_of(op_name: str) -> Tuple[str, str]:
    """(scope, phase) of an ``op_name``: the last of ``LAYERS`` in it as a
    whole word (``.../checkpoint/mlp/add_any``, ``jvp(mlp)``), else
    ``unscoped``; ``backward`` where it holds ``transpose(``."""
    names = _NAME.findall(op_name or "")
    phase = "backward" if "transpose(" in (op_name or "") else "forward"
    return (names[-1] if names else UNSCOPED), phase


def op_names_from_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name``, from a compiled module's HLO text."""
    return dict(_HLO_OP.findall(text))


def by_scope(tr, op_names: Dict[str, str], module: str = STEP_MODULE
             ) -> Optional[Dict[Tuple[str, str], float]]:
    """Device seconds per step by (scope, phase) of the non-container op
    events that start inside a ``module`` program event of their plane;
    ``None`` without such an event."""
    import bisect
    import trace_reduce
    out: Dict[Tuple[str, str], float] = {}
    steps = 0
    for plane, mods in tr.modules.items():
        mods = sorted((m for m in mods if module in m.name),
                      key=lambda m: m.start)
        steps += len(mods)
        starts = [m.start for m in mods]
        for e in tr.ops.get(plane, []):
            if trace_reduce.is_container(e):
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.start >= mods[i].end:
                continue
            key = scope_of(op_names.get(e.name, ""))
            out[key] = out.get(key, 0.0) + e.dur
    if not steps:
        return None
    return {k: s / steps for k, s in out.items()}


def group_ms(split: Dict[Tuple[str, str], float]) -> Dict[str, float]:
    """``GROUPS``' device milliseconds per step from ``by_scope``."""
    return {g: 1e3 * sum(s for (sc, _), s in split.items() if sc in names)
            for g, names in GROUPS.items()}


# --- on the chip -----------------------------------------------------------

def step_hlo(tr) -> str:
    """Optimized HLO text of the trainer's compiled step. Nothing compiles
    again: jit's caches hold the step's executable."""
    import jax.numpy as jnp
    import train
    toks, tgts = train.batch(tr.job, tr.c["vocab_size"], tr.seed, tr.k)
    b = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(tgts)}
    with tr._ctx(), tr.mesh:
        return tr.step_fn.lower(tr.params, tr.opt_state, b).compile(
        ).as_text()


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import time

    import run  # sets up sys.path

    import harness
    import model as bench_model
    import trace_reduce
    import traffic as bench_traffic
    import train
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.load_cell(args.workload, bench)
    c = bench_model.load(cell["config"])
    job = bench_traffic.load(cell["traffic"])
    try:
        jax, devs = run.setup_jax(cell["chips"])
    except run.NoChip as e:
        print(f"scopes.py: {e}", file=sys.stderr)
        return 3
    log = harness.CompileLog(jax)
    tr = train.Trainer(c, job)
    tr.start(args.seed)
    for _ in range(2):                  # the second step compiles again
        jax.block_until_ready(tr.dispatch())
    t = time.monotonic()
    jax.block_until_ready(tr.dispatch())
    step_s = time.monotonic() - t
    tr_dir = run.TRACE_DIR / f"scopes-{cell['name']}-{args.seed}"
    shutil.rmtree(tr_dir, ignore_errors=True)
    tr_dir.mkdir(parents=True)
    train.run_window(tr, args.seconds, step_s, log, trace_dir=str(tr_dir),
                     trace_seconds=args.seconds)
    path = trace_reduce.find(str(tr_dir))
    if path is None:
        print("scopes.py: the profiler wrote no trace", file=sys.stderr)
        return 1
    tr_data = trace_reduce.load(path)
    shutil.rmtree(tr_dir, ignore_errors=True)
    split = by_scope(tr_data, op_names_from_hlo(step_hlo(tr)))
    if split is None:
        print("scopes.py: no train step in the trace", file=sys.stderr)
        return 1
    total = sum(split.values())
    steps = sum(1 for evs in tr_data.modules.values() for m in evs
                if STEP_MODULE in m.name)
    print(json.dumps({
        "device": devs[0].device_kind, "steps": steps,
        "step_op_ms": 1e3 * total,
        "unscoped_share": sum(s for (sc, _), s in split.items()
                              if sc == UNSCOPED) / total,
        "ms": {f"{sc}.{ph}": 1e3 * s for (sc, ph), s in sorted(split.items())},
        "groups": group_ms(split),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
