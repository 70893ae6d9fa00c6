#!/usr/bin/env python3
"""Device time of the train step by the program's layer scopes.

The program names its device work with ``jax.named_scope``; the names are
its own (``repro.obs.scopes``): the layers ``LAYERS`` (``embed``, ``attn``,
``mlp``, ``head``, ``loss``, ``adamw``) and, nested inside a layer, the
parts ``PARTS`` that an architecture names (none where the program
declares none). Each name lands in the ``op_name`` metadata of the HLO
instructions traced under it. A TPU v5e trace's op events carry no
``op_name``, only the instruction's name, so the names are read from the
compiled step's optimized HLO text (``op_names_from_hlo``). ``by_scope``
sums the device seconds of the ``XLA Ops`` events that run inside a
``train_step`` program event, by scope, part and phase, per step. Run as
a script on the chip, it traces a few steps of a training cell and prints
that split, one JSON object:

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \
        [--seconds 4]

Not part of a benchmark run.
"""
from __future__ import annotations

import re
from functools import lru_cache
from typing import Dict, Optional, Tuple

if __name__ == "__main__":
    import run  # noqa: F401  (puts the program's src/ on sys.path)

from repro.obs import scopes as program_scopes  # noqa: E402

LAYERS = program_scopes.LAYERS
UNSCOPED = "unscoped"
STEP_MODULE = "train_step"
PHASES = ("forward", "backward", "recompute")

# per-layer readings of the train step: device ms per step, every part and
# phase; a scope outside these is read with ``layer_ms``
GROUPS = {
    "train_vocab_ms": ("embed", "head", "loss"),
    "train_attn_ms": ("attn",),
    "train_mlp_ms": ("mlp",),
    "train_adamw_ms": ("adamw",),
}

Key = Tuple[str, str, str]

_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*\bop_name="([^"]*)"',
                     re.M)


def parts() -> Tuple[str, ...]:
    """The program's sub-scopes that nest inside a layer scope."""
    return tuple(getattr(program_scopes, "PARTS", ()))


@lru_cache(maxsize=None)
def _words(names: Tuple[str, ...]):
    return re.compile(r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])"
                      % "|".join(map(re.escape, names)))


def scope_of(op_name: str) -> Key:
    """(scope, part, phase) of an ``op_name``. The scope is the last of
    ``LAYERS`` in it as a whole word (``.../checkpoint/mlp/add_any``,
    ``jvp(mlp)``), else ``unscoped``; the part the last of ``parts()``
    after that scope, else ``""``; the phase ``recompute`` where it holds
    ``rematted_computation``, ``backward`` where it holds ``transpose(``,
    else ``forward``."""
    s = op_name or ""
    found = list(_words(LAYERS).finditer(s))
    part = ""
    if found and parts():
        after = _words(parts()).findall(s, found[-1].end())
        part = after[-1] if after else ""
    phase = ("recompute" if "rematted_computation" in s
             else "backward" if "transpose(" in s else "forward")
    return (found[-1].group(1) if found else UNSCOPED), part, phase


def op_names_from_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name``, from a compiled module's HLO text."""
    return dict(_HLO_OP.findall(text))


def by_scope(tr, op_names: Dict[str, str], module: str = STEP_MODULE
             ) -> Optional[Dict[Key, float]]:
    """Device seconds per step by (scope, part, phase) of the
    non-container op events that start inside a ``module`` program event
    of their plane; ``None`` without such an event."""
    import bisect
    import trace_reduce
    out: Dict[Key, float] = {}
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


def group_ms(split: Dict[Key, float]) -> Dict[str, float]:
    """``GROUPS``' device milliseconds per step from ``by_scope``."""
    return {g: 1e3 * sum(s for (sc, _, _), s in split.items() if sc in names)
            for g, names in GROUPS.items()}


def layer_ms(split: Dict[Key, float], scope: Optional[str] = None,
             part: Optional[str] = None, phase: Optional[str] = None
             ) -> Optional[float]:
    """Device milliseconds per step of ``by_scope``'s entries with that
    scope, part and phase (``None``: any); ``None`` where no entry has
    them."""
    got = [s for k, s in split.items()
           if all(w is None or w == v for w, v in zip((scope, part, phase), k))]
    return 1e3 * sum(got) if got else None


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

    import run

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
        "unscoped_share": (layer_ms(split, UNSCOPED) or 0.0) / (1e3 * total),
        "ms": {".".join(filter(None, k)): 1e3 * s
               for k, s in sorted(split.items())},
        "phases": {ph: layer_ms(split, phase=ph) for ph in PHASES},
        "groups": group_ms(split),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
