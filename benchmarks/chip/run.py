#!/usr/bin/env python3
"""Chip benchmark of the program: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for. The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
each metric is read by ``metrics/<metric>.py``. With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first
seconds.

A traffic file with ``"kind": "train"`` is a training job, run by
``train.py``: set-up builds the program's train step with its state from
the seed and drives it through its first steps, the window goes on with
it, and once the window has closed and the state is freed the plain
float32 reference (``train_reference.py``) follows the first steps.

Any other traffic is served (``harness.py``). Set-up (counted in
``setup_s``): TPU start-up, weights made on the device from the seed, the
program's own start-up, the synthetic ticks of ``harness.warm_ticks`` run
through the backend, and the traffic's warm-in. Then the window. Once it
has closed, the program's state is freed and a plain float32 reference
(``reference.py``) checks a sample of the served requests drawn from the
seed. Every step program is compiled or loaded from the compile cache in
``<checkout>/.jax_cache``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit,
which also close standard error. Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 3.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import harness  # noqa: E402
import model as bench_model  # noqa: E402
import peaks as bench_peaks  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as bench_traffic  # noqa: E402
import train  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 4.0


class NoChip(Exception):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str, bench: dict):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: dict, trace: bool):
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def reader(name: str):
    return importlib.import_module(f"metrics.{name}").read


def setup_jax(chips: int):
    """Compile cache at a fixed path in the checkout; the chips the cell
    needs, or ``NoChip``."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return jax, devs


def device_info(devs, chips: int) -> dict:
    """The cell's devices; peaks on the fullest chip: ``peak_bytes_in_use``
    and, apart, ``peak_bytes_reserved`` (the programs' temporaries, which
    the runtime reserves outside what is in use)."""
    used = devs[:chips]
    peak = reserved = 0
    for d in used:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        reserved = max(reserved, int(st.get("peak_bytes_reserved", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak,
            "memory_reserved_peak_bytes": reserved}


def reduce_trace(w, tr_dir: Path):
    """(trace, busy/window seconds, breakdown) of the traced seconds."""
    path = trace_reduce.find(str(tr_dir))
    if path is None:
        return None, None, None
    tr = trace_reduce.load(path)
    ticks = [h for h in tr.host if h.name == "bench.tick"]
    if not ticks:
        return tr, None, None
    t0 = min(h.start for h in ticks)
    t1 = max(h.end for h in ticks)
    per_chip = []
    ops_all = []
    for evs in tr.ops.values():
        inside = [e for e in evs if e.end > t0 and e.start < t1]
        ops_all.extend(inside)
        per_chip.append(trace_reduce.busy_seconds(inside))
    busy = sum(per_chip) / len(per_chip) if per_chip else 0.0
    by_name = {}
    for e in ops_all:
        if not trace_reduce.is_container(e):
            by_name[e.name] = by_name.get(e.name, 0.0) + e.dur
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    first = next(iter(tr.ops.values()), [])
    gaps = trace_reduce.idle_gaps(
        [e for e in first if e.end > t0 and e.start < t1], t0, t1)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    breakdown = {"device_ops": [[n, s] for n, s in top_ops],
                 "idle_gaps": [[trace_reduce.label_gap(g, tr.host), g[1] - g[0]]
                               for g in gaps]}
    return tr, {"busy_s": busy, "window_s": t1 - t0}, breakdown


def check(c: dict, w, seed: int, controls=()):
    """Reference comparison of a sample of the window's served requests.

    Returns ``({name: checks}, sample)``: ``"served"`` holds the checks of
    the tokens the clients received; each precision in ``controls``
    (``"fp8"``) holds the same checks of the tokens that the reference in
    that precision puts first at the same positions (the control)."""
    sample = harness.sample_for_check(w, seed)
    vocab = c["vocab_size"]
    seqs, poss, toks, complete = [], [], [], 0
    for rec in sample:
        s, p, t, ok = harness.check_sequence(rec, vocab)
        seqs.append(s)
        poss.append(p)
        toks.append(t)
        complete += int(ok)
    gaps = {q: 0.0 for q in (None,) + tuple(controls)}
    if sample:
        out = reference.logits_at(c, seed, seqs, poss, tuple(gaps))
        for q in gaps:
            for i, t in enumerate(toks):
                chosen = t if q is None else out[q][i].argmax(-1)
                gaps[q] = max(gaps[q],
                              reference.widest_gap(out[None][i], chosen))
    n_tok = sum(len(t) for t in toks)
    result = {}
    for q, gap in gaps.items():
        result["served" if q is None else q] = {
            "max_logit_gap": {"value": gap,
                              "limit": c["limits"]["max_logit_gap"]},
            "incomplete_requests": {"value": len(sample) - complete,
                                    "limit": 0},
            "served_tokens_compared": {
                "value": n_tok, "limit": w.spec["sample"]["min_tokens"]},
        }
    return result, sample


def checks_pass(checks: dict) -> bool:
    return (checks["max_logit_gap"]["value"]
            <= checks["max_logit_gap"]["limit"]
            and checks["incomplete_requests"]["value"] == 0
            and checks["served_tokens_compared"]["value"]
            >= checks["served_tokens_compared"]["limit"])


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:8.3f}] {msg}", file=sys.stderr,
          flush=True)


def measure(args, cell, c, spec, jax, devs, compile_log):
    bench_peaks.peaks(devs[0].device_kind)          # unknown kind: error
    log(f"device {devs[0].device_kind} x{len(devs)}")
    system = harness.System(c, args.seed)
    log(f"weights and program start-up done: compiles="
        f"{compile_log.programs} cache_hits={compile_log.cache_hits}")
    plan = harness.warm_ticks(c, spec)
    system.warm(plan)
    log(f"{len(plan)} ticks warm: compiles={compile_log.programs} "
        f"seconds={compile_log.seconds:.3f} "
        f"cache_hits={compile_log.cache_hits}")
    loop = harness.Loop(system, spec, args.seed,
                            annotate=bool(args.trace))
    loop.run_until(loop.base + spec["warm_in_s"])
    setup_s = time.monotonic() - T_START
    log(f"warm-in done: compiles={compile_log.programs}")
    tr_dir = None
    if args.trace:
        tr_dir = TRACE_DIR / f"{cell['name']}-{args.seed}"
        shutil.rmtree(tr_dir, ignore_errors=True)
        tr_dir.mkdir(parents=True)
    w = harness.run_window(loop, args.seconds, compile_log,
                           trace_dir=str(tr_dir) if tr_dir else None,
                           trace_seconds=TRACE_SECONDS)
    loop.restore()
    device = device_info(devs, cell["chips"])
    attempted, failed = harness.counts(w)
    ctx = {"setup_s": setup_s, "trace": w.trace,
           "peaks": bench_peaks.peaks(device["kind"])}
    breakdown = None
    if tr_dir is not None:
        tr, dev_t, breakdown = reduce_trace(w, tr_dir)
        shutil.rmtree(tr_dir, ignore_errors=True)
        ctx["trace_data"] = tr
        ctx["device_time"] = dev_t
        if dev_t:
            device["busy_s"] = dev_t["busy_s"]
            device["window_s"] = dev_t["window_s"]
    metrics = {}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in cell_metrics(bench, cell, bool(args.trace)):
        v = reader(m["name"])(w, ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"window: seconds={w.seconds} ticks={len(w.ticks)} "
        f"compiles_in_window={w.compiles} compile_s={w.compile_s} "
        f"attempted={attempted} failed={failed} "
        f"compiled_in={harness.compiled_ticks(w)}")
    # free the program's state before the reference runs
    system.close()
    del loop, system
    gc.collect()
    checks = check(c, w, args.seed)[0]["served"]
    log("reference check done")
    result = {"correct": checks_pass(checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_cell(args.workload, bench)
    c = bench_model.load(cell["config"])
    spec = bench_traffic.load(cell["traffic"])
    try:
        jax, devs = setup_jax(cell["chips"])
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    compile_log = harness.CompileLog(jax)
    if spec.get("kind") == "train":
        result = train.measure(args, cell, c, spec, devs, compile_log,
                               sys.modules[__name__], bench)
    else:
        result = measure(args, cell, c, spec, jax, devs, compile_log)
    sys.stdout.flush()
    for name, ch in result["checks"].items():
        print(f"check {name} value={ch['value']} limit={ch['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
