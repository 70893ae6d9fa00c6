"""Harness of a training cell: the program's own train step, driven from
the seed through its first steps and then through the window.

The step is ``repro.distributed.steps.build_train_step`` (forward,
cross-entropy, backward, ``adamw_update``), jitted with the weights and the
optimizer state donated, as ``repro.launch.train.train`` builds it, over a
one-chip mesh and at the matrix-product precision that the configuration's
``train`` group states. Set-up builds that one object with its state:
float32 weights made on the device from the seed (``model.make_params``)
and the program's ``init_opt``. The first ``CHECK_STEPS`` steps then go
through the window's own call and feed, and the check reads them: each
step's loss, the gradient as the optimizer got it (its first moment after
one step, over ``1 - beta1``) and the weights' change after the last of
them, read before the next step overwrites the weights. The window goes on
from there: steps are dispatched ahead (``job["ahead_s"]`` seconds of
them), each loss is read once the step ``ahead`` later has been sent, and
at the close nothing more is sent, everything sent is waited for, and the
clock is read after that wait.

A batch (``batch``) is ``job["batch"]`` rows of ``job["seq_len"] + 1``
token ids drawn uniformly from the vocabulary by ``(seed, step)``: inputs
are each row's first ``seq_len`` ids, targets its last ``seq_len``. Every
row of every step differs, and every seed has the same shapes.
"""
from __future__ import annotations

import math
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

import model as bench_model

CHECK_STEPS = 3
STEP_SPAN = "bench.train_step"


def batch(job: dict, vocab: int, seed: int, step: int):
    """(tokens, targets), each (batch, seq_len) int32, of step ``step``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    rows = rng.integers(0, vocab, size=(job["batch"], job["seq_len"] + 1),
                        dtype=np.int32)
    return rows[:, :-1], rows[:, 1:]


def tokens_per_step(job: dict) -> int:
    return job["batch"] * job["seq_len"]


def leaf_names(tree) -> List[str]:
    """One name per compared leaf: each layer of a stacked leaf apart."""
    import jax
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if name.startswith("['layers']"):
            out += [f"{name}[{i}]" for i in range(x.shape[0])]
        else:
            out.append(name)
    return out


def leaf_norms(tree):
    """Float32 norms in ``leaf_names`` order (traceable)."""
    import jax
    import jax.numpy as jnp
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        if jax.tree_util.keystr(path).startswith("['layers']"):
            out.append(jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2,
                                        axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(out)


_CHANGE = {}


def change_norms(c: dict):
    """Jitted (weights, key) -> ``leaf_norms`` of the weights minus the
    float32 weights that ``key`` makes."""
    import jax
    import jax.numpy as jnp
    if id(c) not in _CHANGE:
        _CHANGE[id(c)] = jax.jit(lambda p, key: leaf_norms(jax.tree.map(
            lambda a, b: a - b, p,
            bench_model.build_params(c, key, jnp.float32))))
    return _CHANGE[id(c)]


def opt_config(c: dict):
    from repro.train.optimizer import OptConfig
    o = dict(c["train"]["optimizer"])
    o["betas"] = tuple(o["betas"])
    return OptConfig(**o)


class Trainer:
    """The compiled step with its state, for configuration ``c`` and job
    ``job``. ``wrap`` (tests and fault readings only) wraps the program's
    step function before it is jitted."""

    def __init__(self, c: dict, job: dict, wrap=None):
        import jax
        from repro.distributed.steps import build_train_step
        from repro.launch.mesh import make_test_mesh
        self.c, self.job = c, job
        t = c["train"]
        if t["param_dtype"] != "float32":
            raise ValueError(f"{c['name']}: the program trains float32 "
                             f"weights, not {t['param_dtype']}")
        self.precision = t["matmul_precision"]
        self.mesh = make_test_mesh(1)
        self.opt = opt_config(c)
        fn = build_train_step(bench_model.arch(c).program_config(c),
                              self.mesh, opt=self.opt, remat=t["remat"])
        if wrap is not None:
            fn = wrap(fn)

        def train_step(params, opt_state, batch_):
            return fn(params, opt_state, batch_)
        self.step_fn = jax.jit(train_step, donate_argnums=(0, 1))
        self._mu_norms = jax.jit(leaf_norms)
        self.params = self.opt_state = None
        self.k = 0

    def _ctx(self):
        import jax
        return jax.default_matmul_precision(self.precision)

    def start(self, seed: int) -> None:
        """Fresh state from ``seed``: float32 weights and ``init_opt``."""
        import jax
        import jax.numpy as jnp
        from repro.train.optimizer import init_opt
        self.free()
        self.seed = seed
        self.params = bench_model.make_params(self.c, seed, jnp.float32)
        self.opt_state = jax.jit(init_opt)(self.params)
        self.k = 0

    def free(self) -> None:
        self.params = self.opt_state = None

    def dispatch(self) -> dict:
        """Feed and send step ``k``; returns the step's outputs, its loss
        among them (not waited for)."""
        import jax.numpy as jnp
        toks, tgts = batch(self.job, self.c["vocab_size"], self.seed, self.k)
        b = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(tgts)}
        with self._ctx(), self.mesh:
            self.params, self.opt_state, m = self.step_fn(
                self.params, self.opt_state, b)
        self.k += 1
        return m

    def check_steps(self) -> dict:
        """Steps 1 to ``CHECK_STEPS`` from a fresh state, and what the check
        reads of them. Also returns the seconds of the last one."""
        losses = [float(self.dispatch()["loss"])]
        b1 = self.opt.betas[0]
        grad = np.asarray(self._mu_norms(self.opt_state.mu)) / (1 - b1)
        for _ in range(CHECK_STEPS - 1):
            t = time.monotonic()
            losses.append(float(self.dispatch()["loss"]))
        step_s = time.monotonic() - t
        change = np.asarray(change_norms(self.c)(
            self.params, bench_model.root_key(self.seed)))
        return {"loss": losses, "grad": grad, "change": change,
                "step_s": step_s}


@dataclass
class TrainWindow:
    c: dict
    job: dict
    t_open: float = 0.0
    t_close: float = 0.0
    steps: int = 0
    ahead: int = 1
    dispatch_s: float = 0.0
    losses: List[float] = field(default_factory=list)
    compiles: int = 0
    traced_steps: int = 0
    traced_outputs: List[dict] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def run_window(tr: Trainer, seconds: float, step_s: float, compile_log,
               trace_dir: Optional[str] = None,
               trace_seconds: float = 0.0) -> TrainWindow:
    """Steps for ``seconds``, ``job["ahead_s"]`` seconds of them in flight.
    With ``trace_dir`` the window's first ``trace_seconds`` of steps are
    traced, waited for, and the trace stopped before the rest are sent;
    those steps' outputs are then read back, one ``device_get`` a step,
    into ``traced_outputs``. Other steps' losses alone are read."""
    import jax
    w = TrainWindow(tr.c, tr.job)
    w.ahead = max(1, math.ceil(tr.job["ahead_s"] / max(step_s, 1e-3)))
    n0 = compile_log.programs
    pending = deque()
    traced = []
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    w.t_open = time.monotonic()
    while time.monotonic() - w.t_open < seconds:
        t0 = time.monotonic()
        span = (jax.profiler.TraceAnnotation(STEP_SPAN) if tracing
                else nullcontext())
        with span:
            m = tr.dispatch()
        pending.append(m["loss"])
        if tracing:
            traced.append(m)
        w.dispatch_s += time.monotonic() - t0
        w.steps += 1
        while len(pending) > w.ahead:
            w.losses.append(float(pending.popleft()))
        if tracing and time.monotonic() - w.t_open >= trace_seconds:
            jax.block_until_ready((tr.params, tr.opt_state))
            w.losses += [float(x) for x in pending]
            pending.clear()
            jax.profiler.stop_trace()
            w.traced_steps = w.steps
            w.traced_outputs = [jax.device_get(m) for m in traced]
            tracing = False
    jax.block_until_ready((tr.params, tr.opt_state))
    w.losses += [float(x) for x in pending]
    w.t_close = time.monotonic()
    if tracing:
        jax.profiler.stop_trace()
        w.traced_steps = w.steps
        w.traced_outputs = [jax.device_get(m) for m in traced]
    w.compiles = compile_log.programs - n0
    return w


def step_counters(outputs: List[dict]) -> dict:
    """Mean over the steps of each scalar output other than the loss."""
    names = [k for k, v in (outputs[0] if outputs else {}).items()
             if k != "loss" and np.ndim(v) == 0]
    return {k: float(np.mean([o[k] for o in outputs])) for k in names}


# --- the comparison --------------------------------------------------------

def leaf_mask(ref: dict) -> np.ndarray:
    """Leaves compared: those whose reference gradient is at least a
    thousandth of the median leaf's (a key bias under softmax has none)."""
    g = ref["grad"]
    return g >= 1e-3 * np.median(g)


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared: the largest relative gap of the three losses,
    and of the per-leaf gradient and change norms by the worst leaf, each
    against the larger of the reference leaf's norm and the median
    leaf's."""
    keep = leaf_mask(ref)
    out = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                           zip(prog["loss"], ref["loss"]))}
    for k in ("grad", "change"):
        p, r = np.asarray(prog[k])[keep], np.asarray(ref[k])[keep]
        den = np.maximum(r, np.median(r))
        out[f"{k}_norm_gap"] = float(np.max(np.abs(p - r) / den))
    return {k: float(v) for k, v in out.items()}


def checks(c: dict, prog: dict, ref: dict) -> dict:
    lim = c["limits"]
    return {k: {"value": v, "limit": lim[k]}
            for k, v in gaps(prog, ref).items()}


def checks_pass(ch: dict) -> bool:
    # a NaN reads as failed: no comparison with it is true
    return all(v["value"] <= v["limit"] for v in ch.values())


# --- the trace -------------------------------------------------------------

def reduce_trace(tr) -> tuple:
    """(busy/window seconds, breakdown, step programs' seconds and count)
    of the traced steps: the window runs from the first step's dispatch to
    the last device operation."""
    import trace_reduce
    spans = [h for h in tr.host if h.name == STEP_SPAN]
    ops = {k: v for k, v in tr.ops.items() if v}
    if not spans or not ops:
        return None, None, None
    t0 = min(h.start for h in spans)
    t1 = max(e.end for evs in ops.values() for e in evs)
    per_chip, by_name = [], {}
    for evs in ops.values():
        inside = [e for e in evs if e.end > t0 and e.start < t1]
        per_chip.append(trace_reduce.busy_seconds(inside))
        for e in inside:
            if not trace_reduce.is_container(e):
                by_name[e.name] = by_name.get(e.name, 0.0) + e.dur
    first = next(iter(ops.values()))
    gap_list = sorted(trace_reduce.idle_gaps(
        [e for e in first if e.end > t0 and e.start < t1], t0, t1),
        key=lambda g: g[0] - g[1])[:10]

    def label(g):
        s, e = g
        host = sum(max(0.0, min(e, h.end) - max(s, h.start)) for h in spans)
        return ("step dispatch and feed (host)" if host >= (e - s) / 2
                else "host outside dispatch (loss readback, loop)")
    mods = [e for evs in tr.modules.values() for e in evs
            if "train_step" in e.name]
    breakdown = {
        "device_ops": [[n, s] for n, s in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[label(g), g[1] - g[0]] for g in gap_list]}
    return ({"busy_s": sum(per_chip) / len(per_chip), "window_s": t1 - t0},
            breakdown,
            {"seconds": sum(e.dur for e in mods), "count": len(mods)})


# --- one run ---------------------------------------------------------------

def measure(args, cell, c, job, devs, compile_log, rt, bench,
            wrap=None) -> dict:
    """One run of a training cell: set-up, the window, the metrics, then
    the reference check. ``rt`` is ``run.py``'s module (its clock, log,
    device and metric helpers)."""
    import gc
    import shutil
    import peaks as bench_peaks
    import train_reference
    log = rt.log
    pk = bench_peaks.peaks(devs[0].device_kind)      # unknown kind: error
    log(f"device {devs[0].device_kind} x{len(devs)}")
    tr = Trainer(c, job, wrap=wrap)
    tr.start(args.seed)
    prog = tr.check_steps()
    setup_s = time.monotonic() - rt.T_START
    log(f"set-up done: compiles={compile_log.programs} "
        f"seconds={compile_log.seconds:.3f} cache_hits="
        f"{compile_log.cache_hits} step_s={prog['step_s']:.4f}")
    tr_dir = None
    if args.trace:
        tr_dir = rt.TRACE_DIR / f"{cell['name']}-{args.seed}"
        shutil.rmtree(tr_dir, ignore_errors=True)
        tr_dir.mkdir(parents=True)
    w = run_window(tr, args.seconds, prog["step_s"], compile_log,
                   trace_dir=str(tr_dir) if tr_dir else None,
                   trace_seconds=rt.TRACE_SECONDS)
    device = rt.device_info(devs, cell["chips"])
    ctx = {"setup_s": setup_s, "peaks": pk}
    breakdown = None
    if tr_dir is not None:
        import scopes
        import trace_reduce
        ctx["step_counters"] = step_counters(w.traced_outputs)
        log(f"step_counters over {len(w.traced_outputs)} traced steps: "
            f"{ctx['step_counters']}")
        path = trace_reduce.find(str(tr_dir))
        if path is not None:
            trace = trace_reduce.load(path)
            dev_t, breakdown, mods = reduce_trace(trace)
            ctx["device_time"], ctx["step_modules"] = dev_t, mods
            if dev_t:
                device["busy_s"] = dev_t["busy_s"]
                device["window_s"] = dev_t["window_s"]
            n0 = compile_log.programs
            split = scopes.by_scope(
                trace, scopes.op_names_from_hlo(scopes.step_hlo(tr)))
            ctx["scope_split"] = split
            if split and mods:
                ms = dict(scopes.group_ms(split),
                          unscoped=scopes.layer_ms(split, scopes.UNSCOPED),
                          recompute=scopes.layer_ms(split, phase="recompute"),
                          step_ops=1e3 * sum(split.values()),
                          step_programs=1e3 * mods["seconds"]
                          / mods["count"])
                log("device ms per traced step, by scope: " + " ".join(
                    f"{k}={v}" for k, v in ms.items())
                    + f"; compiles for the HLO: {compile_log.programs - n0}")
        shutil.rmtree(tr_dir, ignore_errors=True)
    metrics = {}
    for m in rt.cell_metrics(bench, cell, bool(args.trace)):
        v = rt.reader(m["name"])(w, ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"window: seconds={w.seconds} steps={w.steps} ahead={w.ahead} "
        f"compiles_in_window={w.compiles} traced_steps={w.traced_steps} "
        f"losses_finite={bool(np.all(np.isfinite(w.losses)))}")
    tr.free()
    del tr
    gc.collect()
    ref = train_reference.readings(c, job, args.seed)
    ch = checks(c, prog, ref)
    log("reference check done")
    result = {"correct": checks_pass(ch), "attempted": w.steps,
              "failed": int(np.sum(~np.isfinite(w.losses))),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = ch
    return result
