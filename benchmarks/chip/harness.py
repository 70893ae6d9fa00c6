"""Harness core: builds the served system for a cell, warms it with
synthetic ticks through ``JaxBackend.run_batch``, drives the cell's
traffic through ``Engine.tick`` on the harness's own clock, and records
what each client sees.

The loop is a copy of ``repro.engine.engine.run_live`` with the traffic's
due times: it calls ``Engine.tick`` -> ``JaxBackend.run_batch`` ->
``_PagedLayout.run_mixed`` -> ``lm_mixed_paged`` (Pallas
``paged_flash_attention`` for prefill packs, ``paged_attention_fused`` for
decode lanes) with ``RealToolExecutor`` and the configuration's policy.
After each tick the harness stamps the tokens that tick emitted, found by
diffing each batched session's ``meta["generated"]``; it reads neither
``Session.ttfts`` nor the bus's event times.

A round's served tokens are what the client receives: the tokens its
decode lanes append to ``meta["generated"]``. Time to first token, the
gaps and the correctness check all read that stream.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple
from unittest import mock

import numpy as np

import model as bench_model
import traffic as bench_traffic


class CompileLog:
    """Backend compiles (cache loads included) and their seconds, from
    ``jax.monitoring`` (copied from ``chip_smoke.CompileLog``)."""

    def __init__(self, jax):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.times: List[float] = []     # when each compile ended
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs
            self.times.append(time.monotonic())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclass
class RoundRec:
    due: float                           # harness clock: when it was due
    tokens: List[int] = field(default_factory=list)
    stamps: List[float] = field(default_factory=list)
    path: str = "cold"                   # cold | kept | restored | recomputed


@dataclass
class ReqRec:
    req: bench_traffic.Request
    sid: int
    rounds: List[RoundRec] = field(default_factory=list)
    done_at: Optional[float] = None
    failed: bool = False


@dataclass
class TickRec:
    t0: float
    t1: float
    rb0: float = 0.0
    rb1: float = 0.0
    packs: Tuple[Tuple[int, int], ...] = ()     # (start, chunk) per pack
    lanes: Tuple[int, ...] = ()                 # kv length after the write


class System:
    """Engine + paged ``JaxBackend`` for configuration ``c``, with the
    benchmark's seeded weights in place of the program's own init."""

    def __init__(self, c: dict, seed: int, model_cfg=None):
        import jax.numpy as jnp
        from repro.core.events import EventBus
        from repro.engine.jax_runner import JaxBackend
        from repro.models import model_zoo
        self.c = c
        self.e = c["engine"]
        self.cfg = model_cfg or bench_model.arch(c).program_config(c)
        params = bench_model.make_params(c, seed)
        with mock.patch.object(model_zoo, "init",
                               lambda cfg, key, dtype: params):
            self.backend = JaxBackend(
                self.cfg, layout="paged",
                max_slots=self.e["max_decode_batch"],
                total_pages=self.e["pages"], page_size=self.e["page_size"],
                dtype=jnp.bfloat16)
        del params
        assert self.backend.layout == "paged"
        self.bus = EventBus()
        self.engine = None
        self.tools = None

    def new_engine(self):
        from repro.core.events import EventBus
        from repro.engine.engine import Engine, EngineConfig
        from repro.engine.tools import RealToolExecutor
        self.close_engine()
        e = self.e
        self.bus = EventBus()
        self.tools = RealToolExecutor(cpu_slots=e["cpu_slots"], bus=self.bus)
        self.engine = Engine(EngineConfig(
            total_kv_blocks=e["pages"], block_size=e["page_size"],
            token_budget=e["token_budget"],
            max_decode_batch=e["max_decode_batch"],
            cpu_slots=e["cpu_slots"]), e["policy"], self.backend,
            bus=self.bus, tool_exec=self.tools)
        return self.engine

    def close_engine(self) -> None:
        if self.tools is not None:
            self.tools.shutdown()
            self.tools = None
        self.engine = None

    def close(self) -> None:
        self.close_engine()
        self.backend.close()

    # --- warm-up ----------------------------------------------------------
    def warm(self, ticks) -> None:
        """Run each synthetic tick once through ``backend.run_batch``, the
        entry the engine itself calls, so that whatever step program the
        backend builds for such a tick is compiled, or loaded from the
        cache, before the window. A tick is ``(chunks, lanes)``: the
        lengths of its whole-prompt prefill packs and the context lengths
        of its decode lanes. Pages are leased in turn round the pool; what
        the ticks write there no session reads, since each writes its own
        KV before it reads it."""
        from repro.core.session import Round, make_session
        from repro.engine.backend import BatchWork
        page, pages = self.e["page_size"], self.e["pages"]
        nxt = 0
        for chunks, lanes in ticks:
            work = BatchWork([], [], [], mixed=True)
            for n, lane in ([(ch, False) for ch in chunks]
                            + [(ctx, True) for ctx in lanes]):
                s = make_session(0.0, [Round(n, 1)])
                s.meta["context_ids"] = [2] * n
                k = -(-n // page)
                work.leases[s.sid] = tuple((nxt + i) % pages
                                           for i in range(k))
                nxt += k
                if lane:
                    s.resident_len = n - 1
                    s.meta["next_token"] = 2
                    work.decodes.append((s, 1))
                else:
                    work.prefills.append((s, n))
            self.backend.run_batch(work, 0.0)


def _powers_of_two(lo: int, hi: int) -> List[int]:
    """Each power of two from the first >= lo to the first >= hi."""
    out = [1 << max(0, lo - 1).bit_length()]
    while out[-1] < hi:
        out.append(2 * out[-1])
    return out


def warm_ticks(c: dict, spec: dict) -> List[Tuple[List[int], List[int]]]:
    """The ticks a mix's steady state produces, as work for ``System.warm``.

    The program buckets each dimension of its fused step to powers of
    two, so a tick per power of two reaches every bucket the mix needs.
    Decode lanes: ``clients`` of them (the decode batch for an open loop),
    the longest at each power of two of tokens from ``warm.longest_from``
    up to the longest context in the mix's pool. Prefill: none, or
    ``warm.packs`` whole prompts at once, at each power of two from the
    shortest prompt to the longest. A tick of another shape compiles in
    the window, and the run prints how many did."""
    e = c["engine"]
    b = bench_traffic.bounds(spec)
    w = spec["warm"]
    n_lanes = min(spec.get("clients", e["max_decode_batch"]),
                  e["max_decode_batch"])
    out = []
    for ctx in _powers_of_two(w["longest_from"], b["context"]):
        lanes = [ctx] + [e["page_size"]] * (n_lanes - 1)
        out.append(([], lanes))
        for n in _powers_of_two(*b["prompt"]):
            out += [([n] * k, lanes) for k in w["packs"]]
    return out


class Loop:
    """Submits the traffic on its due times, ticks the engine, and stamps
    every token on the harness clock."""

    def __init__(self, system: System, spec: dict, seed: int,
                 clock=time.monotonic, annotate: bool = False):
        self.sys = system
        self.spec = spec
        self.seed = seed
        self.clock = clock
        self.annotate = annotate
        self.vocab = system.cfg.vocab_size
        self.eng = system.new_engine()
        n = 4096 if spec["loop"] == "closed" else 2048
        self.reqs = bench_traffic.requests(spec, seed, n)
        self.next_req = 0
        self.recs: Dict[int, ReqRec] = {}
        self.ticks: List[TickRec] = []
        self.base = clock()
        self._work = None
        self._n_finished = 0
        self._n_rejected = 0
        rb = system.backend.run_batch
        self._rb = rb

        def run_batch(work, now):
            self._work = work
            t = self._cur
            if work.mixed and (work.prefills or work.decodes):
                t.packs = tuple((s.resident_len, ch)
                                for s, ch in work.prefills)
                t.lanes = tuple(s.resident_len + 1 for s, _ in work.decodes)
            t.rb0 = self.clock()
            with self._span("bench.run_batch"):
                out = rb(work, now)
            t.rb1 = self.clock()
            return out

        system.backend.run_batch = run_batch
        self._cur = None
        if spec["loop"] == "closed":
            stagger = 0.5 * spec["warm_in_s"] / spec["clients"]
            self.client_due = [self.base + i * stagger
                               for i in range(spec["clients"])]
        else:
            self.client_due = []

    def _span(self, name):
        if not self.annotate:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def restore(self) -> None:
        self.sys.backend.run_batch = self._rb

    # --- submission -------------------------------------------------------
    def _submit(self, due: float) -> ReqRec:
        from repro.core.session import Round, make_session
        req = self.reqs[self.next_req % len(self.reqs)]
        self.next_req += 1
        rounds = [Round(rs.append, rs.output, rs.tool_kind, rs.tool_s)
                  for rs in req.rounds]
        s = make_session(due - self.base, rounds, ideal_time=1.0)
        s.meta["context_ids"] = [int(x) for x in req.tokens(0, self.vocab)]
        rec = ReqRec(req, s.sid, [RoundRec(due)])
        rec.session = s
        self.recs[s.sid] = rec
        self.eng.submit(s)
        return rec

    def _submit_due(self, now: float) -> None:
        if self.spec["loop"] == "closed":
            for i, due in enumerate(self.client_due):
                if due is not None and due <= now:
                    self.client_due[i] = None
                    self._submit(due).client = i
        else:
            while (self.next_req < len(self.reqs) and self.base
                   + self.reqs[self.next_req].arrival <= now):
                self._submit(self.base + self.reqs[self.next_req].arrival)

    # --- one iteration ----------------------------------------------------
    def step(self) -> None:
        now = self.clock()
        self._submit_due(now)
        t = TickRec(now, now)
        self._cur = t
        self._work = None
        with self._span("bench.tick"):
            elapsed, progressed = self.eng.tick(now - self.base)
        t.t1 = self.clock()
        work = self._work
        if work is not None and not work.empty:
            self.ticks.append(t)
            if t.packs or t.lanes:
                self._stamp(work, t.t1)
        eng = self.eng
        while len(eng.finished) > self._n_finished:
            s = eng.finished[self._n_finished]
            self._n_finished += 1
            rec = self.recs.get(s.sid)
            if rec is not None:
                rec.done_at = t.t1
                self._client_free(rec, t.t1)
        while len(eng.rejected) > self._n_rejected:
            s = eng.rejected[self._n_rejected]
            self._n_rejected += 1
            rec = self.recs.get(s.sid)
            if rec is not None:
                rec.failed = True
                rec.done_at = t.t1
                self._client_free(rec, t.t1)
        if not progressed and elapsed == 0.0:
            time.sleep(0.002)

    def _client_free(self, rec: ReqRec, at: float) -> None:
        i = getattr(rec, "client", None)
        if i is not None:
            self.client_due[i] = at

    def _stamp(self, work, at: float) -> None:
        from repro.core.session import Phase
        for s, _g in work.decodes:
            rec = self.recs.get(s.sid)
            if rec is None:
                continue
            gen = s.meta.get("generated", [])
            seen = getattr(rec, "seen", 0)
            r = rec.rounds[s.cur_round]
            for tok in gen[seen:]:
                r.tokens.append(int(tok))
                r.stamps.append(at)
            rec.seen = len(gen)
            if s.phase in (Phase.TOOL,) and s.cur_round + 1 < len(
                    rec.req.rounds) and len(rec.rounds) == s.cur_round + 1:
                self._to_tool(rec, s)

    def _to_tool(self, rec: ReqRec, s) -> None:
        """The round ended in a tool call: the tool's output tokens join
        the session's context before it can resume."""
        from repro.core.session import KVState
        nxt = s.cur_round + 1
        s.meta["context_ids"].extend(
            int(x) for x in rec.req.tokens(nxt, self.vocab))
        path = {KVState.PINNED: "kept", KVState.SWAPPED: "restored"}.get(
            s.kv_state, "recomputed")
        tool_end = self.clock() + rec.req.rounds[s.cur_round].tool_s
        rec.rounds.append(RoundRec(tool_end, path=path))

    def run_until(self, t_end: float) -> None:
        while self.clock() < t_end:
            self.step()


@dataclass
class Window:
    t_open: float
    t_close: float
    ticks: List[TickRec]
    recs: List[ReqRec]
    dispatch0: dict
    dispatch1: dict
    compiles: int
    compile_s: float
    compile_times: List[float] = field(default_factory=list)
    trace: Optional[dict] = None
    cfg: object = None
    c: dict = None
    spec: dict = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def run_window(loop: Loop, seconds: float, compile_log,
               trace_dir: Optional[str] = None,
               trace_seconds: float = 4.0) -> Window:
    """Measure ``seconds`` of traffic. With ``trace_dir`` the profiler
    records the first ``trace_seconds`` of the window (between ticks)."""
    import jax
    st = loop.sys.backend.dispatch_stats
    d0 = dict(st)
    n0, s0 = compile_log.programs, compile_log.seconds
    t_open = loop.clock()
    first_tick = len(loop.ticks)
    trace = None
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
        tr0 = loop.clock()
        loop.run_until(tr0 + min(trace_seconds, seconds))
        tr1 = loop.clock()
        jax.profiler.stop_trace()
        trace = {"dir": trace_dir, "t0": tr0, "t1": tr1,
                 "first_tick": first_tick, "last_tick": len(loop.ticks)}
    loop.run_until(t_open + seconds)
    t_close = loop.clock()
    w = Window(t_open, t_close, loop.ticks[first_tick:],
               list(loop.recs.values()), d0, dict(st),
               compile_log.programs - n0, compile_log.seconds - s0,
               compile_log.times[n0:], cfg=loop.sys.cfg, c=loop.sys.c,
               spec=loop.spec)
    if trace is not None:
        trace["ticks"] = loop.ticks[trace["first_tick"]:trace["last_tick"]]
        w.trace = trace
    return w


# --- what the window shows -------------------------------------------------

def window_rounds(w: Window):
    """(request, round index, round record) for rounds due in the window."""
    for rec in w.recs:
        for i, r in enumerate(rec.rounds):
            if w.t_open <= r.due < w.t_close:
                yield rec, i, r


def compiled_ticks(w: Window) -> List[dict]:
    """The work of each window tick during which a program compiled (or
    loaded from the cache): what the warm-up missed."""
    out = []
    for at in w.compile_times:
        t = next((t for t in w.ticks if t.t0 <= at <= t.t1), None)
        out.append({"at": at - w.t_open} if t is None else {
            "at": at - w.t_open, "packs": list(t.packs),
            "lanes": len(t.lanes), "longest": max(t.lanes, default=0)})
    return out


def counts(w: Window) -> Tuple[int, int]:
    """(attempted, failed): rounds due in the window, and those refused."""
    attempted = failed = 0
    for rec, _i, _r in window_rounds(w):
        attempted += 1
        failed += int(rec.failed)
    return attempted, failed


def sample_for_check(w: Window, seed: int) -> List[ReqRec]:
    """Requests finished in the window, drawn from the seed: the one with
    the most served tokens, then others until the mix's ``sample`` says."""
    want = w.spec["sample"]
    done = [r for r in w.recs if not r.failed and r.done_at is not None
            and w.t_open <= r.done_at <= w.t_close]
    if not done:
        return []

    def served(r):
        return sum(len(x.tokens) for x in r.rounds)

    done.sort(key=lambda r: r.sid)
    longest = max(done, key=lambda r: (served(r) + sum(
        rs.append for rs in r.req.rounds), -r.sid))
    rng = np.random.default_rng([seed % 2**63, 12])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    pick = [longest]
    n = served(longest)
    for r in rest:
        if len(pick) >= want["max_requests"] or n >= want["min_tokens"]:
            break
        pick.append(r)
        n += served(r)
    return pick


def check_sequence(rec: ReqRec, vocab: int):
    """(sequence, positions, served tokens, complete) of one request, as
    its client sees it: the appended tokens and the served tokens of every
    round in order. Each served token is compared at the position before
    it: a round's first at the last token appended before it."""
    seq: List[int] = []
    pos: List[int] = []
    toks: List[int] = []
    complete = len(rec.rounds) == len(rec.req.rounds)
    for i, r in enumerate(rec.rounds):
        seq.extend(int(x) for x in rec.req.tokens(i, vocab))
        complete &= len(r.tokens) == rec.req.rounds[i].output
        for tok in r.tokens:
            pos.append(len(seq) - 1)
            toks.append(tok)
            seq.append(tok)
    if toks:
        seq = seq[:-1]        # the last served token predicts nothing
    return (np.asarray(seq, np.int32), np.asarray(pos, np.int32),
            np.asarray(toks, np.int32), complete)
