"""From a profiler trace (``.xplane.pb``) to intervals and durations.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip and ``XLA Modules`` one per program
run. Host spans that the harness writes with ``TraceAnnotation`` sit on
the host plane. All events share the trace's clock (nanoseconds).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("bench.tick", "bench.run_batch", "bench.train_step")


CONTAINERS = ("while", "conditional", "call")


@dataclass
class Event:
    name: str               # short op name: ``paged_attention_fused.5``
    start: float            # seconds on the trace clock
    dur: float              # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> op events
    modules: Dict[str, List[Event]]    # device plane -> program events
    host: List[Event]                  # the harness's spans


def find(dir_: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(dir_, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [Event(short_name(e.name), e.start_ns * 1e-9,
                             e.duration_ns * 1e-9) for e in line.events]
                dest = ops if line.name == OPS_LINE else modules
                dest.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
    return Trace(ops, modules, host)


def short_name(name: str) -> str:
    """``%while.2 = (s32[], ...) while(...)`` -> ``while.2``."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_container(ev: Event) -> bool:
    """A loop or call whose time is its body's ops' time."""
    return ev.name.split(".")[0] in CONTAINERS


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(events: Sequence[Event]) -> float:
    return sum(e - s for s, e in union((ev.start, ev.end) for ev in events))


def matching(events: Sequence[Event], needles: Sequence[str]) -> List[Event]:
    """Events whose short name starts with any of ``needles``."""
    return [e for e in events if e.name.startswith(tuple(needles))]


def idle_gaps(events: Sequence[Event], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """Intervals of [t0, t1] in which no device op ran."""
    gaps = []
    cur = t0
    for s, e in union((ev.start, ev.end) for ev in events):
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    return [(s, e) for s, e in gaps if e > s]


def label_gap(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the harness was doing during most of a device idle gap: in the
    engine tick outside the backend (``tick host``), in ``run_batch``
    (packing, dispatch, readback), or neither (``between ticks``)."""
    s, e = gap
    dur = {"run_batch": 0.0, "tick": 0.0}
    for h in host:
        ov = min(e, h.end) - max(s, h.start)
        if ov > 0:
            dur["run_batch" if h.name == "bench.run_batch" else "tick"] += ov
    host_only = dur["tick"] - dur["run_batch"]
    best = max((dur["run_batch"], "packing and dispatch (run_batch)"),
               (host_only, "tick host (admission, policy, batch)"),
               ((e - s) - dur["tick"], "between ticks (waiting)"))
    return best[1]
