"""One generator for every traffic mix: reads ``traffic/<name>.json``.

A mix is plain data. ``loop: closed`` keeps ``clients`` requests in flight
(each client sends its next request when the last one finished);
``loop: open`` sends sessions at Poisson times, ``sessions_per_s``.
Sizes are lognormal by median and sigma, clipped to ``[min, max]``.

The work is a fixed sequence of ``pool`` request shapes, cycled: sizes at
the ``pool`` quantiles of their distributions (stratified, so a short
sequence stands for the whole distribution), the number of rounds, the
tool calls and the open loop's arrival times, all drawn from
``sizes_seed`` alone. The run's seed draws the token ids only, so every
seed has the same amount of work, in the same order. Tool durations
follow the bimodal mixture of ``repro.workloads.generator.TOOL_KINDS``
(lognormal by mean), times ``tools.scale``.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass
class RoundSpec:
    append: int                    # prompt tokens (round 0) or tool output
    output: int                    # decode tokens the engine is asked for
    tool_kind: Optional[str] = None
    tool_s: float = 0.0


@dataclass
class Request:
    rid: int
    rounds: List[RoundSpec]
    arrival: float = 0.0           # open loop: seconds after the start
    seed: int = 0
    _tokens: List[np.ndarray] = field(default_factory=list, repr=False)

    def tokens(self, r: int, vocab: int) -> np.ndarray:
        """Token ids appended before round ``r``, drawn from (seed, rid)."""
        if not self._tokens:
            rng = np.random.default_rng([self.seed % 2**63, self.rid])
            self._tokens = [rng.integers(2, vocab, rs.append, dtype=np.int64)
                            .astype(np.int32) for rs in self.rounds]
        return self._tokens[r]


def load(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    with open(path) as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"{path}: name {spec.get('name')!r} != {name!r}")
    return spec


def _quantiles(rng, d: dict, n: int) -> List[int]:
    """``n`` sizes at the midpoint quantiles of the clipped lognormal
    ``d``, in an order drawn from ``rng``."""
    z = NormalDist()
    v = [int(np.clip(round(math.exp(math.log(d["median"]) + d["sigma"]
                                    * z.inv_cdf((i + 0.5) / n))),
                     d["min"], d["max"])) for i in range(n)]
    return [v[i] for i in rng.permutation(n)]


def _tool(rng, tools: dict):
    kinds = sorted(tools["kinds"])
    kind = kinds[int(rng.integers(len(kinds)))]
    p_short, m_s, sg_s, m_l, sg_l = tools["kinds"][kind]
    mean, sigma = (m_s, sg_s) if rng.random() < p_short else (m_l, sg_l)
    dur = float(rng.lognormal(math.log(mean) - sigma ** 2 / 2, sigma))
    return kind, dur * tools["scale"]


def size_pool(spec: dict) -> List[List[RoundSpec]]:
    """The mix's sequence of request shapes (independent of the seed)."""
    rng = np.random.default_rng(spec["sizes_seed"])
    lo, hi = spec["rounds"]
    n = spec["pool"]
    n_rounds = [int(rng.integers(lo, hi + 1)) for _ in range(n)]
    prompts = _quantiles(rng, spec["prompt_tokens"], n)
    outputs = _quantiles(rng, spec["output_tokens"], sum(n_rounds))
    n_appends = sum(n_rounds) - n
    appends = (_quantiles(rng, spec["append_tokens"], n_appends)
               if n_appends else [])
    pool = []
    for k in range(n):
        rounds = []
        for r in range(n_rounds[k]):
            append = prompts[k] if r == 0 else appends.pop()
            kind, dur = (_tool(rng, spec["tools"]) if r < n_rounds[k] - 1
                         else (None, 0.0))
            rounds.append(RoundSpec(append, outputs.pop(), kind, dur))
        pool.append(rounds)
    return pool


def requests(spec: dict, seed: int, n: int) -> List[Request]:
    """The first ``n`` requests: the mix's sequence of shapes, cycled, with
    arrival times for an open loop; token ids drawn from ``seed``."""
    pool = size_pool(spec)
    rng = np.random.default_rng([spec["sizes_seed"], 1])
    out = []
    t = 0.0
    for rid in range(n):
        if spec["loop"] == "open":
            t += float(rng.exponential(1.0 / spec["sessions_per_s"]))
        out.append(Request(rid, pool[rid % len(pool)], arrival=t,
                           seed=seed))
    return out


def bounds(spec: dict) -> dict:
    """Token bounds of the mix's pool of sizes, for sizing the warm-up:
    the shortest and longest first prompt, and the longest context a
    request reaches."""
    pool = size_pool(spec)
    prompts = [rounds[0].append for rounds in pool]
    return {"prompt": (min(prompts), max(prompts)),
            "context": max(sum(r.append + r.output for r in rounds)
                           for rounds in pool)}
