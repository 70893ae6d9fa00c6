"""Helpers the metric readers share."""
from __future__ import annotations

import numpy as np

# device op names of each kernel in the trace (the HLO custom call takes the
# name of the Pallas entry point: ``paged_attention_fused.5``)
DECODE_KERNEL = ("paged_attention_fused",)
FLASH_KERNEL = ("paged_flash_attention",)
STEP_MODULE = ("_mixed",)


def percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q)) if len(
        values) else None


def ttfts(w, first_round: bool):
    """Seconds from each round's due time to its first client-visible
    token, for rounds due in the window; a round with none by the close
    counts at its age then."""
    import harness
    out = []
    for _rec, i, r in harness.window_rounds(w):
        if (i == 0) != first_round:
            continue
        t = next((s for s in r.stamps if s >= r.due), None)
        out.append((t if t is not None and t <= w.t_close else w.t_close)
                   - r.due)
    return out


def gaps_ms(w):
    """Every gap between consecutive client-visible tokens of one round
    that ends in the window, in milliseconds."""
    out = []
    for rec in w.recs:
        for r in rec.rounds:
            st = r.stamps
            out += [1e3 * (b - a) for a, b in zip(st, st[1:])
                    if w.t_open <= b < w.t_close]
    return out


def traced_ticks(ctx):
    tr = ctx.get("trace")
    return tr["ticks"] if tr else []


def device_ops(ctx):
    tr = ctx.get("trace_data")
    if tr is None:
        return []
    return [e for evs in tr.ops.values() for e in evs]


def roofline(flops, bytes_, seconds, pk):
    """Least time the chip could take over the time taken, in percent."""
    if seconds <= 0 or (flops <= 0 and bytes_ <= 0):
        return None
    t_min = max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * t_min / seconds


def scope_ms(ctx, group):
    """Device milliseconds per traced train step in ``scopes.GROUPS[group]``,
    every part and phase (``scopes.group_ms`` of the window's split);
    ``None`` without a split or where none of the group's scopes ran."""
    import scopes
    split = ctx.get("scope_split")
    if not split or not any(k[0] in scopes.GROUPS[group] for k in split):
        return None
    return scopes.group_ms(split)[group]


def split_ms(ctx, scope=None, part=None, phase=None):
    """Device milliseconds per traced train step with that scope, part and
    phase (``None``: any; ``scopes.layer_ms`` of the window's split);
    ``None`` without a split or where no op had them."""
    import scopes
    split = ctx.get("scope_split")
    return scopes.layer_ms(split, scope, part, phase) if split else None
