"""Plain float32 reference of a served decoder, a whole sequence at a time.

Written from the published architecture, not from the program: the
embedding, the configuration's decoder layers (``layer`` of its
architecture module, ``model.arch``), a final RMSNorm with its gain stored
as an offset from 1, and the unembedding. Weights are redrawn from the
seed one layer at a time (``layer_weights``), upcast to float32, so the
reference never holds more than one layer and imports nothing of the
program.

``logits_at`` runs whole sequences and returns the logits at chosen
positions; the embedding and the unembedding stay in bf16 and are upcast
a row or a block of the vocabulary at a time. With ``quant="fp8"`` every
product with a weight takes its inputs rounded to float8 e4m3 (weights
per output column, activations per row, each scaled to the format's
range): the control that stands in for a lower-precision program.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence

import numpy as np

import model as bench_model

F8_MAX = 448.0
HEAD_BLOCK = 16384       # vocabulary columns unembedded at a time
QUANTS = (None, "fp8")   # float32, and the fp8 control


def _e4m3(jnp, x):
    """Round float32 ``x`` (|x| <= 448) to the nearest float8 e4m3
    value, ties to even, in float32 arithmetic: 3 mantissa bits, normal
    exponents down to -6, subnormal steps of 2^-9."""
    _m, ex = jnp.frexp(x)
    step = jnp.exp2(jnp.maximum(ex - 1, -6).astype(jnp.float32) - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -F8_MAX, F8_MAX)


def _fp8(jnp, x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return _e4m3(jnp, x / s) * s


def _mm(jnp, x, w, quant):
    """x (..., K) @ w (K, N) in float32 at full precision."""
    if quant == "fp8":
        x = _fp8(jnp, x, -1)
        w = _fp8(jnp, w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rms(jnp, x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def _rope(jnp, x, pos, theta):
    """x (..., S, H, Dh); pos (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_fn(c: dict, quant: Optional[str]):
    """(weights f32, x (S, D)) -> x after one decoder layer: the
    architecture's ``layer`` over one sequence, its products with weights
    through ``_mm`` and attention's at full precision."""
    import jax.numpy as jnp
    arch = bench_model.arch(c)

    def mm(x, w):
        return _mm(jnp, x, w, quant)

    def attn_mm(a, b):
        return jnp.matmul(a, b, precision="highest")

    return lambda w, x: arch.layer(c, w, x[None], mm, attn_mm)[0]


def layer_getter(c: dict):
    """Jitted (key, layer) -> that layer's weights, upcast to float32."""
    return _programs(c)["layer"]


_PROGRAMS: Dict[str, dict] = {}


def _programs(c: dict) -> dict:
    """The jitted pieces of the reference for configuration ``c``, built
    once a process, so that repeated calls compile nothing new."""
    import jax
    import jax.numpy as jnp
    tag = json.dumps(c, sort_keys=True)
    if tag not in _PROGRAMS:
        f32, bf16 = jnp.float32, jnp.bfloat16
        _PROGRAMS[tag] = {
            "embed": jax.jit(lambda k: bench_model.embed_weights(c, k, bf16)),
            "layer": jax.jit(lambda k, l: jax.tree.map(
                lambda a: a.astype(f32),
                bench_model.arch(c).layer_weights(
                    c, bench_model.layer_key(k, l), bf16))),
            "head": jax.jit(lambda k: bench_model.head_weights(c, k, bf16)),
            "gain": jax.jit(lambda k: bench_model.final_norm(c, k, bf16)
                            .astype(f32)),
            "apply": {qn: jax.jit(layer_fn(c, qn)) for qn in QUANTS},
            "unembed": {qn: jax.jit(
                lambda h, w, qn=qn: _mm(jnp, h, w.astype(f32), qn))
                for qn in QUANTS},
        }
    return _PROGRAMS[tag]


def _pad_len(n: int, step: int = 512) -> int:
    return -(-n // step) * step


def logits_at(c: dict, seed: int, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray],
              quants: Sequence[Optional[str]] = (None,)
              ) -> Dict[Optional[str], List[np.ndarray]]:
    """Logits (len(positions[i]), V) of sequence ``seqs[i]`` at
    ``positions[i]``, for each precision in ``quants``. Every sequence is
    right-padded to one length, the longest rounded up to a multiple of
    512 (causal: padding never reaches a real position), so one compiled
    layer serves them all. Layer by layer: one layer's weights live at a
    time."""
    import jax.numpy as jnp
    fn = _programs(c)
    key = bench_model.root_key(seed)
    embed = fn["embed"](key)
    S = _pad_len(max(len(s) for s in seqs))
    xs = {}
    for qn in quants:
        xs[qn] = []
        for s in seqs:
            ids = np.zeros(S, np.int32)
            ids[:len(s)] = s
            xs[qn].append(embed[jnp.asarray(ids)].astype(jnp.float32))
    del embed

    for layer in range(c["num_hidden_layers"]):
        w = fn["layer"](key, layer)
        for qn in quants:
            xs[qn] = [fn["apply"][qn](w, x) for x in xs[qn]]
        del w

    head = fn["head"](key)
    gain = fn["gain"](key)
    eps = float(c["rms_norm_eps"])
    V = head.shape[1]
    out = {}
    for qn in quants:
        rows = []
        for x, pos in zip(xs[qn], positions):
            h = _rms(jnp, x[jnp.asarray(pos)], gain, eps)
            rows.append(np.concatenate(
                [np.asarray(fn["unembed"][qn](h, head[:, j:j + HEAD_BLOCK]))
                 for j in range(0, V, HEAD_BLOCK)], -1))
        out[qn] = rows
    return out


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Largest amount by which a chosen token's reference logit lies below
    the reference's best, over all positions."""
    best = ref_logits.max(axis=-1)
    chosen = np.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return float(np.max(best - chosen)) if len(tokens) else 0.0
