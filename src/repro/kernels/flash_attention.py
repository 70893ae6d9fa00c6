"""Pallas TPU flash attention (prefill/train hot path).

Supports causal masking, sliding-window (gemma2 local layers), logit softcap,
GQA, and chunked-prefill query offsets. Online-softmax accumulation runs in
VMEM scratch across the innermost (sequential) kv-block grid dimension;
block shapes are MXU/VREG aligned (multiples of (8,128) in f32).

The online-softmax block update (``online_softmax_init`` /
``online_softmax_block``) is shared with the paged variant in
``paged_flash_attention.py`` — the two kernels differ only in how KV blocks
reach VMEM (contiguous grid stride here, scalar-prefetched block-table
indirection there) and in how the mask is built.

Ragged query lengths are handled wrapper-side: ``Sq`` is padded up to a
multiple of ``block_q`` (padded rows attend causally past the real tail and
are sliced off the output), so chunked-prefill callers never have to align
chunk lengths to the block shape. ``Skv`` stays asserted — KV buffers are
cache allocations, always block-aligned.

TARGET is TPU; ``interpret=None`` resolves by backend (compiled on TPU,
interpreter elsewhere — the kernel is validated on CPU against
``ref.flash_attention_ref``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def online_softmax_block(s, v, m_scr, l_scr, acc_scr, *,
                         pv_dims=(((2,), (0,)), ((), ()))):
    """One online-softmax accumulation step over a scored KV block.

    s: (G, bq, bk) f32 masked scores; v: (bk, D) f32;
    scratch: m/l (G, bq, 1) f32, acc (G, bq, D) f32 — updated in place.
    ``pv_dims`` is the ``p @ v`` contraction (the paged kernel batches its
    KV heads: s (Hkv, rows, page), v (page, Hkv, D)).
    """
    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v, pv_dims, preferred_element_type=F32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new


def online_softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def online_softmax_finish(o_ref, m_scr, l_scr, acc_scr):
    """Write the normalized accumulator to the output block. The 1e-30
    denominator floor keeps fully-masked (padded) rows finite instead of
    NaN — their garbage is sliced off by the wrapper."""
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], q_offset: int, block_q: int,
            block_k: int, n_kv_blocks: int):
    """Grid: (B, Hkv, n_q_blocks, n_kv_blocks); each block carries the G
    query heads of one KV head.

    q_ref/o_ref: (G, block_q, D); k_ref/v_ref: (block_k, D);
    scratch: m/l (G, block_q, 1) f32, acc (G, block_q, D) f32.
    """
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        online_softmax_init(m_scr, l_scr, acc_scr)

    q = q_ref[...].astype(F32) * scale            # (G, bq, D)
    k = k_ref[...].astype(F32)                    # (bk, D)
    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=F32)   # (G, bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q, block_k), 1)
    kv_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q, block_k), 2)
    mask = jnp.ones((1, block_q, block_k), bool)
    if causal:
        mask = kv_pos <= q_pos
    if window is not None:
        mask = mask & (q_pos - kv_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    online_softmax_block(s, v_ref[...].astype(F32), m_scr, l_scr, acc_scr)

    @pl.when(kj == n_kv_blocks - 1)
    def _finish():
        online_softmax_finish(o_ref, m_scr, l_scr, acc_scr)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset", "block_q",
                     "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    ``Sq`` may be any length (padded to ``block_q`` internally); ``Skv``
    must stay a multiple of ``block_k``. ``interpret`` defaults by backend:
    compiled on TPU, interpreter everywhere else — resolved at trace time,
    so the jit cache keys on the resolved static value.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Skv % block_k == 0, (Skv, block_k)
    Sq_pad = -(-Sq // block_q) * block_q
    if Sq_pad != Sq:            # ragged final q block: pad, slice off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_pad - Sq), (0, 0)))
    n_q = Sq_pad // block_q
    n_kv = Skv // block_k
    qg = q.reshape(B, Hkv, G, Sq_pad, D)

    kernel = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, block_q=block_q, block_k=block_k,
        n_kv_blocks=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, None, G, block_q, D),
                         lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, block_q, D),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Sq_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, block_q, 1), F32),
            pltpu.VMEM((G, block_q, 1), F32),
            pltpu.VMEM((G, block_q, D), F32),
        ],
        interpret=interpret,
    )(qg, k, v)
    out = out.reshape(B, Hq, Sq_pad, D)
    return out[:, :, :Sq] if Sq_pad != Sq else out
