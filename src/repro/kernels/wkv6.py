"""Pallas TPU kernel for the RWKV6 (WKV) recurrence — chunked formulation.

The per-channel decayed recurrence
    S_t = diag(w_t) S_{t-1} + k_t v_t^T ;  o_t = r_t S_{t-1} + (r_t.(u*k_t)) v_t
is computed chunk-by-chunk: intra-chunk terms become two (C,K)x(K,C)
MXU matmuls with a strictly-lower-triangular mask, and the (K,K) state is
carried in VMEM scratch across the sequential chunk-grid dimension — the
TPU-native adaptation of RWKV's CUDA kernel (no warp-level primitives; the
state tile lives in VMEM instead of registers/smem).

TARGET is TPU; validated on CPU with ``interpret=True`` against
``ref.wkv6_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sT_ref, s_scr,
            *, chunk: int, n_chunks: int):
    """Grid: (B, H, n_chunks). r/k/v/lw_ref: (C, K); u_ref: (K,);
    s0_ref/sT_ref: (K, K); o_ref: (C, K); s_scr: (K, K) f32."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[...].astype(F32)

    S = s_scr[...]
    r = r_ref[...].astype(F32)
    k = k_ref[...].astype(F32)
    v = v_ref[...].astype(F32)
    lw = lw_ref[...].astype(F32)
    u = u_ref[...].astype(F32)

    cum = jnp.cumsum(lw, axis=0)                      # (C, K) inclusive
    half = 0.5 * cum[-1:]
    r_dec = r * jnp.exp(cum - lw)                     # decay excl. current
    o_inter = jax.lax.dot(r_dec, S, preferred_element_type=F32)   # (C, K)
    q_ = r * jnp.exp(cum - lw - half)
    k_ = k * jnp.exp(half - cum)
    att = jax.lax.dot_general(q_, k_, (((1,), (1,)), ((), ())),
                              preferred_element_type=F32)         # (C, C)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(tj < ti, att, 0.0)                # strictly lower tri
    o_intra = jax.lax.dot(att, v, preferred_element_type=F32)
    bonus = jnp.sum(r * u[None] * k, axis=1, keepdims=True)       # (C, 1)
    o_ref[...] = (o_inter + o_intra + bonus * v).astype(o_ref.dtype)

    total = cum[-1]                                   # (K,)
    k_dec = k * jnp.exp(total[None] - cum)
    s_new = jnp.exp(total)[:, None] * S + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=F32)
    s_scr[...] = s_new

    @pl.when(c == n_chunks - 1)
    def _finish():
        sT_ref[...] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, state, *, chunk: int = 32,
         interpret: Optional[bool] = None):
    """r/k/v/w: (B, T, H, K) [w in (0,1)]; u: (H, K); state: (B, H, K, K) f32.

    Returns (o (B, T, H, K) f32, final state (B, H, K, K) f32).
    ``interpret=None`` resolves by backend: compiled on TPU, interpreter
    elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, K = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk
    # layout: (B, H, T, K) so each grid cell reads a contiguous (C, K) tile.
    rt, kt, vt = (jnp.moveaxis(a, 1, 2) for a in (r, k, v))
    lw = jnp.log(jnp.clip(jnp.moveaxis(w, 1, 2).astype(F32), 1e-12, 1.0))

    seq_spec = pl.BlockSpec((None, None, chunk, K), lambda b, h, c: (b, h, c, 0))
    st_spec = pl.BlockSpec((None, None, K, K), lambda b, h, c: (b, h, 0, 0))
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    o, sT = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((None, K), lambda b, h, c: (h, 0)),
                  st_spec],
        out_specs=[seq_spec, st_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, K), F32),
                   jax.ShapeDtypeStruct((B, H, K, K), F32)],
        scratch_shapes=[pltpu.VMEM((K, K), F32)],
        interpret=interpret,
    )(rt, kt, vt, lw, u, state)
    return jnp.moveaxis(o, 1, 2), sT
