"""Pallas TPU paged attention (decode hot path).

vLLM-style block-table indirection, adapted to TPU: the block table and
per-request lengths are *scalar-prefetched* so the KV page index_map can
steer HBM->VMEM DMA directly from the table (no gather materialization).
Online-softmax accumulates across the sequential page-grid dimension in
VMEM scratch. Page size defaults to 32 tokens so a (page, head_dim) tile is
VREG-aligned on TPU (the repo-wide adaptation noted in DESIGN.md §3).

``paged_attention_fused`` additionally folds the decode-side KV *write*
into the kernel prologue: the new token's k/v arrive as VMEM inputs, a
dynamic async copy lands them in their ``(write_page, write_offset)`` pool
slot at the first grid step, and the page pool rides through as aliased
ANY-space outputs — replacing the separate ``cache.at[...].set`` dispatch
(one full read-modify-write of the touched pages) that used to precede the
attention call. The accumulation never trusts the slot being written: page
reads are masked at ``kv_pos < lengths - 1`` and the final token's
contribution is added from the VMEM inputs at the last grid step, so the
in-flight HBM write cannot race the block pipeline's page fetches.

TARGET is TPU; validated on CPU with ``interpret=True`` against
``ref.paged_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page: int, n_pages: int):
    """Grid: (B, max_pages). q_ref/o_ref: (Hkv, G, D); k/v_ref: (page, Hkv, D)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(F32) * scale                    # (Hkv, G, D)
    k = k_ref[...].astype(F32)                            # (page, Hkv, D)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=F32)   # (Hkv, G, page)
    kv_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = kv_pos < len_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                   # (Hkv, G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v_ref[...].astype(F32),
                             (((2,), (0,)), ((0,), (1,))),
                             preferred_element_type=F32)  # (Hkv, G, D)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def _fused_kernel(table_ref, len_ref, wp_ref, wo_ref, q_ref, k_ref, v_ref,
                  kn_ref, vn_ref, o_ref, kp_out, vp_out,
                  m_scr, l_scr, acc_scr, k_sem, v_sem, *, scale: float,
                  page: int, n_pages: int):
    """Grid: (B, max_pages). q_ref/o_ref: (Hkv, G, D); k/v_ref: (page, Hkv,
    D) steered by the table; kn/vn_ref: (1, Hkv, D) the new token's KV (the
    unit row axis is kept, not squeezed, so the prologue DMA's source and
    its (1, Hkv, D) pool-slot target agree in rank when compiled);
    kp/vp_out: the pool in HBM (ANY space, aliased to the blocked k/v page
    inputs — same underlying buffers, written via dynamic async copy).

    Write/read discipline: the new token's pool slot is its own sequence
    position ``lengths[b] - 1`` (the caller's contract), so page reads mask
    ``kv_pos < lengths[b] - 1`` and the new token joins the online softmax
    from VMEM at the last grid step — the async HBM write launched in the
    prologue can land whenever it likes without racing a page fetch.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        slot = (pl.ds(wp_ref[b], 1), wo_ref[b])
        kcp = pltpu.make_async_copy(kn_ref, kp_out.at[slot], k_sem)
        vcp = pltpu.make_async_copy(vn_ref, vp_out.at[slot], v_sem)
        kcp.start()
        vcp.start()
        kcp.wait()
        vcp.wait()

    q = q_ref[...].astype(F32) * scale                    # (Hkv, G, D)
    k = k_ref[...].astype(F32)                            # (page, Hkv, D)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=F32)   # (Hkv, G, page)
    kv_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = kv_pos < len_ref[b] - 1
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                   # (Hkv, G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v_ref[...].astype(F32),
                             (((2,), (0,)), ((0,), (1,))),
                             preferred_element_type=F32)  # (Hkv, G, D)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        # the new token, straight from VMEM: one more online-softmax step
        # over a single-entry kv block at position lengths[b] - 1
        kn = kn_ref[0].astype(F32)                        # (Hkv, D)
        s_new = jax.lax.dot_general(q, kn, (((2,), (1,)), ((0,), (0,))),
                                    preferred_element_type=F32)[..., None]
        m_prev2 = m_scr[...]
        m_fin = jnp.maximum(m_prev2, s_new)
        p_new = jnp.exp(s_new - m_fin)                    # (Hkv, G, 1)
        alpha2 = jnp.exp(m_prev2 - m_fin)
        l_fin = alpha2 * l_scr[...] + p_new
        acc = acc_scr[...] * alpha2 + \
            p_new * vn_ref[0].astype(F32)[:, None, :]
        o_ref[...] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_fused(q, k_pages, v_pages, block_table, lengths, k_new,
                          v_new, write_pages, write_offsets, *,
                          interpret: bool = None):
    """Decode attention with the KV write fused into the kernel.

    q: (B, Hq, D); k/v_pages: (P, page, Hkv, D); block_table: (B, max_pages)
    int32; lengths: (B,) int32 valid kv tokens INCLUDING the new token;
    k/v_new: (B, Hkv, D) the new token's KV; write_pages/write_offsets: (B,)
    its pool slot. Contract: the slot is the table position of sequence
    index ``lengths[b] - 1`` (idle lanes: length 1, slot (scratch, 0), an
    all-scratch table row — the contract holds degenerately).

    Returns ``(o (B, Hq, D), k_pages, v_pages)`` with the pools updated in
    place (the inputs are donated to the aliased outputs under jit).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, D = q.shape
    P, page, Hkv, _ = k_pages.shape
    max_pages = block_table.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)

    def q_map(b, j, *_):
        return (b, 0, 0, 0)

    def kv_map(b, j, table, *_):
        return (table[b, j], 0, 0, 0)

    def new_map(b, j, *_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((None, Hkv, G, D), q_map),
            pl.BlockSpec((None, page, Hkv, D), kv_map),
            pl.BlockSpec((None, page, Hkv, D), kv_map),
            pl.BlockSpec((1, Hkv, D), new_map),
            pl.BlockSpec((1, Hkv, D), new_map),
        ],
        out_specs=[
            pl.BlockSpec((None, Hkv, G, D), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), F32),
            pltpu.VMEM((Hkv, G, 1), F32),
            pltpu.VMEM((Hkv, G, D), F32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(_fused_kernel, scale=D ** -0.5, page=page,
                               n_pages=max_pages)
    # operand indices for the aliases count the scalar-prefetch args too:
    # (table, lengths, wp, wo, qg, k_pages, v_pages, k_new, v_new) -> the
    # blocked pool inputs (operands 5 and 6) alias the ANY-space outputs
    o, kp, vp = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
    )(block_table, lengths, write_pages, write_offsets, qg, k_pages, v_pages,
      k_new, v_new)
    return o.reshape(B, Hq, D), kp, vp


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_table, lengths, *,
                    interpret: bool = None):
    """q: (B, Hq, D); k/v_pages: (P, page, Hkv, D);
    block_table: (B, max_pages) int32; lengths: (B,) int32 -> (B, Hq, D).

    ``interpret`` defaults by backend: compiled on TPU, interpreter
    everywhere else (this is a TPU Mosaic kernel — CPU CI and GPU hosts
    must not try to lower it) — resolved at trace time, so the jit cache
    keys on the resolved static value."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, D = q.shape
    P, page, Hkv, _ = k_pages.shape
    max_pages = block_table.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)

    def q_map(b, j, table, lens):
        return (b, 0, 0, 0)

    def kv_map(b, j, table, lens):
        return (table[b, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((None, Hkv, G, D), q_map),
            pl.BlockSpec((None, page, Hkv, D), kv_map),
            pl.BlockSpec((None, page, Hkv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((None, Hkv, G, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), F32),
            pltpu.VMEM((Hkv, G, 1), F32),
            pltpu.VMEM((Hkv, G, D), F32),
        ],
    )
    kernel = functools.partial(_kernel, scale=D ** -0.5, page=page,
                               n_pages=max_pages)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(block_table, lengths, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
