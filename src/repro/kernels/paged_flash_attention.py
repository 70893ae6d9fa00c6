"""Pallas TPU paged flash attention (chunked-prefill hot path).

Gather-free chunked prefill: the flash kernel's KV grid dimension walks a
*scalar-prefetched block table* instead of a contiguous cache, so each
(page-sized) KV block is DMA'd HBM->VMEM **in place** from wherever the
page pool holds it — no ``pages[table]`` gather, no O(context) dense copy
per chunk. Per chunk the read side touches ``Np * page`` tokens of KV once
(what the attention math itself needs) instead of three times (gather read
+ dense-copy write + kernel read), which is what the
``prefill_hbm_bytes_per_chunk`` figure in ``benchmarks/paged_runner_bench``
accounts.

Grid and table indirection
    grid = (B, n_q_blocks, Np) with ``num_scalar_prefetch=3``
    (``block_table (B, Np)``, ``kv_len (B,)``, ``q_offset (B,)``). One grid
    step holds every query head of one q block, ``(Hkv, G, block_q, D)``,
    and one pool page of every KV head, ``(page, Hkv, D)`` — a block whose
    last two dims equal the pool's own, as the TPU (8, 128) tiling rule
    requires, and which fetches each page once for all heads. The KV
    BlockSpec index_map returns ``(table[b, j], 0, 0, 0)`` — the scalar
    prefetch happens before the grid runs, so the DMA engine can steer
    every page fetch directly off the table with no device round trip. The
    innermost (page) dimension is sequential: online-softmax state for the
    current q block lives in VMEM scratch across it, exactly as in
    ``flash_attention``, whose block-update helpers this kernel reuses
    (batched over the KV heads, as the decode kernel's dot products are).

VMEM scratch budget
    m/l: 2 * (Hkv, G * block_q, 1) f32 and acc: (Hkv, G * block_q, D) f32
    — for Hq=16, block_q=128, D=128 that is 5 MiB once m/l pad their unit
    last dim to 128 lanes, plus the double-buffered q/o blocks (2 MiB in
    bf16) and k/v pages; the TPU compiler accepts it at Qwen2.5-3B widths
    (``tests/test_tpu_compile.py``).

Masking rules
    * **causality**: queries sit at absolute kv positions ``q_offset[b] +
      i`` (chunked prefill: the chunk is the tail of the sequence so far);
      a score survives iff ``kv_pos <= q_pos``.
    * **scratch page / stale tail**: ``kv_pos < kv_len[b]`` masks every
      slot past the written prefix — the table is scratch-padded (its last
      entry is always the scratch page) and pool pages may hold stale
      garbage beyond the sequence tail (CoW tails, freed leases). Masked
      scores hit -1e30 before the online max, so garbage never reaches the
      accumulator.
    * **ragged final q block**: ``Sq`` is padded wrapper-side to a multiple
      of ``block_q``; padded rows get q positions past the real tail (all
      kv visible), stay finite through the 1e-30 denominator floor, and are
      sliced off the returned output.

TARGET is TPU; ``interpret=None`` resolves by backend (compiled on TPU,
interpreter elsewhere). Validated on CPU against
``ref.paged_flash_attention_ref`` (which *is* a gather — it is the oracle,
not the hot path).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, F32, online_softmax_block,
                                           online_softmax_init)


def _kernel(table_ref, len_ref, qoff_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page: int, block_q: int,
            n_pages: int):
    """Grid: (B, n_q_blocks, Np).

    q_ref/o_ref: (Hkv, G, block_q, D) — every query head of one q block,
    flattened in-kernel to rows ``g * block_q + i``; k_ref/v_ref: (page,
    Hkv, D) — one pool page of every KV head, steered by the prefetched
    table; scratch: m/l (Hkv, G * block_q, 1) f32, acc (Hkv, G * block_q, D)
    f32.
    """
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        online_softmax_init(m_scr, l_scr, acc_scr)

    Hkv, G, _, D = q_ref.shape
    q = q_ref[...].astype(F32).reshape(Hkv, G * block_q, D) * scale
    k = k_ref[...].astype(F32)                    # (page, Hkv, D)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=F32)   # (Hkv, G*bq, page)

    rows = s.shape[1]
    q_pos = qoff_ref[b] + qi * block_q + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (1, rows, page), 1), block_q)
    kv_pos = j * page + jax.lax.broadcasted_iota(
        jnp.int32, (1, rows, page), 2)
    mask = (kv_pos <= q_pos) & (kv_pos < len_ref[b])
    s = jnp.where(mask, s, NEG_INF)

    online_softmax_block(s, v_ref[...].astype(F32), m_scr, l_scr, acc_scr,
                         pv_dims=(((2,), (0,)), ((0,), (1,))))

    @pl.when(j == n_pages - 1)
    def _finish():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = o.reshape(Hkv, G, block_q, D).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def paged_flash_attention(q, k_pages, v_pages, block_table, kv_len, q_offset,
                          *, block_q: int = 128,
                          interpret: Optional[bool] = None):
    """q: (B, Hq, Sq, D); k/v_pages: (P, page, Hkv, D);
    block_table: (B, Np) int32 pool page ids in token order (scratch-padded,
    last entry always the scratch page); kv_len: (B,) int32 valid kv tokens
    (the chunk's own KV must already be scattered into its pages);
    q_offset: (B,) int32 absolute position of each row's first query.
    Returns (B, Hq, Sq, D).

    ``kv_len``/``q_offset`` are traced (scalar-prefetched), so chunk starts
    never trigger recompiles; only shapes do. ``Sq`` may be ragged.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, Sq, D = q.shape
    _, page, Hkv, _ = k_pages.shape
    Np = block_table.shape[1]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    Sq_pad = -(-Sq // block_q) * block_q
    if Sq_pad != Sq:            # ragged final q block: pad, slice off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_pad - Sq), (0, 0)))
    n_q = Sq_pad // block_q
    qg = q.reshape(B, Hkv, G, n_q, block_q, D)

    def q_map(b, i, j, table, kl, qo):
        return (b, 0, 0, i, 0, 0)

    def kv_map(b, i, j, table, kl, qo):
        return (table[b, j], 0, 0, 0)

    # one grid step holds every head of a q block and one page of every KV
    # head: the (page, Hkv, D) block equals the pool's own minor dims, as
    # the TPU (8, 128) tiling rule requires, and each page is fetched once
    rows = G * block_q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_q, Np),
        in_specs=[
            pl.BlockSpec((None, Hkv, G, None, block_q, D), q_map),
            pl.BlockSpec((None, page, Hkv, D), kv_map),
            pl.BlockSpec((None, page, Hkv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((None, Hkv, G, None, block_q, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rows, 1), F32),
            pltpu.VMEM((Hkv, rows, 1), F32),
            pltpu.VMEM((Hkv, rows, D), F32),
        ],
    )
    kernel = functools.partial(_kernel, scale=D ** -0.5, page=page,
                               block_q=block_q, n_pages=Np)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(block_table, kv_len, q_offset, qg, k_pages, v_pages)
    out = out.reshape(B, Hq, Sq_pad, D)
    return out[:, :, :Sq] if Sq_pad != Sq else out
