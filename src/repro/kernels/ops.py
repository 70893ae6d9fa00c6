"""Public kernel entry points with platform dispatch.

On TPU the Pallas kernels run compiled (each kernel's ``interpret=None``
default resolves to compiled there); everywhere else the jnp oracle runs
unless ``use_kernel=True`` asks for the kernel in interpret mode. Model code
calls these wrappers, never ``pl.pallas_call`` directly.

    attention(...)         prefill/train attention (flash kernel | oracle)
    prefill_attention(...) gather-free paged prefill (paged flash | oracle)
    decode_attention(...)  paged decode attention (paged kernel | oracle),
                           optionally with the KV write fused in
    wkv(...)               RWKV6 recurrence        (wkv6 kernel | oracle)
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import (paged_attention as _paged,
                                           paged_attention_fused as
                                           _paged_fused)
from repro.kernels.paged_flash_attention import (paged_flash_attention as
                                                 _paged_flash)
from repro.kernels.wkv6 import wkv6 as _wkv6


def _use_kernels(override: Optional[bool]) -> bool:
    if override is not None:
        return override
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, q_offset: int = 0,
              use_kernel: Optional[bool] = None):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D)."""
    if _use_kernels(use_kernel):
        return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                      q_offset=q_offset)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    softcap=softcap, q_offset=q_offset)


def prefill_attention(q, k_pages, v_pages, block_table, kv_len, q_offset, *,
                      use_kernel: Optional[bool] = None, block_q: int = 128):
    """Gather-free chunked-prefill attention over paged KV.

    q: (B, Hq, Sq, D); pages: (P, page, Hkv, D); block_table: (B, Np);
    kv_len/q_offset: (B,) int32. The chunk's own KV must already be
    scattered into its pages. On the kernel path pages are read in place
    (block-table steered DMA); the oracle gathers — it is the ground truth
    and the CPU default, not the hot path.
    """
    if _use_kernels(use_kernel):
        return _paged_flash(q, k_pages, v_pages, block_table, kv_len,
                            q_offset, block_q=block_q)
    return _ref.paged_flash_attention_ref(q, k_pages, v_pages, block_table,
                                          kv_len, q_offset)


def decode_attention(q, k_pages, v_pages, block_table, lengths, *,
                     k_new=None, v_new=None, write_pages=None,
                     write_offsets=None, use_kernel: Optional[bool] = None):
    """q: (B, Hq, D); pages: (P, page, Hkv, D); table: (B, max_pages).

    With ``k_new/v_new/write_pages/write_offsets`` the decode-side KV write
    is fused: the new token's KV (``(B, Hkv, D)``, slot contract: position
    ``lengths - 1``) lands in the pool inside the call and the result is
    ``(o, k_pages, v_pages)``. Without them: plain read-only attention,
    returns ``o``.
    """
    fused = k_new is not None
    if _use_kernels(use_kernel):
        if fused:
            return _paged_fused(q, k_pages, v_pages, block_table, lengths,
                                k_new, v_new, write_pages, write_offsets)
        return _paged(q, k_pages, v_pages, block_table, lengths)
    if fused:
        k_pages = k_pages.at[write_pages, write_offsets].set(k_new)
        v_pages = v_pages.at[write_pages, write_offsets].set(v_new)
        o = _ref.paged_attention_ref(q, k_pages, v_pages, block_table,
                                     lengths)
        return o, k_pages, v_pages
    return _ref.paged_attention_ref(q, k_pages, v_pages, block_table, lengths)


def wkv(r, k, v, w, u, state, *, chunk: int = 32,
        use_kernel: Optional[bool] = None):
    if _use_kernels(use_kernel):
        return _wkv6(r, k, v, w, u, state, chunk=chunk)
    return _ref.wkv6_ref(r, k, v, w, u, state)
