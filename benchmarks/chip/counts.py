"""Operations and bytes that the dispatched work needs, from what each tick
dispatched (real packs, lanes and context lengths), never from the padded
buckets: the same work counts the same whatever implements it.

``c`` is a configuration (``configs/<name>.json``); the weights a token
multiplies in a layer come from its architecture module
(``layer_matmul_params``). Attention counts two multiply-adds per (query
head, key) pair and head dim: Q.K and P.V. Bytes are the least the kernel
must move: the keys and values it reads once, its queries in and outputs
out, and the new keys and values it writes, in bf16 (2 bytes).
"""
from __future__ import annotations

from typing import Iterable, Tuple

import model as bench_model

BYTES = 2


def _dims(c: dict):
    return (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["num_hidden_layers"])


def decode_attention(c: dict, kv_lens: Iterable[int]) -> Tuple[float, float]:
    """``paged_attention_fused`` over all layers: one query token per lane
    attending to ``kv_len`` keys (its own new key included, which the
    kernel writes). Returns (flops, bytes)."""
    H, Hkv, Dh, L = _dims(c)
    flops = bytes_ = 0.0
    for n in kv_lens:
        flops += 4.0 * H * Dh * n
        bytes_ += BYTES * (2 * Hkv * Dh * (n - 1)      # keys, values read
                           + 2 * H * Dh                # query in, output out
                           + 2 * Hkv * Dh)             # new key, value
    return L * flops, L * bytes_


def prefill_attention(c: dict, packs: Iterable[Tuple[int, int]]
                      ) -> Tuple[float, float]:
    """``paged_flash_attention`` over all layers: ``chunk`` queries at
    positions ``start .. start+chunk-1``, each attending causally to every
    key up to its own. Returns (flops, bytes)."""
    H, Hkv, Dh, L = _dims(c)
    flops = bytes_ = 0.0
    for start, chunk in packs:
        pairs = chunk * start + chunk * (chunk + 1) / 2
        flops += 4.0 * H * Dh * pairs
        bytes_ += BYTES * (2 * Hkv * Dh * (start + chunk)
                           + 2 * H * Dh * chunk)
    return L * flops, L * bytes_


def step_flops(c: dict, packs, kv_lens) -> float:
    """Model operations one fused step needs: every token through every
    layer's matrix products and attention, and the unembedding of the rows
    whose next token is chosen (a pack's last token, every decode lane)."""
    packs, kv_lens = list(packs), list(kv_lens)
    L = c["num_hidden_layers"]
    tokens = sum(ch for _, ch in packs) + len(kv_lens)
    dense = 2.0 * L * bench_model.arch(c).layer_matmul_params(c) * tokens
    unembed = 2.0 * c["hidden_size"] * c["vocab_size"] * (len(packs)
                                                          + len(kv_lens))
    return (dense + unembed + prefill_attention(c, packs)[0]
            + decode_attention(c, kv_lens)[0])


def train_step_flops(c: dict, batch: int, seq_len: int) -> float:
    """Model operations one train step needs: forward and backward (three
    times the forward) through every layer's matrix products, the
    unembedding and causal attention, for ``batch`` rows of ``seq_len``
    tokens. Recomputation in the backward pass is not counted."""
    H, _Hkv, Dh, L = _dims(c)
    tokens = batch * seq_len
    dense = 2.0 * (L * bench_model.arch(c).layer_matmul_params(c)
                   + c["hidden_size"] * c["vocab_size"]) * tokens
    pairs = batch * seq_len * (seq_len + 1) / 2
    return 3.0 * (dense + 4.0 * H * Dh * pairs * L)
