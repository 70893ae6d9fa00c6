"""Dense / MoE decoder-only LM (covers gemma2, internlm2, qwen2.5, llama3.2,
llava backbone, dbrx, granite-moe).

Layer stacks are scanned (``lax.scan``) so HLO size is O(1) in depth — this is
what keeps 512-device dry-run compiles tractable. Alternating local/global
attention (gemma2) is handled by a per-layer ``is_local`` scalar carried as a
scan input; logit softcaps and pre+post sublayer norms are config-driven.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.scan_util import scan as _uscan
from repro.models import layers as L
from repro.models.layers import (ParallelCtx, apply_norm, attention, attn_out,
                                 attn_qkv, constrain, init_attn, init_mlp,
                                 init_moe, init_norm, mha, mlp, moe_ffn,
                                 moe_ffn_ep_local, paged_decode_attention,
                                 paged_prefill_attention)
from repro.obs import scopes

F32 = jnp.float32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """Dense per-layer KV cache: k/v (L, B, Smax, Hkv, Dh)."""
    k: jax.Array
    v: jax.Array

    def tree_flatten(self):
        return (self.k, self.v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def zeros(cls, cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
        shp = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim_)
        return cls(jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))

    @classmethod
    def specs(cls, cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
        shp = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim_)
        sds = jax.ShapeDtypeStruct(shp, dtype)
        return cls(sds, sds)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p = {
        "ln_attn": init_norm(cfg, cfg.d_model, dtype),
        "attn": init_attn(cfg, ks[0], dtype),
        "ln_mlp": init_norm(cfg, cfg.d_model, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(cfg, ks[1], dtype)
    else:
        p["mlp"] = init_mlp(cfg, ks[1], dtype)
    if cfg.post_sublayer_norm:
        p["ln_post_attn"] = init_norm(cfg, cfg.d_model, dtype)
        p["ln_post_mlp"] = init_norm(cfg, cfg.d_model, dtype)
    return p


def init_lm(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict[str, Any]:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: _init_layer(cfg, k, dtype))(layer_keys)
    params = {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model), dtype)
                 * cfg.d_model ** -0.5,
        "layers": stacked,
        "ln_final": init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size), dtype) * cfg.d_model ** -0.5
    return params


def layer_kind_flags(cfg: ModelConfig) -> jax.Array:
    """(L,) float32: 1.0 where the layer uses local (sliding-window) attention."""
    return jnp.array([1.0 if k == "local" else 0.0 for k in cfg.layer_kinds()], F32)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _moe_block(cfg: ModelConfig, lp, h, pctx: Optional[ParallelCtx]):
    if pctx is not None and pctx.ep_axis is not None and pctx.mesh is not None:
        m = cfg.moe
        dp = pctx.dp_spec
        ep, tp = pctx.ep_axis, pctx.tp_axis
        wspec = {"router": P(), "w_gate": P(ep, None, tp), "w_up": P(ep, None, tp),
                 "w_down": P(ep, tp, None)}
        fn = jax.shard_map(
            partial(moe_ffn_ep_local, cfg, ep_axis=ep, tp_axis=tp),
            mesh=pctx.mesh, in_specs=(wspec, P(dp, None, None)),
            out_specs=P(dp, None, None), check_vma=False)
        return fn(lp["moe"], h)
    import os
    token_shard = "moe_replicated" in os.environ.get("REPRO_OPT", "")
    return moe_ffn(cfg, lp["moe"], h, pctx, token_shard=token_shard)


def _attn_block(cfg: ModelConfig, lp, x, q_pos, attend):
    """Attention sublayer under the ``attn`` scope: ln_attn, QKV,
    ``attend(q, k, v) -> (o, kv)`` (the attention with any KV write), the
    output projection and the residual add. Returns (x, kv)."""
    with jax.named_scope(scopes.ATTN):
        h = apply_norm(cfg, lp["ln_attn"], x)
        q, k, v = attn_qkv(cfg, lp["attn"], h, q_pos)
        o, kv = attend(q, k, v)
        o = attn_out(lp["attn"], o)
        if cfg.post_sublayer_norm:
            o = apply_norm(cfg, lp["ln_post_attn"], o)
        return x + o, kv


def _mlp_block(cfg: ModelConfig, lp, x, pctx):
    """MLP (or MoE) sublayer under the ``mlp`` scope: ln_mlp through the
    residual add."""
    with jax.named_scope(scopes.MLP):
        h = apply_norm(cfg, lp["ln_mlp"], x)
        if cfg.family == "moe":
            f = _moe_block(cfg, lp, h, pctx)
        else:
            f = mlp(cfg, lp["mlp"], h, pctx)
        if cfg.post_sublayer_norm:
            f = apply_norm(cfg, lp["ln_post_mlp"], f)
        return x + f


def _layer_full(cfg: ModelConfig, x, lp, is_local, positions, pctx):
    """Full-sequence layer (train / prefill). Returns (x, (k, v))."""
    def attend(q, k, v):
        o = attention(q, k, v, positions, positions, causal=True,
                      window=cfg.sliding_window, is_local=is_local,
                      softcap=cfg.attn_logit_softcap)
        return o, (k, v)

    x, kv = _attn_block(cfg, lp, x, positions, attend)
    x = constrain(x, pctx, pctx.dp_spec if pctx else None, None, None)
    x = _mlp_block(cfg, lp, x, pctx)
    x = constrain(x, pctx, pctx.dp_spec if pctx else None, None, None)
    return x, kv


def _embed(cfg: ModelConfig, params, tokens, embeds=None):
    with jax.named_scope(scopes.EMBED):
        x = params["embed"][tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if embeds is not None:   # modality-stub tokens are prepended
            x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
        return x


def _final_norm(cfg: ModelConfig, params, x):
    with jax.named_scope(scopes.HEAD):
        return apply_norm(cfg, params["ln_final"], x)


def _unembed(cfg: ModelConfig, params, x):
    with jax.named_scope(scopes.HEAD):
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("...d,dv->...v", x, w, preferred_element_type=F32)
        if cfg.final_logit_softcap is not None:
            c = cfg.final_logit_softcap
            logits = c * jnp.tanh(logits / c)
        return logits


# ---------------------------------------------------------------------------
# forward: full sequence (train / prefill)
# ---------------------------------------------------------------------------

# What a recomputed layer keeps for the backward pass: the outputs of its
# products without batch dimensions (the q/k/v/o projections, the MLP's
# gate and up). The attention scores, softmax, norms, RoPE and SiLU are
# recomputed; so are an MoE layer's expert products (an expert batch dim).
REMAT_POLICY = jax.checkpoint_policies.dots_with_no_batch_dims_saveable


def lm_forward(cfg: ModelConfig, params, tokens, *, pctx: Optional[ParallelCtx] = None,
               embeds=None, positions=None, return_cache: bool = False,
               remat: bool = False, return_hidden: bool = False):
    """tokens (B, S) -> logits (B, S_total, V); optionally per-layer KV."""
    x = _embed(cfg, params, tokens, embeds)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, pctx, pctx.dp_spec if pctx else None, None, None)
    kinds = layer_kind_flags(cfg)
    q_pos = positions

    def body(x, scanned):
        lp, is_local = scanned
        return _layer_full(cfg, x, lp, is_local, positions, pctx)

    body_fn = jax.checkpoint(body, policy=REMAT_POLICY) if remat else body
    x, (ks, vs) = _uscan(body_fn, x, (params["layers"], kinds))
    x = _final_norm(cfg, params, x)
    if return_hidden:
        return x
    logits = _unembed(cfg, params, x)
    logits = constrain(logits, pctx, pctx.dp_spec if pctx else None, None,
                       pctx.tp_axis if pctx else None)
    if return_cache:
        return logits, KVCache(ks, vs)
    return logits


def lm_prefill(cfg: ModelConfig, params, tokens, *, pctx=None, embeds=None,
               positions=None):
    logits, cache = lm_forward(cfg, params, tokens, pctx=pctx, embeds=embeds,
                               positions=positions, return_cache=True)
    return logits[:, -1], cache


# ---------------------------------------------------------------------------
# forward: incremental step against a dense KV cache
#   C == 1      -> decode
#   C == chunk  -> chunked prefill (attends to previously cached prefix)
# ---------------------------------------------------------------------------

def lm_step(cfg: ModelConfig, params, cache: KVCache, tokens, positions, *,
            pctx: Optional[ParallelCtx] = None):
    """tokens (B, C) int32; positions (B, C) int32 (cache indices to write).

    Returns (logits (B, C, V), updated cache). Every layer writes its new KV
    at ``positions`` then attends over the full valid prefix (+ sliding
    window on local layers).
    """
    B, C = tokens.shape
    Smax = cache.k.shape[2]
    x = _embed(cfg, params, tokens)                   # (B, C, D)
    x = constrain(x, pctx, _decode_dp(pctx, B), None, None)
    kinds = layer_kind_flags(cfg)
    kv_pos = jnp.broadcast_to(jnp.arange(Smax, dtype=jnp.int32), (B, Smax))
    kv_valid = kv_pos <= jnp.max(positions, axis=1, keepdims=True)
    q_pos = positions
    b_idx = jnp.arange(B)[:, None]

    def body(x, scanned):
        lp, is_local, k_l, v_l = scanned

        def attend(q, k_new, v_new):
            k = k_l.at[b_idx, positions].set(k_new)
            v = v_l.at[b_idx, positions].set(v_new)
            o = attention(q, k, v, q_pos, kv_pos, kv_valid=kv_valid,
                          causal=True, window=cfg.sliding_window,
                          is_local=is_local, softcap=cfg.attn_logit_softcap)
            return o, (k, v)

        x, kv = _attn_block(cfg, lp, x, q_pos, attend)
        return _mlp_block(cfg, lp, x, pctx), kv

    x, (ks, vs) = _uscan(body, x, (params["layers"], kinds, cache.k, cache.v))
    x = _final_norm(cfg, params, x)
    logits = _unembed(cfg, params, x)
    return logits, KVCache(ks, vs)


def lm_decode(cfg: ModelConfig, params, cache: KVCache, tokens, positions, *,
              pctx: Optional[ParallelCtx] = None):
    """tokens (B,), positions (B,) -> (logits (B, V), updated cache)."""
    logits, cache = lm_step(cfg, params, cache, tokens[:, None],
                            positions[:, None], pctx=pctx)
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# paged KV: physical page-pool layout driven by BlockPool block tables.
#
# The cache is a *global* pool of P pages of `page` tokens each, shared by
# every session on the device: a sequence's KV lives wherever its block
# table points, so two sessions prefix-sharing a repository context read the
# SAME physical pages (the live analogue of the kvcache radix accounting).
# Page id P-1 by convention is scratch: padded prefill lanes and idle decode
# lanes park their writes there.
# ---------------------------------------------------------------------------

def supports_paged(cfg: ModelConfig) -> bool:
    """The paged decode path covers plain causal GQA; sliding-window
    alternation and logit softcaps (gemma2) stay on the dense layout."""
    return (cfg.family in ("dense", "moe") and cfg.sliding_window is None
            and cfg.attn_logit_softcap is None)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Paged per-layer KV pool: k/v (L, P, page, Hkv, Dh)."""
    k: jax.Array
    v: jax.Array

    def tree_flatten(self):
        return (self.k, self.v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @classmethod
    def zeros(cls, cfg: ModelConfig, n_pages: int, page: int,
              dtype=jnp.bfloat16):
        shp = (cfg.n_layers, n_pages, page, cfg.n_kv_heads, cfg.head_dim_)
        return cls(jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))


def lm_decode_paged(cfg: ModelConfig, params, cache: PagedKVCache, tokens,
                    positions, block_tables, lengths, write_pages,
                    write_offsets, *, pctx: Optional[ParallelCtx] = None):
    """One decode step against the global page pool.

    tokens/positions: (B,) int32 (absolute positions for RoPE);
    block_tables: (B, max_pages) int32 device page ids in token order;
    lengths: (B,) valid kv tokens AFTER this step's write (pos + 1);
    write_pages/write_offsets: (B,) — the page/slot each lane's new KV
    lands in (idle lanes point at the scratch page). The slot must be the
    table position of sequence index ``lengths - 1`` (the fused kernel's
    write/read contract; idle lanes satisfy it degenerately with length 1
    and an all-scratch table row).
    Returns (logits (B, V), updated cache).
    """
    assert supports_paged(cfg), "paged decode: unsupported attention variant"
    B = tokens.shape[0]
    x = _embed(cfg, params, tokens[:, None])          # (B, 1, D)
    x = constrain(x, pctx, _decode_dp(pctx, B), None, None)
    q_pos = positions[:, None]

    def body(x, scanned):
        lp, k_l, v_l = scanned                        # k/v_l: (P, page, H, D)

        def attend(q, k_new, v_new):
            # KV write fused into the attention dispatch (kernel prologue on
            # the Pallas path; scatter-then-attend on the jnp oracle path —
            # bitwise the old separate-scatter math)
            o, k, v = paged_decode_attention(
                q[:, 0], k_l, v_l, block_tables, lengths,
                k_new=k_new[:, 0], v_new=v_new[:, 0],
                write_pages=write_pages, write_offsets=write_offsets)
            return o[:, None], (k, v)

        x, kv = _attn_block(cfg, lp, x, q_pos, attend)
        return _mlp_block(cfg, lp, x, pctx), kv

    x, (ks, vs) = _uscan(body, x, (params["layers"], cache.k, cache.v))
    x = _final_norm(cfg, params, x)
    logits = _unembed(cfg, params, x[:, 0])
    return logits, PagedKVCache(ks, vs)


def lm_prefill_paged(cfg: ModelConfig, params, cache: PagedKVCache, tokens,
                     positions, table, write_pages, write_offsets, kv_len, *,
                     pctx: Optional[ParallelCtx] = None,
                     return_hidden: bool = False):
    """Chunked prefill of ONE sequence against the page pool, gather-free.

    tokens/positions: (1, C) — absolute positions; padded lanes sit at
    ``Np*page - 1`` (which the table maps to the scratch page). table:
    (Np,) page ids covering the sequence's lease in token order,
    scratch-padded, with the LAST entry always scratch.
    write_pages/write_offsets: (C,) destination of each chunk token's KV
    (padded lanes: the scratch page). kv_len: () int32 — valid kv tokens
    after this chunk (chunk start + real chunk tokens), traced so chunk
    starts never recompile.

    Per layer the chunk's KV is scattered into its leased pages FIRST, then
    ``paged_prefill_attention`` reads every page **in place** via the
    scalar-prefetched table — the read side never materializes a dense
    ``pages[table]`` view (the O(context)-bytes-per-chunk copy the legacy
    ``lm_prefill_paged_gather`` pays). Exact semantics: queries at absolute
    positions, causal over the previously cached (possibly *shared*) prefix
    + the chunk itself, stale/scratch slots masked by ``kv_len``.
    Returns (logits (1, C, V), cache), or with ``return_hidden`` the final
    normed hidden states (1, C, D) in place of the logits — callers that
    need one position's logits unembed just that row.
    """
    assert supports_paged(cfg), "paged prefill: unsupported attention variant"
    x = _embed(cfg, params, tokens)                   # (1, C, D)
    x = constrain(x, pctx, _decode_dp(pctx, 1), None, None)
    q_pos = positions
    q_offset = positions[:, 0]                        # (1,) chunk start
    kv_len_b = jnp.reshape(kv_len, (1,)).astype(jnp.int32)
    table_b = table[None]                             # (1, Np)

    def body(x, scanned):
        lp, k_l, v_l = scanned                        # k/v_l: (P, page, H, D)

        def attend(q, k_new, v_new):
            k = k_l.at[write_pages, write_offsets].set(k_new[0])
            v = v_l.at[write_pages, write_offsets].set(v_new[0])
            o = paged_prefill_attention(q, k, v, table_b, kv_len_b, q_offset)
            return o, (k, v)

        x, kv = _attn_block(cfg, lp, x, q_pos, attend)
        return _mlp_block(cfg, lp, x, pctx), kv

    x, (ks, vs) = _uscan(body, x, (params["layers"], cache.k, cache.v))
    x = _final_norm(cfg, params, x)
    if return_hidden:
        return x, PagedKVCache(ks, vs)
    return _unembed(cfg, params, x), PagedKVCache(ks, vs)


def prefill_next_token(cfg: ModelConfig, params, hidden, last_idx):
    """Greedy next token after a prefill chunk: unembeds only row
    ``last_idx`` of ``hidden`` (1, C, D) — the (C, V) logits of the whole
    chunk would be the step's largest temporary."""
    logits = _unembed(cfg, params, hidden[0, last_idx])
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def lm_mixed_paged(cfg: ModelConfig, params, cache: PagedKVCache,
                   p_tokens, p_positions, p_tables, p_write_pages,
                   p_write_offsets, p_kv_lens, p_last_idx,
                   d_tokens, d_positions, d_tables, d_lengths,
                   d_write_pages, d_write_offsets, *,
                   pctx: Optional[ParallelCtx] = None):
    """ONE mixed continuous-batching iteration against the page pool:
    ``P`` chunked-prefill packs (scanned, cache as carry) followed by ``B``
    one-token decode lanes, fused into a single traced computation so the
    engine's mixed tick costs one dispatch.

    Prefill pack arrays carry a leading ``P`` axis over the per-pack
    ``lm_prefill_paged`` arguments: p_tokens (P, 1, C), p_positions
    (P, 1, C), p_tables (P, Np), p_write_pages/p_write_offsets (P, C),
    p_kv_lens (P,), p_last_idx (P,) — the index of each pack's last real
    chunk token, whose greedy argmax seeds the session's decoding. Decode
    arrays are ``lm_decode_paged``'s, batch-first: d_tokens/d_positions/
    d_lengths/d_write_pages/d_write_offsets (B,), d_tables (B, max_pages).
    ``P == 0`` / ``B == 0`` skip their stage *statically* (shape-driven:
    a different bucket recompiles, which the power-of-two bucketing
    bounds).

    Ordering within the fused iteration is safe by the pool's write-
    exclusivity: a pack scatters KV only into its session's exclusively
    owned pages (freshly leased or CoW'd), so prefill writes can never
    alias a decode lane's readable prefix — the scan-then-decode order is
    an implementation choice, not a correctness requirement.

    Returns (p_next (P,) int32, d_next (B,) int32, cache).
    """
    assert supports_paged(cfg), "mixed paged: unsupported attention variant"
    P = p_tokens.shape[0]
    B = d_tokens.shape[0]

    def pack_body(carry, inp):
        toks, pos, table, wpid, woff, kv_len, last = inp
        hidden, carry = lm_prefill_paged(cfg, params, carry, toks, pos,
                                         table, wpid, woff, kv_len,
                                         pctx=pctx, return_hidden=True)
        return carry, prefill_next_token(cfg, params, hidden, last)

    if P > 0:
        with jax.named_scope(scopes.PREFILL):
            cache, p_next = lax.scan(
                pack_body, cache,
                (p_tokens, p_positions, p_tables, p_write_pages,
                 p_write_offsets, p_kv_lens, p_last_idx))
    else:
        p_next = jnp.zeros((0,), jnp.int32)
    if B > 0:
        with jax.named_scope(scopes.DECODE):
            logits, cache = lm_decode_paged(cfg, params, cache, d_tokens,
                                            d_positions, d_tables, d_lengths,
                                            d_write_pages, d_write_offsets,
                                            pctx=pctx)
            d_next = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        d_next = jnp.zeros((0,), jnp.int32)
    return p_next, d_next, cache


def lm_prefill_paged_gather(cfg: ModelConfig, params, cache: PagedKVCache,
                            tokens, positions, table, write_pages,
                            write_offsets, *,
                            pctx: Optional[ParallelCtx] = None):
    """Legacy chunked prefill: gather the lease into a dense view, run the
    dense ``lm_step`` on it, scatter the chunk's KV back.

    Exact but O(context) HBM bytes per chunk (gather read + dense-copy
    write + kernel read). Kept as the bit-exactness baseline for
    ``lm_prefill_paged`` (tests) and the accounting baseline for the
    ``prefill_hbm_bytes_per_chunk`` bench figure. Same argument layout as
    ``lm_prefill_paged`` minus ``kv_len``.
    """
    assert supports_paged(cfg), "paged prefill: unsupported attention variant"
    page = cache.page_size
    C = tokens.shape[1]
    ks = cache.k[:, table]                            # (L, Np, page, H, D)
    vs = cache.v[:, table]
    L_, Np = ks.shape[0], ks.shape[1]
    dense = KVCache(ks.reshape(L_, 1, Np * page, *ks.shape[3:]),
                    vs.reshape(L_, 1, Np * page, *vs.shape[3:]))
    logits, sub = lm_step(cfg, params, dense, tokens, positions, pctx=pctx)
    start = positions[0, 0]
    k_chunk = jax.lax.dynamic_slice_in_dim(sub.k, start, C, axis=2)[:, 0]
    v_chunk = jax.lax.dynamic_slice_in_dim(sub.v, start, C, axis=2)[:, 0]
    k = cache.k.at[:, write_pages, write_offsets].set(k_chunk)
    v = cache.v.at[:, write_pages, write_offsets].set(v_chunk)
    return logits, PagedKVCache(k, v)


# ---------------------------------------------------------------------------
# windowed decode (perf iteration, EXPERIMENTS.md §Perf): local (sliding-
# window) layers keep a ring buffer of `window` KV slots instead of the full
# sequence — for gemma2-style local/global alternation this halves the KV
# footprint and HBM traffic of long-context decode exactly.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WindowedKVCache:
    """k/v_loc: (Lp, B, W, Hkv, Dh) ring buffers (local layers);
    k/v_glob: (Lp, B, Smax, Hkv, Dh). Pattern period must be 2
    ('local','global')."""
    k_loc: jax.Array
    v_loc: jax.Array
    k_glob: jax.Array
    v_glob: jax.Array

    def tree_flatten(self):
        return (self.k_loc, self.v_loc, self.k_glob, self.v_glob), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def specs(cls, cfg: ModelConfig, batch: int, max_len: int,
              dtype=jnp.bfloat16):
        assert cfg.layer_pattern == ("local", "global")
        Lp = cfg.n_layers // 2
        loc = jax.ShapeDtypeStruct(
            (Lp, batch, cfg.sliding_window, cfg.n_kv_heads, cfg.head_dim_),
            dtype)
        glob = jax.ShapeDtypeStruct(
            (Lp, batch, max_len, cfg.n_kv_heads, cfg.head_dim_), dtype)
        return cls(loc, loc, glob, glob)


def lm_decode_windowed(cfg: ModelConfig, params, cache: WindowedKVCache,
                       tokens, positions, *,
                       pctx: Optional[ParallelCtx] = None):
    """Single-token decode with ring-buffered local layers. Exact semantics:
    slot i of the ring holds the most recent position p <= pos with
    p ≡ i (mod W), which is precisely the sliding-window attention set."""
    assert cfg.layer_pattern == ("local", "global")
    B = tokens.shape[0]
    W = cfg.sliding_window
    Smax = cache.k_glob.shape[2]
    x = _embed(cfg, params, tokens[:, None])
    b_idx = jnp.arange(B)
    q_pos = positions[:, None]
    # ring-buffer positions per slot
    slot = jnp.arange(W, dtype=jnp.int32)
    ring_pos = positions[:, None] - ((positions[:, None] - slot[None]) % W)
    ring_valid = ring_pos >= 0
    kv_pos_g = jnp.broadcast_to(jnp.arange(Smax, dtype=jnp.int32), (B, Smax))
    kv_valid_g = kv_pos_g <= positions[:, None]
    pair_params = jax.tree.map(
        lambda a: a.reshape((cfg.n_layers // 2, 2) + a.shape[1:]),
        params["layers"])

    def sublayer(x, lp, k_l, v_l, kv_pos, kv_valid, write_pos):
        def attend(q, k_new, v_new):
            k = k_l.at[b_idx, write_pos].set(k_new[:, 0])
            v = v_l.at[b_idx, write_pos].set(v_new[:, 0])
            o = attention(q, k, v, q_pos, kv_pos, kv_valid=kv_valid,
                          causal=True, softcap=cfg.attn_logit_softcap)
            return o, (k, v)

        x, (k_l, v_l) = _attn_block(cfg, lp, x, q_pos, attend)
        return _mlp_block(cfg, lp, x, pctx), k_l, v_l

    def body(x, scanned):
        lp_pair, kl, vl, kg, vg = scanned
        lp0 = jax.tree.map(lambda a: a[0], lp_pair)
        lp1 = jax.tree.map(lambda a: a[1], lp_pair)
        x, kl, vl = sublayer(x, lp0, kl, vl, ring_pos, ring_valid,
                             positions % W)
        x, kg, vg = sublayer(x, lp1, kg, vg, kv_pos_g, kv_valid_g, positions)
        return x, (kl, vl, kg, vg)

    x, (kl, vl, kg, vg) = _uscan(
        body, x, (pair_params, cache.k_loc, cache.v_loc,
                  cache.k_glob, cache.v_glob))
    x = _final_norm(cfg, params, x)
    logits = _unembed(cfg, params, x[:, 0])
    return logits, WindowedKVCache(kl, vl, kg, vg)


def _decode_dp(pctx: Optional[ParallelCtx], batch: int):
    if pctx is None:
        return None
    return pctx.dp_spec
