"""Distributed step builders for the multi-pod dry-run and the launchers.

For every (architecture x shape) cell this module produces:
  * ``input_specs(cfg, shape, mesh)`` — sharded ShapeDtypeStruct stand-ins
    for every input (weak-type-correct, no device allocation), and
  * the step function to ``jax.jit(...).lower(**specs).compile()``:
      - train_4k      -> train_step(params, opt_state, batch)
      - prefill_32k   -> prefill_step(params, batch)
      - decode_32k /
        long_500k     -> serve_step(params, cache, tokens, positions)

Sharding: params via ``sharding.param_specs`` (TP/EP), batch over DP axes,
KV caches over (batch | sequence for B=1 long-context) + head/dim TP.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.shapes import ShapeSpec
from repro.distributed import sharding as sh
from repro.models import model_zoo
from repro.models.config import ModelConfig
from repro.obs import scopes
from repro.train.optimizer import OptConfig, OptState, adamw_update

F32 = jnp.float32
BF16 = jnp.bfloat16


def _sds(shape, dtype, mesh, spec: P):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _param_sds(cfg: ModelConfig, mesh, dtype=BF16):
    params = jax.eval_shape(
        lambda k: model_zoo.init(cfg, k, dtype), jax.random.PRNGKey(0))
    specs = sh.param_specs(cfg, mesh, params)
    return jax.tree.map(
        lambda s, p: _sds(s.shape, s.dtype, mesh, p), params, specs)


def _opt_sds(param_sds, cfg=None, mesh=None):
    def moment(s):
        sharding = s.sharding
        if mesh is not None:
            spec = sh.opt_moment_spec(sharding.spec, s.shape, mesh)
            sharding = NamedSharding(mesh, spec)
        return jax.ShapeDtypeStruct(s.shape, F32, sharding=sharding)

    mu = jax.tree.map(moment, param_sds)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return OptState(step, mu, jax.tree.map(lambda s: s, mu))


def _batch_sds(cfg: ModelConfig, spec: ShapeSpec, mesh,
               with_targets: bool) -> Dict[str, Any]:
    dp = P(tuple(a for a in mesh.axis_names if a in ("pod", "data")))
    B, S = spec.global_batch, spec.seq_len
    bsp = P(dp[0] if dp else None, None)
    batch: Dict[str, Any] = {}
    if cfg.family == "whisper":
        enc_len = S // 4                      # conv-stub downsampling
        dec_len = min(cfg.max_target_len, S)
        batch["frames"] = _sds((B, enc_len, cfg.d_model), BF16,
                               mesh, P(bsp[0], None, None))
        batch["tokens"] = _sds((B, dec_len), jnp.int32, mesh, bsp)
        if with_targets:
            batch["targets"] = _sds((B, dec_len), jnp.int32, mesh, bsp)
        return batch
    n_text = S
    if cfg.frontend == "image_patches":
        n_text = S - cfg.n_frontend_tokens
        batch["embeds"] = _sds((B, cfg.n_frontend_tokens, cfg.d_model), BF16,
                               mesh, P(bsp[0], None, None))
    batch["tokens"] = _sds((B, n_text), jnp.int32, mesh, bsp)
    if with_targets:
        batch["targets"] = _sds((B, S), jnp.int32, mesh, bsp)
    return batch


def _cache_sds(cfg: ModelConfig, spec: ShapeSpec, mesh):
    B, S = spec.global_batch, spec.seq_len
    seq_shard = B < sh._dp_size(mesh)
    enc_len = S // 4 if cfg.family == "whisper" else 0
    max_len = min(cfg.max_target_len, S) if cfg.family == "whisper" else S
    shapes = model_zoo.cache_specs(cfg, B, max_len, BF16, enc_len=enc_len)
    specs = sh.cache_specs(cfg, mesh, B, seq_shard=seq_shard)
    return jax.tree.map(lambda s, p: _sds(s.shape, s.dtype, mesh, p),
                        shapes, specs)


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def cross_entropy(logits, targets):
    """Vocab-sharding-friendly CE: the target logit is extracted with an
    iota-compare masked reduce (elementwise on the sharded vocab dim + psum)
    instead of take_along_axis, which GSPMD would all-gather."""
    with jax.named_scope(scopes.LOSS):
        lf = logits.astype(F32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
        tgt = jnp.sum(jnp.where(vocab_iota == targets[..., None], lf, 0.0),
                      axis=-1)
        return jnp.mean(lse - tgt)


def chunked_cross_entropy(cfg: ModelConfig, params, hidden, targets,
                          chunk: int = 512):
    """Beyond-paper memory optimization (§Perf iteration 1): compute the CE
    loss by scanning sequence chunks of the final hidden states through the
    unembedding, so the (B, S, V) f32 logits tensor — the single largest
    temp of every train cell — never materializes. Exact same math."""
    from jax import lax as _lax
    from repro.models.transformer import _unembed
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    if S % chunk != 0:
        return cross_entropy(_unembed(cfg, params, hidden), targets)
    nb = S // chunk
    hs = jnp.moveaxis(hidden.reshape(B, nb, chunk, D), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, nb, chunk), 1, 0)

    def body(acc, xs):
        h, t = xs
        lg = _unembed(cfg, params, h)
        with jax.named_scope(scopes.LOSS):
            lg = lg.astype(F32)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
            tgt = jnp.sum(jnp.where(iota == t[..., None], lg, 0.0), axis=-1)
            return acc + jnp.sum(lse - tgt), None

    total, _ = _lax.scan(body, jnp.zeros((), F32), (hs, ts))
    return total / (B * S)


def build_train_step(cfg: ModelConfig, mesh, *, opt: Optional[OptConfig] = None,
                     remat: bool = True, chunked_ce: bool = False):
    opt = opt or OptConfig()
    pctx = sh.make_pctx(cfg, mesh)

    def loss_fn(params, batch):
        if chunked_ce and cfg.family in ("dense", "moe"):
            from repro.models.transformer import lm_forward
            hidden = lm_forward(cfg, params, batch["tokens"], pctx=pctx,
                                embeds=batch.get("embeds"), remat=remat,
                                return_hidden=True)
            tgt = batch["targets"]
            if hidden.shape[1] != tgt.shape[1]:
                tgt = jnp.pad(tgt, ((0, 0), (hidden.shape[1] - tgt.shape[1], 0)))
            return chunked_cross_entropy(cfg, params, hidden, tgt)
        logits = model_zoo.forward(cfg, params, batch, pctx=pctx, remat=remat)
        tgt = batch["targets"]
        if logits.shape[1] != tgt.shape[1]:      # VLM: frontend tokens prepended
            tgt = jnp.pad(tgt, ((0, 0), (logits.shape[1] - tgt.shape[1], 0)))
        return cross_entropy(logits, tgt)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state, metrics = adamw_update(opt, params, grads, opt_state)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def build_prefill_step(cfg: ModelConfig, mesh):
    pctx = sh.make_pctx(cfg, mesh)

    def prefill_step(params, batch):
        last_logits, cache = model_zoo.prefill(cfg, params, batch, pctx=pctx)
        return jnp.argmax(last_logits, axis=-1).astype(jnp.int32), cache

    return prefill_step


def build_decode_step(cfg: ModelConfig, mesh, *, windowed: bool = False):
    pctx = sh.make_pctx(cfg, mesh)
    if windowed:
        from repro.models.transformer import lm_decode_windowed

        def serve_step_w(params, cache, tokens, positions):
            logits, cache = lm_decode_windowed(cfg, params, cache, tokens,
                                               positions, pctx=pctx)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        return serve_step_w

    def serve_step(params, cache, tokens, positions):
        logits, cache = model_zoo.decode(cfg, params, cache, tokens, positions,
                                         pctx=pctx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return serve_step


# ---------------------------------------------------------------------------
# cell assembly: (step_fn, example_args_as_SDS, donate)
# ---------------------------------------------------------------------------

def perf_opts() -> set:
    """Beyond-paper perf-iteration toggles (see EXPERIMENTS.md §Perf):
    REPRO_OPT=chunked_ce,moe_replicated,windowed_kv (comma-separated)."""
    return set(filter(None, os.environ.get("REPRO_OPT", "").split(",")))


def build_cell(cfg: ModelConfig, spec: ShapeSpec, mesh,
               ) -> Tuple[Any, Tuple, Dict[str, int]]:
    """Returns (step_fn, sds_args, jit_kwargs) for one dry-run cell."""
    opts = perf_opts()
    if spec.kind == "train":
        params = _param_sds(cfg, mesh)
        opt_state = _opt_sds(params, cfg, mesh)
        batch = _batch_sds(cfg, spec, mesh, with_targets=True)
        fn = build_train_step(cfg, mesh, chunked_ce="chunked_ce" in opts)
        return fn, (params, opt_state, batch), dict(donate_argnums=(0, 1))
    if spec.kind == "prefill":
        params = _param_sds(cfg, mesh)
        batch = _batch_sds(cfg, spec, mesh, with_targets=False)
        fn = build_prefill_step(cfg, mesh)
        return fn, (params, batch), {}
    if spec.kind == "decode":
        params = _param_sds(cfg, mesh)
        windowed = ("windowed_kv" in opts
                    and cfg.layer_pattern == ("local", "global")
                    and cfg.family == "dense")
        if windowed:
            cache = _windowed_cache_sds(cfg, spec, mesh)
        else:
            cache = _cache_sds(cfg, spec, mesh)
        dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        B = spec.global_batch
        b_ax = dp if B % sh._dp_size(mesh) == 0 and B >= sh._dp_size(mesh) else None
        tokens = _sds((B,), jnp.int32, mesh, P(b_ax))
        positions = _sds((B,), jnp.int32, mesh, P(b_ax))
        fn = build_decode_step(cfg, mesh, windowed=windowed)
        return fn, (params, cache, tokens, positions), dict(donate_argnums=(1,))
    raise ValueError(spec.kind)


def _windowed_cache_sds(cfg: ModelConfig, spec: ShapeSpec, mesh):
    from repro.models.transformer import WindowedKVCache
    B, S = spec.global_batch, spec.seq_len
    shapes = WindowedKVCache.specs(cfg, B, S, BF16)
    h_ax, d_ax = sh.kv_head_axis(cfg, mesh)
    seq_shard = B < sh._dp_size(mesh)
    b_ax: Any = tuple(a for a in mesh.axis_names if a in ("pod", "data")) \
        if not seq_shard else None
    loc = P(None, b_ax, None, h_ax, d_ax)              # ring stays unsharded in W
    glob = P(None, b_ax, "data" if seq_shard else None, h_ax, d_ax)
    specs = WindowedKVCache(loc, loc, glob, glob)
    return jax.tree.map(lambda s, p: _sds(s.shape, s.dtype, mesh, p),
                        shapes, specs)
