"""What the train step's recomputed layers keep for the backward pass.

``lm_forward`` wraps each layer in ``jax.checkpoint`` under ``remat``, with
``transformer.REMAT_POLICY``: the outputs of the products without batch
dimensions (the q/k/v/o projections, the MLP's gate and up) are kept, the
rest is recomputed. These tests check what the policy saves against those
widths, the loss and gradients against ``nothing_saveable`` and against no
recompute, and, in the compiled tiny step, which products are recomputed.
"""
import contextlib
import dataclasses
import io
import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from repro.distributed import sharding as sh
from repro.distributed.steps import build_train_step, cross_entropy
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig, MoEConfig
from repro.obs import scopes
from repro.train.optimizer import init_opt

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))
from scopes import op_names_from_hlo  # noqa: E402

DENSE = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                    vocab_size=512, qkv_bias=True, tie_embeddings=True,
                    act="silu", gated_mlp=True)
MOE = dataclasses.replace(DENSE, name="tiny-moe", family="moe",
                          moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32))
PLAIN_MLP = dataclasses.replace(DENSE, name="tiny-plain", gated_mlp=False,
                                act="gelu")
CFGS = {"dense": DENSE, "moe": MOE, "plain_mlp": PLAIN_MLP}
NOTHING = jax.checkpoint_policies.nothing_saveable
RESIDUAL = re.compile(r"^(\w+)\[([\d,]*)\] output of scan")
DOT = re.compile(r"^\s*(?:ROOT )?%(\S+) = \S+ dot\(")


@pytest.fixture
def set_policy(monkeypatch):
    """Recompute the layers under ``policy`` in place of the program's."""
    def set_(policy):
        monkeypatch.setattr(T, "REMAT_POLICY", policy)
    return set_


# --- what the policy saves --------------------------------------------------

def _product_bytes(cfg, tokens):
    """Float32 outputs of the products the backward pass reads, over the
    layer stack: q, k, v and o, and the MLP's gate and up (the down
    product's output feeds only the residual add). An MoE layer keeps its
    router's logits; its expert products carry an expert batch dim."""
    width = cfg.q_dim + 2 * cfg.kv_dim + cfg.d_model
    if cfg.family == "moe":
        width += cfg.moe.num_experts
    else:
        width += (2 if cfg.gated_mlp else 1) * cfg.d_ff
    return tokens * cfg.n_layers * width * 4


def _scan_residual_bytes(cfg, dtype):
    """Bytes of the residuals the layer scan saves for the backward pass."""
    mesh = make_test_mesh(1)
    pctx = sh.make_pctx(cfg, mesh)
    params = T.init_lm(cfg, jax.random.PRNGKey(0), dtype)
    tokens = jnp.zeros((2, 32), jnp.int32)

    def loss(p):
        return T.lm_forward(cfg, p, tokens, pctx=pctx,
                            remat=True).astype(jnp.float32).sum()

    out = io.StringIO()
    with mesh, contextlib.redirect_stdout(out):
        print_saved_residuals(loss, params)
    total = 0
    for line in out.getvalue().splitlines():
        m = RESIDUAL.match(line)
        if m:
            dt, dims = m.groups()
            dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dt]
            total += math.prod(map(int, dims.split(","))) * jnp.dtype(dt).itemsize
    return total


@pytest.mark.parametrize("name", sorted(CFGS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_policy_saves_the_products_outputs(name, dtype, set_policy):
    """Beyond what ``nothing_saveable`` saves, the program's policy saves
    exactly the float32 outputs of the products without batch dims."""
    cfg = CFGS[name]
    kept = _scan_residual_bytes(cfg, dtype)
    set_policy(NOTHING)
    nothing = _scan_residual_bytes(cfg, dtype)
    assert kept - nothing == _product_bytes(cfg, 2 * 32)


# --- the same loss and gradients under each policy --------------------------

def _loss_and_grads(cfg, remat):
    mesh = make_test_mesh(1)
    pctx = sh.make_pctx(cfg, mesh)
    params = T.init_lm(cfg, jax.random.PRNGKey(0), jnp.float32)
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (2, 32), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.fold_in(key, 1), (2, 32), 0,
                                 cfg.vocab_size)

    def loss(p):
        return cross_entropy(T.lm_forward(cfg, p, tokens, pctx=pctx,
                                          remat=remat), targets)

    with mesh:
        return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("name", sorted(CFGS))
def test_parity_across_policies(name, set_policy):
    """The loss and every gradient leaf with the products kept equal those
    with nothing kept, bit for bit, and those without recompute to 1e-6 of
    the leaf's largest magnitude (the order of float32 sums differs there)."""
    cfg = CFGS[name]
    keep = _loss_and_grads(cfg, True)
    plain = _loss_and_grads(cfg, False)
    set_policy(NOTHING)
    nothing = _loss_and_grads(cfg, True)
    np.testing.assert_array_equal(keep[0], nothing[0])
    np.testing.assert_allclose(keep[0], plain[0], rtol=1e-6)
    for a, b, c in zip(*map(jax.tree.leaves, (keep[1], nothing[1], plain[1]))):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, c, rtol=1e-6,
                                   atol=1e-6 * float(jnp.max(jnp.abs(c))))


# --- which products the compiled step recomputes ----------------------------

def _rematted_dots(cfg):
    """(op_name, has batch dimensions) of each dot of the compiled tiny
    train step that runs in a recomputed layer."""
    mesh = make_test_mesh(1)
    params = T.init_lm(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "targets": jnp.zeros((2, 32), jnp.int32)}
    with mesh:
        hlo = jax.jit(build_train_step(cfg, mesh, remat=True)).lower(
            params, init_opt(params), batch).compile().as_text()
    names = op_names_from_hlo(hlo)
    out = []
    for line in hlo.splitlines():
        m = DOT.match(line)
        if m and "rematted_computation" in names.get(m.group(1), ""):
            out.append((names[m.group(1)], "lhs_batch_dims=" in line))
    return out


@pytest.mark.parametrize("name", ["dense", "plain_mlp"])
def test_kept_products_are_not_recomputed(name, set_policy):
    """Under the program's policy the recomputed layers run only the
    attention scores' batched products: no projection and nothing of the
    MLP. Under ``nothing_saveable`` the same reading finds both."""
    cfg = CFGS[name]
    kept = _rematted_dots(cfg)
    assert kept and all(batched for _, batched in kept)
    assert not any(f"/{scopes.MLP}/" in on for on, _ in kept)
    set_policy(NOTHING)
    unbatched = [on for on, batched in _rematted_dots(cfg) if not batched]
    assert any(f"/{scopes.ATTN}/" in on for on in unbatched)
    assert any(f"/{scopes.MLP}/" in on for on in unbatched)
