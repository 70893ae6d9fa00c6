"""The device scopes (``repro.obs.scopes``) in compiled steps.

Compiles the train step (``build_train_step``, layers recomputed) and the
served fused step (``lm_mixed_paged``, both stages) for a tiny dense model
and reads each instruction's ``op_name`` in the optimized HLO: every matrix
product and kernel call, and every fusion holding one, names a layer; the
train step's backward names attn, mlp and head inside ``transpose(``, and
AdamW runs once, outside it.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.distributed.steps import build_train_step
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs import scopes
from repro.train.optimizer import init_opt

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=512, qkv_bias=True, tie_embeddings=True,
                  act="silu", gated_mlp=True)
LAYER = re.compile(r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])"
                   % "|".join(scopes.LAYERS))
COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = \S+ ([a-z][\w-]*)\(")
CALLS = re.compile(r"calls=%([\w.-]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')
PRODUCTS = ("dot", "convolution", "custom-call")


def instructions(hlo: str):
    """(computation, name, opcode, op_name, called computation) of each
    instruction of an HLO module's text."""
    out, comp = [], None
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if m and comp is not None:
            on, calls = OP_NAME.search(line), CALLS.search(line)
            out.append((comp, m.group(1), m.group(2),
                        on.group(1) if on else "",
                        calls.group(1) if calls else None))
    return out


def products_and_their_fusions(ins):
    """The matrix products and kernel calls, and the fusions holding one."""
    holders = {c for c, _, op, _, _ in ins if op in PRODUCTS}
    return [i for i in ins if i[2] in PRODUCTS
            or (i[2] == "fusion" and i[4] in holders)]


@pytest.fixture(scope="module")
def train_hlo():
    mesh = make_test_mesh(1)
    params = T.init_lm(CFG, jax.random.PRNGKey(0), jnp.float32)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "targets": jnp.zeros((2, 32), jnp.int32)}
    fn = build_train_step(CFG, mesh, remat=True)
    with mesh:
        return jax.jit(fn).lower(params, init_opt(params),
                                 batch).compile().as_text()


@pytest.fixture(scope="module")
def mixed_hlo():
    params = T.init_lm(CFG, jax.random.PRNGKey(0), jnp.float32)
    pool = T.PagedKVCache.zeros(CFG, 9, 8, jnp.float32)
    n_packs, chunk, n_pages, lanes = 2, 8, 4, 3

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    args = (pool, i32(n_packs, 1, chunk), i32(n_packs, 1, chunk),
            i32(n_packs, n_pages), i32(n_packs, chunk), i32(n_packs, chunk),
            i32(n_packs), i32(n_packs), i32(lanes), i32(lanes),
            i32(lanes, n_pages), jnp.ones((lanes,), jnp.int32), i32(lanes),
            i32(lanes))
    lowered = jax.jit(lambda p, *a: T.lm_mixed_paged(CFG, p, *a)).lower(
        params, *args)
    # XLA's CPU pass that drops a unit batch dimension from a product
    # rebuilds the dot without its metadata; the chip runs a kernel there
    return lowered.compile(compiler_options={
        "xla_disable_hlo_passes": "batch-dot-simplification"}).as_text()


@pytest.mark.parametrize("step", ["train", "mixed"])
def test_every_product_names_its_layer(step, train_hlo, mixed_hlo):
    ins = instructions(train_hlo if step == "train" else mixed_hlo)
    found = products_and_their_fusions(ins)
    assert any(op == "dot" for _, _, op, _, _ in found)
    unnamed = [(n, on) for _, n, _, on, _ in found if not LAYER.search(on)]
    assert not unnamed, unnamed


def test_train_step_backward_and_update_scopes(train_hlo):
    names = [on for _, _, _, on, _ in instructions(train_hlo)]

    def layers_of(on):
        return set(LAYER.findall(on))

    for layer in (scopes.ATTN, scopes.MLP, scopes.HEAD):
        assert any(layer in layers_of(on) and "transpose(" in on
                   for on in names), layer
    update = [on for on in names if scopes.ADAMW in layers_of(on)]
    assert update and not any("transpose(" in on for on in update)


def test_mixed_step_stages_are_scoped(mixed_hlo):
    dots = [on for _, _, op, on, _ in instructions(mixed_hlo) if op == "dot"]
    for stage in (scopes.PREFILL, scopes.DECODE):
        assert any(f"/{stage}/" in on for on in dots), stage
