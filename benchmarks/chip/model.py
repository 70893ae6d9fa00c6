"""A configuration file, and the weights made for it from the seed.

``configs/<name>.json`` holds the published config's keys (Hugging Face
names) as the benchmark runs them, the engine settings that every cell of
the configuration shares, and under ``reference`` the name of its
architecture: the module ``arch/<reference>.py``, which gives what
differs between architectures (the program's config, a layer's weights
and their products, the plain reference layer).

Weights are random and made on the device in one jitted call, in the
type they are run in (bf16 served, float32 trained), in the program's
parameter layout. Layer ``l`` is drawn from
``fold_in(key, l)``, so the reference can redraw one layer at a time and
get the very same values.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

LAYER_TAG, EMBED_TAG, HEAD_TAG, FINAL_TAG = 0, 1, 2, 3


def load(name: str) -> dict:
    path = HERE / "configs" / f"{name}.json"
    with open(path) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise ValueError(f"{path}: name {cfg.get('name')!r} != {name!r}")
    arch(cfg)
    return cfg


def arch(c: dict):
    """The architecture module that configuration ``c`` names by its
    ``reference`` key: ``arch/<reference>.py``."""
    name = c["reference"]
    try:
        return importlib.import_module(f"arch.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"arch.{name}":
            raise
        raise ValueError(f"{c['name']}: no architecture {name!r}, expected "
                         f"{HERE / 'arch' / (name + '.py')}") from None


def root_key(seed: int):
    """A threefry key from any whole-number seed (64 bits and more)."""
    import jax
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _normal(jax, jnp, key, shape, scale, dtype):
    # drawn and scaled in float32, rounded once: no fusion can change it
    x = jax.random.normal(key, shape, jnp.float32) * jnp.float32(scale)
    return x.astype(dtype)


def embed_weights(c: dict, key, dtype):
    import jax
    import jax.numpy as jnp
    D, V = c["hidden_size"], c["vocab_size"]
    return _normal(jax, jnp, jax.random.fold_in(key, EMBED_TAG), (V, D),
                   D ** -0.5, dtype)


def head_weights(c: dict, key, dtype):
    """Unembedding (D, V); the transposed embedding when tied."""
    import jax
    import jax.numpy as jnp
    if c["tie_word_embeddings"]:
        return embed_weights(c, key, dtype).T
    D, V = c["hidden_size"], c["vocab_size"]
    return _normal(jax, jnp, jax.random.fold_in(key, HEAD_TAG), (D, V),
                   D ** -0.5, dtype)


def final_norm(c: dict, key, dtype):
    import jax
    import jax.numpy as jnp
    return _normal(jax, jnp, jax.random.fold_in(key, FINAL_TAG),
                   (c["hidden_size"],), 0.1, dtype)


def layer_key(key, layer):
    import jax
    return jax.random.fold_in(jax.random.fold_in(key, LAYER_TAG), layer)


def build_params(c: dict, key, dtype):
    """All weights for ``key`` (traceable: call it inside ``jax.jit``)."""
    import jax
    import jax.numpy as jnp
    a = arch(c)
    layers = jax.vmap(lambda l: a.layer_weights(c, layer_key(key, l), dtype))(
        jnp.arange(c["num_hidden_layers"]))
    p = {"embed": embed_weights(c, key, dtype), "layers": layers,
         "ln_final": {"scale": final_norm(c, key, dtype)}}
    if not c["tie_word_embeddings"]:
        p["lm_head"] = head_weights(c, key, dtype)
    return p


def make_params(c: dict, seed: int, dtype=None):
    """All weights for ``seed``, on the default device, in one jitted call."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    return jax.jit(lambda key: build_params(c, key, dtype))(root_key(seed))
