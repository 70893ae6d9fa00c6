"""Dense grouped-query-attention decoder (Qwen2 / InternLM2 kind).

Written from the published architecture, not from the program: pre-norm
RMSNorm blocks with gains stored as offsets from 1 (a gain vector is
``1 + scale``), rotary embedding on the two halves of each head (the
Hugging Face ``rotate_half`` layout), causal grouped-query softmax
attention with an optional bias on q, k and v, and a SiLU-gated MLP.

An architecture module gives the benchmark four functions:
``program_config`` (the program's ``ModelConfig``), ``layer_weights`` (one
layer's weights drawn from its key), ``layer_matmul_params`` (weights one
token multiplies in a layer) and ``layer`` (one float32 decoder layer,
the plain reference).
"""
from __future__ import annotations

from model import _normal
from reference import _rms, _rope


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration ``c``."""
    from repro.models.config import ModelConfig
    if c["hidden_act"] != "silu":
        raise ValueError(f"{c['name']}: only silu gated MLPs are served")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), qkv_bias=c["attention_bias"],
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=float(c["rms_norm_eps"]), act="silu", gated_mlp=True)


def layer_weights(c: dict, key, dtype):
    """One layer's weights in the program's layout (``_init_layer``)."""
    import jax
    import jax.numpy as jnp
    D, F = c["hidden_size"], c["intermediate_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    ks = jax.random.split(key, 12)
    attn = {"wq": _normal(jax, jnp, ks[0], (D, qd), D ** -0.5, dtype),
            "wk": _normal(jax, jnp, ks[1], (D, kvd), D ** -0.5, dtype),
            "wv": _normal(jax, jnp, ks[2], (D, kvd), D ** -0.5, dtype),
            "wo": _normal(jax, jnp, ks[3], (qd, D), qd ** -0.5, dtype)}
    if c["attention_bias"]:
        attn["bq"] = _normal(jax, jnp, ks[4], (qd,), 0.1, dtype)
        attn["bk"] = _normal(jax, jnp, ks[5], (kvd,), 0.1, dtype)
        attn["bv"] = _normal(jax, jnp, ks[6], (kvd,), 0.1, dtype)
    return {"ln_attn": {"scale": _normal(jax, jnp, ks[7], (D,), 0.1, dtype)},
            "attn": attn,
            "ln_mlp": {"scale": _normal(jax, jnp, ks[8], (D,), 0.1, dtype)},
            "mlp": {"w_gate": _normal(jax, jnp, ks[9], (D, F), D ** -0.5,
                                      dtype),
                    "w_up": _normal(jax, jnp, ks[10], (D, F), D ** -0.5,
                                    dtype),
                    "w_down": _normal(jax, jnp, ks[11], (F, D), F ** -0.5,
                                      dtype)}}


def layer_matmul_params(c: dict) -> int:
    """Weights in one layer's matrix products: q, k, v, o and the MLP."""
    D, F = c["hidden_size"], c["intermediate_size"]
    H, Hkv, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    return D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 3 * D * F


def layer(c: dict, w: dict, x, mm, attn_mm):
    """``x`` (B, S, D) after one decoder layer with float32 weights ``w``.
    ``mm(h, w)`` takes each product with a weight, ``attn_mm(a, b)`` the
    batched products of attention (queries by keys, probabilities by
    values)."""
    import jax
    import jax.numpy as jnp
    H, Hkv, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    G = H // Hkv
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    B, S, _ = x.shape
    pos = jnp.arange(S)
    a = w["attn"]
    h = _rms(jnp, x, w["ln_attn"]["scale"], eps)
    q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
    if c["attention_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(jnp, q.reshape(B, S, H, Dh), pos, theta)
    k = _rope(jnp, k.reshape(B, S, Hkv, Dh), pos, theta)
    v = v.reshape(B, S, Hkv, Dh)
    # (B, Hkv, G, S, Dh): each query head with its key-value group
    q5 = q.reshape(B, S, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)
    k5 = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, Hkv, G, S, Dh))
    v5 = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, Hkv, G, S, Dh))
    s = attn_mm(q5, jnp.swapaxes(k5, -1, -2)) * Dh ** -0.5
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = attn_mm(jax.nn.softmax(s, axis=-1), v5)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, S, H * Dh)
    x = x + mm(o, a["wo"])
    h = _rms(jnp, x, w["ln_mlp"]["scale"], eps)
    m = w["mlp"]
    g = jax.nn.silu(mm(h, m["w_gate"]))
    return x + mm(g * mm(h, m["w_up"]), m["w_down"])
