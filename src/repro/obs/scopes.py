"""Names of the device scopes (``jax.named_scope``) that the model code puts
on its work. Each lands in the ``op_name`` metadata of every HLO instruction
traced under it, so a profiler trace of any compiled step can be read by
layer; the compiled program is unchanged. A backward op's ``op_name`` holds
its scope inside ``transpose(...)``. See docs/OBSERVABILITY.md.
"""
EMBED = "embed"     # token-embedding lookup
ATTN = "attn"       # ln_attn through the attention residual add, KV writes
MLP = "mlp"         # ln_mlp through the MLP (or MoE) residual add
HEAD = "head"       # ln_final and the unembedding
LOSS = "loss"       # softmax cross-entropy
ADAMW = "adamw"     # global norm and the AdamW update

LAYERS = (EMBED, ATTN, MLP, HEAD, LOSS, ADAMW)

# outer scopes of the served fused step (``lm_mixed_paged``)
PREFILL = "prefill"
DECODE = "decode"
