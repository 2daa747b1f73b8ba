"""Operations the forward pass of a latent-attention, routed-expert
decoder needs (DeepSeek-V2), from the published ``config.json`` keys and
the share of routed experts held here.

Counts are of what the mathematics requires whatever implements it:
attention in its latent (absorbed) form, where each query head is carried
into the latent space once and the context is read as latents and rope
keys; the routed experts at the expected share under uniform routing,
``k x held / E`` of an expert a token. A multiply-add is 2 operations.
"""

from __future__ import annotations

from typing import Dict


def token_matmul_params(c: Dict) -> float:
    """Weights one token multiplies through: the attention projections
    (W_q, W_dkv, W_o) and the absorption of each head's up-projections
    (W_uk into the query, W_uv out of the weighted latents); layer 0's
    dense MLP; each expert layer's router, shared experts and expected
    share of held routed experts; the head (the embedding lookup is a
    gather, no matmul)."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    e, k, de = c["n_routed_experts"], c["num_experts_per_tok"], c["moe_intermediate_size"]
    attn = d * h * (dn + dr) + d * (r + dr) + h * dv * d + h * (dn * r + r * dv)
    moe = d * e + 3 * d * de * c["n_shared_experts"] + 3 * d * de * k * c["experts_held"] / e
    return (c["num_hidden_layers"] * attn + n_dense * 3 * d * c["intermediate_size"]
            + n_moe * moe + d * c["vocab_size"])


def attention_flops(c: Dict, context: int) -> int:
    """Latent attention of one query token against ``context`` positions,
    over all layers: scores against each latent and rope key, and the
    weighted sum of the latents."""
    h, r, dr = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    return c["num_hidden_layers"] * (2 * h * (r + dr) + 2 * h * r) * context


def token_flops(c: Dict, context: int) -> float:
    """Forward operations of one token that sees ``context`` positions
    (itself included)."""
    return 2 * token_matmul_params(c) + attention_flops(c, context)


def request_flops(c: Dict, tokens: int) -> float:
    """Forward operations of a sequence of ``tokens`` tokens, each
    attending to itself and every token before it."""
    return (2 * token_matmul_params(c) * tokens
            + attention_flops(c, 1) * tokens * (tokens + 1) // 2)
