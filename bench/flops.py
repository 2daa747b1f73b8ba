"""Operations and bytes the algorithms need, computed from shapes.

Counts are of what the mathematics requires, not of what an
implementation happens to fetch or pad: a kernel that reads more than it
needs shows a lower roofline share, and the count stays the same whatever
implements it. A multiply-add is 2 operations; bfloat16 is 2 bytes.
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def dense_matmul_params(c: Dict) -> int:
    """Weights one token multiplies through in a dense decoder: the
    attention projections, the SwiGLU MLP and the head (the embedding
    lookup is a gather, no matmul)."""
    d, hq, hkv, hd, ff = c["d_model"], c["n_heads"], c["n_kv_heads"], c["d_head"], c["d_ff"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = 3 * d * ff
    return c["n_layers"] * (attn + mlp) + d * c["vocab"]


def attention_flops(c: Dict, context: int) -> int:
    """Scores and weighted values of one query token against ``context``
    keys, over all layers."""
    return 4 * c["n_layers"] * c["n_heads"] * c["d_head"] * context


def token_flops(c: Dict, context: int) -> int:
    """Forward operations of one token that sees ``context`` keys
    (itself included)."""
    return 2 * dense_matmul_params(c) + attention_flops(c, context)


def request_flops(c: Dict, tokens: int) -> int:
    """Forward operations of a sequence of ``tokens`` tokens, each
    attending to itself and every token before it."""
    mm = 2 * dense_matmul_params(c) * tokens
    return mm + attention_flops(c, 1) * tokens * (tokens + 1) // 2


def flash_decode_call(c: Dict, batch: int, kv_len: int) -> Tuple[int, int]:
    """(operations, bytes) of one ``union_flash_attention`` decode call of
    one layer: ``batch`` single-token queries of all query heads against
    the valid ``kv_len`` prefix of their KV heads. Bytes: Q, the valid K
    and V, and the output, in bfloat16."""
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["d_head"]
    ops = 4 * batch * hq * hd * kv_len
    q_out = 2 * batch * hq * hd * BF16
    kv = 2 * batch * hkv * hd * kv_len * BF16
    return ops, q_out + kv


def roofline_seconds(ops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def train_step_flops(active_matmul_params: int, tokens: int, c: Dict, seq: int) -> int:
    """Forward and backward operations of one training step, recomputation
    not counted: 6 per active matmul weight and token, plus causal
    attention (3 x the forward's 2 x 2 x heads x head dim per key, over
    ``seq (seq + 1) / 2`` query-key pairs per sequence)."""
    seqs = tokens // seq
    attn = 3 * 4 * c["n_layers"] * c["n_heads"] * c["d_head"] * seqs * seq * (seq + 1) // 2
    return 6 * active_matmul_params * tokens + attn
