"""Operations and bytes the work needs, computed from its shapes.

``topk_least_bytes``: the fewest bytes any exact implementation over the
stores must read for one scan of an entity bank: every valid row once, at
the width of the smallest copy the store keeps of it (packed int4: half a
byte per element; int8 where no int4 copy is kept; float32 otherwise).

``verifier_prefill_flops``: the FLOPs of one verifier prefill of a decoder
of the Qwen2 kind (GQA attention, SwiGLU MLP) over ``batch`` sequences of
``seq`` positions: the matmuls of every layer, the attention scores and
weighted sums, and the LM head on the last position only, which is the one
the verifier reads. Multiply-adds count as two FLOPs.
"""
from __future__ import annotations

BYTES_PER_ELEMENT = {"int4": 0.5, "int8": 1.0, "fp32": 4.0}


def topk_least_bytes(valid_rows: int, dim: int, smallest_copy: str) -> float:
    return valid_rows * dim * BYTES_PER_ELEMENT[smallest_copy]


def verifier_prefill_flops(*, batch: int, seq: int, layers: int,
                           d_model: int, heads: int, kv_heads: int,
                           head_dim: int, d_ff: int, vocab: int) -> dict:
    tokens = batch * seq
    q = d_model * heads * head_dim
    kv = 2 * d_model * kv_heads * head_dim
    o = heads * head_dim * d_model
    mlp = 3 * d_model * d_ff
    matmul = 2 * tokens * (q + kv + o + mlp) * layers
    # scores and the weighted sum, full (non-causal-skipping) S x S
    attention = 2 * 2 * batch * heads * seq * seq * head_dim * layers
    head = 2 * batch * d_model * vocab
    return {"matmul": float(matmul), "attention": float(attention),
            "lm_head": float(head),
            "total": float(matmul + attention + head)}
