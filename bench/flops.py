"""Operations and bytes the work needs, computed from its shapes.

``topk_least_bytes``: the fewest bytes any exact implementation over the
stores must read for one scan of an entity bank: every valid row once, at
the width of the smallest copy the store keeps of it (packed int4: half a
byte per element; int8 where no int4 copy is kept; float32 otherwise).

``prefill_flops``: the FLOPs of one prefill of a decoder described by a
``repro.configs`` ``ModelConfig`` over ``batch`` sequences of ``seq``
positions: every layer's matmuls (query, key and value projections with
grouped KV heads, the output projection, and the MLP: gated SwiGLU with
three matrices or plain GELU with two; in an expert layer, the router and
the ``experts_per_token`` experts each token goes to, plus a shared expert
where there is one), the attention scores and weighted sums over the full
``seq`` x ``seq``, and the LM head on the last position only, which is the
one a verifier reads. Multiply-adds count as two FLOPs; norms, biases,
rotary embeddings and softmax are left out. Only attention layers are
counted: a configuration with other kinds of layer is refused.
"""
from __future__ import annotations

BYTES_PER_ELEMENT = {"int4": 0.5, "int8": 1.0, "fp32": 4.0}


def topk_least_bytes(valid_rows: int, dim: int, smallest_copy: str) -> float:
    return valid_rows * dim * BYTES_PER_ELEMENT[smallest_copy]


def prefill_flops(cfg, batch: int, seq: int) -> dict:
    kinds = cfg.layer_kinds()
    if set(kinds) != {"attn"}:
        raise ValueError(f"{cfg.name}: prefill_flops counts attention layers "
                         f"only, the configuration has {sorted(set(kinds))}")
    tokens = batch * seq
    d, hd = cfg.d_model, cfg.head_dim
    q = d * cfg.num_heads * hd
    kv = 2 * d * cfg.num_kv_heads * hd
    o = cfg.num_heads * hd * d
    mats = 3 if cfg.mlp_activation == "silu" else 2
    per_token = 0
    for i in range(cfg.num_layers):
        per_token += q + kv + o
        m = cfg.moe
        if cfg.is_moe_layer(i):
            per_token += d * m.num_experts
            per_token += m.experts_per_token * 3 * d * m.d_ff_expert
            per_token += 3 * d * m.shared_expert_d_ff
        else:
            per_token += mats * d * (m.dense_d_ff or cfg.d_ff)
    matmul = 2 * tokens * per_token
    attention = (2 * 2 * batch * cfg.num_heads * seq * seq * hd
                 * cfg.num_layers)
    head = 2 * batch * d * cfg.vocab_size
    return {"matmul": float(matmul), "attention": float(attention),
            "lm_head": float(head),
            "total": float(matmul + attention + head)}
