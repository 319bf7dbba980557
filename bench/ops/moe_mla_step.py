"""Work of one decode step of a DeepSeek-V3-block decoder (Moonlight):
leading dense layers, then layers of latent attention (MLA) and routed
experts, served with the absorbed latent decode.

``flops_per_token``: model FLOPs per processed token, for the step's share
of the chip's peak. Every weight matmul a token needs counts 2 FLOPs per
weight: the direct query projection, the latent down-projection, the
absorbed key and value up-projections (``w_uk``, ``w_uv``), the output
projection; the dense SwiGLU in the leading layers; in the expert layers
the router, the ``top_k`` chosen experts and the shared experts; and the
unembedding. The embedding lookup counts none. Attention adds, per head
and cached position, 2 FLOPs per lane of the latent row (scores) and 2 per
lane of the latent (weighted sum). Norms, RoPE, softmax and the gates are
left out.

``step_bytes``: the bytes one step must read from HBM: every weight but
the embedding table (the dropless decode path, and with 64 rows any
routing, reaches every expert), plus the occupied latent pages, once, at
their unpadded width.
"""


def _attention_weights(c: dict) -> int:
    d, h, m = c["d_model"], c["n_heads"], c["mla"]
    r, rope = m["kv_lora_rank"], m["rope_head_dim"]
    nope, v = m["nope_head_dim"], m["v_head_dim"]
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v)
            + h * v * d)


def flops_per_token(c: dict, context: float) -> float:
    d, m, e = c["d_model"], c["mla"], c["moe"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["n_layers"] - n_dense
    expert = 3 * d * e["d_expert"]
    dense_layer = _attention_weights(c) + 3 * d * c["d_ff"]
    moe_layer = (_attention_weights(c) + d * e["n_experts"]
                 + (e["top_k"] + e["n_shared"]) * expert)
    weights = n_dense * dense_layer + n_moe * moe_layer \
        + d * c["vocab_size"]
    attend = 2.0 * c["n_layers"] * c["n_heads"] \
        * (2 * m["kv_lora_rank"] + m["rope_head_dim"]) * context
    return 2.0 * weights + attend


def weight_bytes(c: dict, dtype_bytes: int = 2) -> float:
    """Every weight of the stack but the embedding table."""
    d, e = c["d_model"], c["moe"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["n_layers"] - n_dense
    norms = 2 * d + c["mla"]["kv_lora_rank"]
    dense_layer = _attention_weights(c) + 3 * d * c["d_ff"] + norms
    moe_layer = (_attention_weights(c) + norms + d * e["n_experts"]
                 + e["n_experts"] * (e["scoring"] == "sigmoid")
                 + (e["n_experts"] + e["n_shared"]) * 3 * d * e["d_expert"])
    n = n_dense * dense_layer + n_moe * moe_layer + d * c["vocab_size"] + d
    return float(n * dtype_bytes)


def step_bytes(c: dict, pages: float, page_size: int,
               dtype_bytes: int = 2) -> float:
    m = c["mla"]
    cache = c["n_layers"] * pages * page_size \
        * (m["kv_lora_rank"] + m["rope_head_dim"]) * dtype_bytes
    return weight_bytes(c, dtype_bytes) + cache
