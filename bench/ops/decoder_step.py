"""Model FLOPs per processed token of a dense decoder (one decode-step
position), for the serve step's share of the chip's peak.

Every weight matmul counts 2 FLOPs per weight: attention projections
(q, k, v, o), the gated MLP (gate, up, down) and the unembedding; the
embedding lookup counts none. Attention adds 4 FLOPs per head dimension
per cached position (scores and weighted sum). Norms, RoPE and softmax are
left out.
"""


def flops_per_token(c: dict, context: float) -> float:
    d, layers = c["d_model"], c["n_layers"]
    h, k, dh, f = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    per_layer = d * (h + 2 * k) * dh + h * dh * d + 3 * d * f
    weights = layers * per_layer + d * c["vocab_size"]
    return 2.0 * weights + 4.0 * layers * h * dh * context
