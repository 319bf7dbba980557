"""Operations and bytes the paged latent decode kernel
(``flash_decode_latent``) needs for one call (one layer of one decode
step).

Every query head of a slot reads the one latent KV head: each occupied
page's rows, ``kv_lora_rank + rope_head_dim`` lanes of the cache's dtype
and no lane padding, are read once and serve as the keys, their first
``kv_lora_rank`` lanes as the values. Each slot's absorbed query and its
output are read and written once. FLOPs are the scores over the whole row
and the weighted sum over the latent, per head, per cached position.
"""


def cost(pages: float, n_slots: int, n_heads: int, kv_lora_rank: int,
         rope_head_dim: int, page_size: int, dtype_bytes: int = 2
         ) -> tuple[float, float]:
    positions = pages * page_size
    row = kv_lora_rank + rope_head_dim
    cache = positions * row * dtype_bytes
    q_out = n_slots * n_heads * (row + kv_lora_rank) * dtype_bytes
    flops = 2.0 * positions * n_heads * (row + kv_lora_rank)
    return flops, cache + q_out
