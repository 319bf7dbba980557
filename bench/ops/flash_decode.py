"""Operations and bytes the paged flash-decode kernel needs for one call
(one layer of one decode step).

The keys and values of the occupied pages are read once, in the cache's
dtype, with no lane padding; each slot's query is read and its output
written once. FLOPs are the two matmuls of attention (scores and the
weighted sum) over every cached position of those pages.
"""


def cost(pages: float, n_slots: int, n_heads: int, n_kv_heads: int,
         head_dim: int, page_size: int, dtype_bytes: int = 2
         ) -> tuple[float, float]:
    positions = pages * page_size
    kv = 2.0 * positions * n_kv_heads * head_dim * dtype_bytes
    q_out = 2.0 * n_slots * n_heads * head_dim * dtype_bytes
    flops = 4.0 * positions * (n_heads // n_kv_heads) * n_kv_heads \
        * head_dim
    return flops, kv + q_out
