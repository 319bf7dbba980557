"""Operations and bytes the writhe map needs for one call.

One call maps ``batch`` chains of ``n_points`` points: every ordered pair
of the n_points - 1 segments gets a Gauss-integral term. Counted as the
algorithm needs them, not as the kernel pads them: the kernel's padding to
its 128-wide blocks is waste that shows as a lower share.

FLOPs per pair, element-wise operations of ``kernels/writhe.py``
``_writhe_block`` counted one each (sqrt and divide included): four
difference vectors (12), four cross products (36), four normalisations
(40), four dot products (20), four clips (8), four arcsines by the Cephes
polynomial (80), the solid-angle sum (3), the two segment vectors (6), the
sign's triple product (15), the scale (3) and the |i - j| <= 1 band (4).
"""

FLOPS_PER_PAIR = 227
F32 = 4


def cost(batch: int, n_points: int) -> tuple[float, float]:
    """(FLOPs, bytes): the map is written once in float32 and the
    coordinates read once."""
    pairs = batch * (n_points - 1) ** 2
    return (float(FLOPS_PER_PAIR * pairs),
            float(F32 * pairs + F32 * 3 * n_points * batch))
