"""Operations and bytes each kernel's work needs, from the shapes.

These count the algorithm's dense work, whatever implements it: the
distance contraction of every point against every centroid (2 k d
operations a point) and the centroid sums (d additions a point).  The
one-hot contraction a kernel uses for the sums, padded lanes and padded
rows do not count, and neither does the extra pass a reseed takes.  Bytes
are what must cross HBM at least once: the points, the centroids in and
out, and per-point results.
"""
from __future__ import annotations

F32 = 4


def lloyd_step(n: int, k: int, d: int) -> tuple[float, float]:
    """One Lloyd iteration over n points: assign and sum."""
    return 2.0 * n * k * d + n * d, F32 * (n * d + 2 * k * d)


def subset_solve(s: int, k: int, d: int, iters: int) -> tuple[float, float]:
    """One S2 subset solved in VMEM: ``iters`` Lloyd iterations and the
    scoring pass of the final centroids, the subset's points read once."""
    flops = iters * (2.0 * s * k * d + s * d) + 2.0 * s * k * d
    return flops, F32 * (s * d + 2 * k * d)

