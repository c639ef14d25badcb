"""PKMeans baseline (Zhao et al. 2009) — the paper's comparison target.

One Lloyd iteration == one MapReduce job: mappers assign points, <=K reducers
average.  The TPU adaptation keeps the per-iteration global synchronization
explicit: points are sharded over the flattened mesh axis and every iteration
performs a ``psum`` of (sums, counts, shift) — that all-reduce is the
job-per-iteration overhead the paper attacks, and it is what the I/O model and
the roofline collective term meter.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import metrics
from repro.core.kmeans import KMeansParams
from repro.kernels import engine as engines
from repro.kernels import ref


class PKMeansResult(NamedTuple):
    centroids: jnp.ndarray     # (k, d)
    sse: jnp.ndarray           # () total SSE over the full dataset
    iters: jnp.ndarray         # () int32 — one MapReduce job per iteration
    converged: jnp.ndarray     # () bool


def _local_stats(points, centroids, mask, backend):
    """Mapper + combiner: local partial (sums, counts, sse) — one
    ``engine.step`` of the selected Lloyd engine.  PKMeans is structurally
    per-iteration (the psum between steps IS the baseline's overhead), so it
    always drives engines stepwise, never ``engine.solve``."""
    w = None if mask is None else mask.astype(points.dtype)
    return engines.get_engine(backend).step(points, centroids, w)


@partial(jax.jit, static_argnames=("params",))
def pkmeans(points: jnp.ndarray,
            init_centroids: jnp.ndarray,
            mask: jnp.ndarray | None = None,
            params: KMeansParams = KMeansParams()) -> PKMeansResult:
    """Single-process PKMeans: global Lloyd to convergence.

    Numerically identical to the distributed version (the psum is exact), so
    this is both the reference and the single-machine-k-means benchmark line
    used in the paper's Fig 8 / Table 3.
    """
    def cond(carry):
        c, _, it, shift = carry
        return jnp.logical_and(it < params.max_iters, shift > params.tol)

    def body(carry):
        c, _, it, _ = carry
        sums, counts, _ = _local_stats(points, c, mask, params.backend)
        new_c = ref.divide_or_keep(sums, counts,
                                   c.astype(sums.dtype)).astype(c.dtype)
        if params.reseed_empty:
            w = None if mask is None else mask.astype(points.dtype)
            new_c = engines.reseed_empty_clusters(
                engines.get_engine(params.backend), points, w, new_c, counts)
        return (new_c, c, it + 1, metrics.centroid_shift(new_c, c))

    with jax.named_scope("lloyd"):
        init = (init_centroids, init_centroids, jnp.int32(0),
                jnp.asarray(jnp.inf))
        final_c, _, iters, shift = jax.lax.while_loop(cond, body, init)
        converged = shift <= params.tol
    with jax.named_scope("sse"):
        total = metrics.sse(points, final_c, mask)
    return PKMeansResult(final_c, total, iters, converged)


def pkmeans_sharded(mesh,
                    axis_names: tuple[str, ...],
                    params: KMeansParams = KMeansParams()):
    """Build a shard_map'd PKMeans step for a mesh: points sharded over the
    flattened ``axis_names``; each Lloyd iteration all-reduces (K*d + K + 1)
    floats — the explicit per-iteration collective.

    Returns a function (points_sharded, init_centroids, mask) -> PKMeansResult
    with centroids replicated.
    """
    if params.reseed_empty:
        # the farthest in-subset point is shard-local state; the global
        # reseed would need a cross-shard argmax collective (not worth the
        # extra per-iteration all-reduce in the baseline we are measuring)
        raise NotImplementedError(
            "reseed_empty is not supported in pkmeans_sharded; reseeding "
            "targets the per-subset solvers (kmeans/ipkmeans)")

    def solve(points, init_centroids, mask):
        def cond(carry):
            c, _, it, shift = carry
            return jnp.logical_and(it < params.max_iters, shift > params.tol)

        def body(carry):
            c, _, it, _ = carry
            sums, counts, _ = _local_stats(points, c, mask, params.backend)
            sums = jax.lax.psum(sums, axis_names)      # <- the "MapReduce job"
            counts = jax.lax.psum(counts, axis_names)
            new_c = ref.divide_or_keep(sums, counts,
                                       c.astype(sums.dtype)).astype(c.dtype)
            return (new_c, c, it + 1, metrics.centroid_shift(new_c, c))

        init = (init_centroids, init_centroids, jnp.int32(0),
                jnp.asarray(jnp.inf))
        final_c, _, iters, shift = jax.lax.while_loop(cond, body, init)
        _, _, local_sse = _local_stats(points, final_c, mask, params.backend)
        total = jax.lax.psum(local_sse, axis_names)
        return PKMeansResult(final_c, total, iters, shift <= params.tol)

    shard_axes = P(axis_names)
    return jax.shard_map(
        solve, mesh=mesh,
        in_specs=(shard_axes, P(), shard_axes),
        out_specs=PKMeansResult(P(), P(), P(), P()),
        check_vma=False)
