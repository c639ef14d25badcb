"""The plain reference the benchmark compares every timed output with.

Straightforward ``jax.numpy`` (and NumPy for the kd-tree) in float32, with
every contraction at full f32 precision.  It imports nothing of the program
under test and takes none of its tables: given the same data and the same
semantics (Lloyd with keep-old empty clusters and the farthest-point reseed,
the paper's kd-tree partition and min-ASSE merge), it gives the same
answers.  Large shapes are computed in blocks of rows so that it fits next
to the program's data.

``precision`` is ``"highest"`` for the reference and ``"high"`` for the
control: three bf16 passes per product (the split ``hi + lo`` of each
operand, dropping ``lo * lo``), which is what ``Precision.HIGH`` computes
on a TPU.  The control is emulated explicitly, so that it reads the same
on every backend.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
HIGH = "high"
BLOCK_ROWS = 16384


def matmul_t(a, b, precision: str = HIGHEST):
    """``a @ b.T`` in f32 at ``precision``."""
    if precision == HIGHEST:
        return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if precision != HIGH:
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(x, y):
        return jnp.dot(x, y.T, preferred_element_type=jnp.float32)

    return dot(ah, bl) + dot(al, bh) + dot(ah, bh)


def sq_dists(x, c, precision: str = HIGHEST):
    """(n, d), (k, d) -> (n, k) squared distances, clamped at 0."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    return jnp.maximum(x2 + c2 - 2.0 * matmul_t(x, c, precision), 0.0)


def _blocks(x, rows: int):
    n = x.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} rows do not split into blocks of {rows}")
    return x.reshape(n // rows, rows, *x.shape[1:])


@partial(jax.jit, static_argnames=("precision",))
def assign(x, c, precision: str = HIGHEST):
    """Nearest centroid (lowest index among equals) and its squared
    distance, for every row of ``x``, in blocks of rows."""
    def one(xb):
        d2 = sq_dists(xb, c, precision)
        lab = jnp.argmin(d2, axis=1).astype(jnp.int32)
        return lab, jnp.take_along_axis(d2, lab[:, None], 1)[:, 0]

    lab, mind = jax.lax.map(one, _blocks(x, BLOCK_ROWS))
    return lab.reshape(-1), mind.reshape(-1)


def exact_sse(points, centroids) -> float:
    """Sum over points of the squared distance to the nearest centroid."""
    _, mind = assign(points, jnp.asarray(centroids, jnp.float32))
    return float(np.sum(np.asarray(mind, np.float64)))


def _divide_or_keep(sums, counts, old):
    return jnp.where(counts[:, None] > 0.0,
                     sums / jnp.maximum(counts, 1.0)[:, None], old)


def _shift(new, old):
    return jnp.max(jnp.sqrt(jnp.sum((new - old) ** 2, axis=1)))


@partial(jax.jit, static_argnames=("max_iters", "precision"))
def lloyd(points, c0, *, max_iters: int, tol: float, precision: str = HIGHEST):
    """Lloyd on the whole data set from ``c0``: iterate while
    ``iters < max_iters`` and the largest centroid move exceeds ``tol``;
    empty clusters keep their centroid -> (centroids, iters)."""
    k = c0.shape[0]

    def step(c):
        lab, _ = assign(points, c, precision)
        sums = jax.ops.segment_sum(points, lab, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones_like(lab, jnp.float32), lab,
                                     num_segments=k)
        return _divide_or_keep(sums, counts, c)

    def cond(carry):
        _, it, shift = carry
        return jnp.logical_and(it < max_iters, shift > tol)

    def body(carry):
        c, it, _ = carry
        new = step(c)
        return new, it + 1, _shift(new, c)

    c, it, _ = jax.lax.while_loop(
        cond, body, (c0.astype(jnp.float32), jnp.int32(0),
                     jnp.float32(jnp.inf)))
    return c, it


def sample_rows(key, n: int, k: int) -> jax.Array:
    """Indices of the k largest of n i.i.d. uniforms drawn from ``key``:
    a uniform k-subset of the rows (the FAISS-style random pick)."""
    r = jax.random.uniform(key, (n,))
    return jax.lax.top_k(r, k)[1]


# ------------------------------------------------------------ IPKMeans ----

def kd_subsets(points: np.ndarray, num_subsets: int, label_axis: int = 0):
    """The paper's S1 on the host: kd-tree median splits with axes cycling
    0, 1, 2, ...  (the left child takes ``ceil(size / 2)`` points, ties by
    row index), ``round(log2(n / M))`` levels, then inside each leaf the
    points ranked along ``label_axis`` and labelled ``rank mod M`` ->
    (n,) subset id of every row."""
    n, d = points.shape
    depth = 0 if n <= num_subsets else max(
        0, round(math.log2(n / num_subsets)))

    def ranks(region, key):
        order = np.lexsort((key, region))
        seg = region[order]
        counts = np.bincount(region, minlength=int(region.max()) + 1)
        starts = np.cumsum(counts) - counts
        rank = np.arange(n) - starts[seg]
        return order, seg, rank, counts[seg]

    region = np.zeros(n, np.int64)
    for level in range(depth):
        order, seg, rank, size = ranks(region, points[:, level % d])
        region[order] = seg * 2 + (rank >= (size + 1) // 2)
    order, _, rank, _ = ranks(region, points[:, label_axis])
    subset = np.empty(n, np.int64)
    subset[order] = rank % num_subsets
    return subset


def pack(points, subset: np.ndarray, num_subsets: int):
    """Subset ``m``'s rows in row order, padded to the largest subset ->
    (points (M, S, d), mask (M, S) bool)."""
    order = np.argsort(subset, kind="stable")
    counts = np.bincount(subset, minlength=num_subsets)
    cap = int(counts.max())
    starts = np.cumsum(counts) - counts
    slot = np.arange(subset.size) - starts[subset[order]]
    idx = np.zeros((num_subsets, cap), np.int64)
    mask = np.zeros((num_subsets, cap), bool)
    idx[subset[order], slot] = order
    mask[subset[order], slot] = True
    return jnp.asarray(points)[jnp.asarray(idx)], jnp.asarray(mask)


@partial(jax.jit, static_argnames=("max_iters", "reseed", "precision"))
def _subset_lloyd(x, w, c0, *, max_iters: int, tol: float, reseed: bool,
                  precision: str):
    """One subset's Lloyd to convergence, as the paper's reducer runs it,
    with the farthest-point reseed of empty clusters: the e-th empty
    cluster (in index order) takes the e-th farthest valid point from the
    updated centroids (ties to the lowest row), while points remain ->
    (centroids, sse, asse, iters)."""
    k = c0.shape[0]
    s = x.shape[0]
    kk = min(k, s)

    def assign_one(c):
        d2 = sq_dists(x, c, precision)
        lab = jnp.argmin(d2, axis=1)
        return lab, jnp.take_along_axis(d2, lab[:, None], 1)[:, 0]

    def body(carry):
        c, it, _ = carry
        lab, _ = assign_one(c)
        counts = jax.ops.segment_sum(w, lab, num_segments=k)
        sums = jax.ops.segment_sum(x * w[:, None], lab, num_segments=k)
        new = _divide_or_keep(sums, counts, c)
        if reseed:
            empty = counts <= 0.0
            _, mind = assign_one(new)
            score = jnp.where(w > 0.0, mind, -jnp.inf)
            order = jnp.argsort(-score, stable=True)
            e = jnp.cumsum(empty.astype(jnp.int32)) - 1
            pick = order[jnp.clip(e, 0, s - 1)]
            ok = empty & (e < kk) & (score[pick] > -jnp.inf)
            new = jnp.where(ok[:, None], x[pick], new)
        return new, it + 1, _shift(new, c)

    def cond(carry):
        _, it, shift = carry
        return jnp.logical_and(it < max_iters, shift > tol)

    c, it, _ = jax.lax.while_loop(
        cond, body, (c0.astype(jnp.float32), jnp.int32(0),
                     jnp.float32(jnp.inf)))
    _, mind = assign_one(c)
    sse = jnp.sum(w * mind)
    cnt = jnp.sum(w)
    asse = jnp.where(cnt > 0.0, sse / jnp.maximum(cnt, 1.0), jnp.inf)
    return c, sse, asse, it


def ipkmeans(points, seeds, *, num_subsets: int, max_iters: int, tol: float,
             reseed: bool, precision: str = HIGHEST, label_axis: int = 0):
    """S1 (kd-tree partition), S2 (one Lloyd per subset from the shared
    seeds, one subset after another) and S3 (the subset of least ASSE) ->
    dict of the final centroids and each subset's asse, iters, centroids."""
    subset = kd_subsets(np.asarray(points), num_subsets, label_axis)
    xs, masks = pack(points, subset, num_subsets)
    ws = masks.astype(jnp.float32)
    c0 = jnp.asarray(seeds, jnp.float32)

    @jax.jit
    def solve_all(xs, ws):
        return jax.lax.map(
            lambda a: _subset_lloyd(a[0], a[1], c0, max_iters=max_iters,
                                    tol=tol, reseed=reseed,
                                    precision=precision), (xs, ws))

    cents, _, asses, iters = solve_all(xs, ws)
    return {"centroids": cents[int(jnp.argmin(asses))], "asses": asses,
            "iters": iters, "subset_centroids": cents}


# -------------------------------------------------------------- checks ----

def rel(a, b, floor: float = 1e-30) -> float:
    return float(abs(a - b) / max(abs(b), floor))


def centroid_gaps(got, want) -> np.ndarray:
    """Per-centroid distance between two centroid sets (same order), over
    the reference centroid's norm."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return (np.linalg.norm(got - want, axis=1)
            / np.maximum(np.linalg.norm(want, axis=1), 1e-30))

