"""Centroid initialization strategies.

The paper evaluates with fixed, shared initial centroids (same centroids fed
to PKMeans and to every IPKMeans reducer) — ``sample_init`` reproduces that.
Beyond the paper, seeding is exactly what controls iterations-to-converge,
which for the resident/batched megakernels means on-chip while-loop trips
per launch:

  * ``kmeans_plus_plus`` — classic sequential k-means++ (Arthur &
    Vassilvitskii 2007), k passes; robust to degenerate residual mass
    (duplicated points, ``k`` > distinct points) by masking chosen indices
    out of the distribution and falling back to uniform over the remainder.
    Selection only: every centroid IS an input point.  Also the weighted
    recluster core of the k-means|| driver.
  * ``kmeans_parallel_init`` — k-means|| (Scalable K-Means++, Bahmani et
    al., PAPERS.md): O(log n) *rounds*, each ONE fused distance+min+sample
    sweep over the points (``kernels/init.py``; ``backend="ref"`` runs the
    bitwise-identical jnp oracle), oversampling an expected ``ell``
    candidates per round, then a weighted k-means++ recluster of the
    ~``ell * rounds`` candidates on-host.  With a ``mesh``, each round's
    sweep runs per-shard under ``shard_map`` (points sharded, candidates
    replicated, potential psum'd) — the distributed path.
  * ``resolve_init`` — the strategy dispatcher the pipeline entry points
    (``kmeans``, ``ipkmeans``, ``ipkmeans_distributed``) call when
    ``init != "given"``.  Runs on host (rounds are a host loop over kernel
    launches), which is why init resolution lives at the entry points and
    not inside the jitted solver cores.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import metrics
from repro.kernels import ref

#: strategies understood by the pipeline (``KMeansParams.init`` /
#: ``IPKMeansConfig.with_init``).  "given" = caller supplies centroids.
INIT_METHODS = ("given", "sample", "kmeans++", "kmeans||")


@partial(jax.jit, static_argnames=("k",))
def sample_init(points: jnp.ndarray, key: jax.Array, k: int) -> jnp.ndarray:
    """Sample k distinct points uniformly as initial centroids.

    Top-k of i.i.d. uniform keys: the k largest draws are a uniform
    k-subset, with O(n) work and O(k) selection state — no O(n)
    permutation materialized (``random.choice(..., replace=False)``
    permutes the whole index range).
    """
    r = jax.random.uniform(key, (points.shape[0],))
    _, idx = jax.lax.top_k(r, k)
    return points[idx]


@partial(jax.jit, static_argnames=("k",))
def kmeans_plus_plus(points: jnp.ndarray, key: jax.Array, k: int,
                     weights: jnp.ndarray | None = None) -> jnp.ndarray:
    """k-means++ seeding (Arthur & Vassilvitskii 2007): each next centroid is
    sampled proportionally to (weighted) squared distance from the chosen set.

    Degeneracy-robust: already-chosen indices are masked out of every draw,
    and when the residual D^2 mass underflows to ~0 (duplicated points,
    ``k`` greater than the number of distinct points) the draw falls back to
    uniform over the not-yet-chosen remainder — so the returned centroids
    are k distinct input points whenever ``k <= n``.  ``weights`` (optional,
    (n,)) scale each point's mass — zero-weight points are drawn only by the
    last-resort fallback; this weighted form is the k-means|| recluster.
    """
    n, d = points.shape
    w0 = (jnp.ones((n,), jnp.float32) if weights is None
          else weights.astype(jnp.float32))

    def draw(sub, mass, chosen):
        # mass over unchosen -> weighted remainder -> uniform remainder ->
        # uniform over everything (k > n; only then may repeats appear)
        residual = jnp.where(chosen, 0.0, mass)
        weighted = jnp.where(chosen, 0.0, w0)
        uniform = jnp.where(chosen, 0.0, 1.0)
        src = jnp.where(jnp.sum(residual) > 0.0, residual,
                        jnp.where(jnp.sum(weighted) > 0.0, weighted,
                                  jnp.where(jnp.sum(uniform) > 0.0, uniform,
                                            jnp.ones((n,), jnp.float32))))
        probs = src / jnp.maximum(jnp.sum(src), 1e-30)
        return jax.random.choice(sub, n, p=probs)

    k0, key = jax.random.split(key)
    first = draw(k0, w0, jnp.zeros((n,), bool))
    chosen = jnp.zeros((n,), bool).at[first].set(True)
    centroids = jnp.zeros((k, d), points.dtype).at[0].set(points[first])

    def body(i, carry):
        cents, chosen, key = carry
        key, sub = jax.random.split(key)
        d2 = metrics.pairwise_sq_dists(points, cents)
        # distances to not-yet-chosen slots must not win the min
        valid = jnp.arange(k) < i
        d2 = jnp.where(valid[None, :], d2, jnp.inf)
        mass = jnp.min(d2, axis=-1) * w0
        idx = draw(sub, mass, chosen)
        return (cents.at[i].set(points[idx]), chosen.at[idx].set(True), key)

    centroids, _, _ = jax.lax.fori_loop(1, k, body, (centroids, chosen, key))
    return centroids


# ------------------------------------------------------------- k-means|| ---

@partial(jax.jit, static_argnames=("ell",))
def _ref_sweep(points, cands, cand_valid, old_mind, uniforms, weights,
               psi_prev, *, ell):
    return ref.init_sweep_ref(points, cands, old_mind, uniforms, psi_prev,
                              ell=ell, cand_valid=cand_valid, weights=weights)


def _make_sweep(backend: str, spec, mesh, axis_names):
    """The per-round sweep callable: fused Pallas kernel or jnp oracle,
    optionally wrapped in a per-shard ``shard_map`` round (points/mind/
    uniforms/weights sharded over ``axis_names``, candidates replicated,
    partial potentials psum'd — on a 1-device mesh this is bitwise the
    single-host sweep)."""
    if backend == "kernel":
        from repro.kernels import ops

        def sweep(x, cands, valid, om, u, w, pp, ell):
            return ops.init_sweep(x, cands, om, u, pp, ell=ell,
                                  cand_valid=valid, weights=w, spec=spec)
    elif backend == "ref":
        def sweep(x, cands, valid, om, u, w, pp, ell):
            return _ref_sweep(x, cands, valid, om, u, w, pp, ell=ell)
    else:
        raise ValueError(f"unknown init sweep backend: {backend!r} "
                         f"(expected 'kernel' | 'ref')")
    if mesh is None:
        return sweep

    def sharded(x, cands, valid, om, u, w, pp, ell):
        def body(xs, oms, us, ws):
            mind, samp, psi = sweep(xs, cands, valid, oms, us, ws, pp, ell)
            return mind, samp, jax.lax.psum(psi, axis_names)

        sp = P(axis_names)
        run = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=(sp, sp, sp, sp),
                                    out_specs=(sp, sp, P()),
                                    check_vma=False))
        return run(x, om, u, w)

    return sharded


def kmeans_parallel_init(points: jnp.ndarray, key: jax.Array, k: int, *,
                         ell: float | None = None,
                         rounds: int | None = None,
                         weights: jnp.ndarray | None = None,
                         backend: str = "kernel",
                         spec=None,
                         mesh=None,
                         axis_names: tuple[str, ...] = ("data",),
                         return_stats: bool = False):
    """k-means|| seeding (Bahmani et al.): oversampled O(log n)-round init.

    Round structure — each round is ONE fused sweep (kernel or oracle) that
    (a) folds the previous round's new candidates into the running per-point
    min squared distance, (b) reduces the new potential ``psi = sum(w *
    mind)``, and (c) Bernoulli-draws the round's candidates with probability
    ``min(1, ell * mind / psi_prev)``.  Sampling uses the PREVIOUS round's
    potential — the slightly conservative variant that makes one sweep per
    round possible (the potential is non-increasing, so draw probabilities
    are only ever under-, never over-estimated).  Round 0 scores the
    weighted-uniform first pick with ``psi_prev = 0`` (no draws).  The
    ~``ell * rounds`` candidates are then weighted by how many points each
    one captures (one assignment pass) and reclustered with weighted
    k-means++ *selection* — so every returned centroid is an input point.

    Defaults: ``ell = 2k`` (the paper's recommended O(k) oversampling),
    ``rounds = min(8, max(2, ceil(log2(n / k))))`` — the O(log n) round
    count, capped because ~5 rounds suffice in practice (Bahmani §5).

    ``backend="kernel"`` runs the fused Pallas sweep (``kernels/init.py``),
    ``"ref"`` the bitwise-identical jnp oracle.  ``spec`` pins the kernel
    geometry (default: the autotuned init winner for the steady-state
    candidate tile, else module defaults).  With ``mesh``, each sweep runs
    per-shard under ``shard_map`` with the candidate set replicated.
    """
    points = jnp.asarray(points)
    n, d = points.shape
    if n < 1:
        raise ValueError("kmeans_parallel_init needs at least one point")
    ell = float(2 * k) if ell is None else float(ell)
    if rounds is None:
        rounds = min(8, max(2, int(math.ceil(math.log2(max(n, 2) / max(k, 1))
                                             )) if n > k else 2))
    rounds = max(1, int(rounds))
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))

    if spec is None and backend == "kernel":
        from repro.kernels import tuning
        cap0 = max(8, 1 << max(0, int(math.ceil(ell)) - 1).bit_length())
        spec = tuning.lookup_init_spec(n, d, cap0, points.dtype)
    sweep = _make_sweep(backend, spec, mesh, axis_names)

    keys = jax.random.split(key, rounds + 3)
    first_key, recluster_key, round_keys = keys[0], keys[1], keys[2:]
    # weighted-uniform first pick (uniform when unweighted)
    probs = np.asarray(w, np.float64)
    total = probs.sum()
    probs = (probs / total if total > 0
             else np.full((n,), 1.0 / n))
    first = int(jax.random.choice(first_key, n,
                                  p=jnp.asarray(probs, jnp.float32)))

    cand_idx = [first]
    new_idx = np.array([first], np.int64)
    old_mind = jnp.full((n,), jnp.inf, jnp.float32)
    psi_prev = jnp.float32(0.0)
    psi_trace = []
    # sweeps 0..rounds: sweep r folds round r-1's draws and draws round r's
    # (round 0 folds the first pick and draws nothing: psi_prev == 0); the
    # final sweep's draws join the pool unfolded — the recluster weighting
    # re-scores every candidate anyway.  A candidate folds to mind == 0, so
    # the strict Bernoulli inequality can never re-draw it: the pool is
    # duplicate-free by construction.
    for r in range(rounds + 1):
        u = jax.random.uniform(round_keys[r], (n,), jnp.float32)
        # pad the new-candidate buffer to a power of two so the round loop
        # compiles O(log) kernel variants, not one per candidate count
        cap = max(8, 1 << max(0, int(new_idx.size) - 1).bit_length())
        idx_pad = np.zeros((cap,), np.int64)
        idx_pad[:new_idx.size] = new_idx
        cands = points[jnp.asarray(idx_pad)]
        valid = jnp.asarray(np.arange(cap) < new_idx.size)
        mind, samp, psi = sweep(points, cands, valid, old_mind, u, w,
                                psi_prev, ell)
        old_mind, psi_prev = mind, psi
        psi_trace.append(float(psi))
        new_idx = np.flatnonzero(np.asarray(samp))
        cand_idx.extend(new_idx.tolist())

    cand = np.unique(np.asarray(cand_idx, np.int64))
    if cand.size < k:
        # degenerate draw (tiny n, tiny ell): top up with the farthest
        # points so the recluster always has k distinct rows when n >= k
        order = np.argsort(-np.asarray(old_mind), kind="stable")
        have = set(cand.tolist())
        extra = [i for i in order if int(i) not in have][:k - cand.size]
        cand = np.concatenate([cand, np.asarray(extra, np.int64)])

    cands = points[jnp.asarray(cand)]
    # candidate weights: total point mass each candidate captures
    if backend == "kernel":
        from repro.kernels import ops
        labels, _ = ops.lloyd_assign_fused(points, cands, spec=spec)
    else:
        labels, _ = ref.assign_ref(points, cands)
    cweights = jnp.zeros((cand.size,), jnp.float32).at[labels].add(w)
    centroids = kmeans_plus_plus(cands, recluster_key, k, weights=cweights)
    centroids = centroids.astype(points.dtype)
    if return_stats:
        return centroids, {"candidates": int(cand.size), "rounds": rounds,
                           "ell": ell, "psi": psi_trace}
    return centroids


# ------------------------------------------------------------- dispatch ----

def resolve_init(points: jnp.ndarray, key: jax.Array, k: int, method: str, *,
                 weights: jnp.ndarray | None = None,
                 backend: str = "kernel",
                 spec=None,
                 mesh=None,
                 axis_names: tuple[str, ...] = ("data",)) -> jnp.ndarray:
    """Resolve an init strategy name to (k, d) centroids.

    The single entry the pipeline wrappers call; ``method="given"`` is the
    callers' own branch (they already hold centroids).  ``backend`` selects
    the k-means|| sweep implementation (``"kernel"`` | ``"ref"``); the
    host-loop strategies ignore it.
    """
    if method not in INIT_METHODS or method == "given":
        raise ValueError(f"unknown init method: {method!r} "
                         f"(expected one of {INIT_METHODS[1:]})")
    with obs.span("init"):
        if method == "sample":
            return sample_init(points, key, k)
        if method == "kmeans++":
            return kmeans_plus_plus(points, key, k, weights=weights)
        return kmeans_parallel_init(points, key, k, weights=weights,
                                    backend=backend, spec=spec,
                                    mesh=mesh, axis_names=axis_names)
