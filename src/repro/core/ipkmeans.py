"""IPKMeans — the paper's contribution, as a composable JAX pipeline.

Three stages (Section 2):
  S1  partition_dataset : k-d tree median splits + labeling  (O(log n) rounds)
  S2  per-subset k-means: M independent Lloyd solvers to convergence —
      *one* program launch, zero collectives inside the loops (the paper's
      "one single MapReduce job with much more reducers")
  S3  merge             : hierarchical midpoint merging or min-ASSE selection

``ipkmeans`` is the single-process reference; ``ipkmeans_distributed`` runs
S2 under ``shard_map`` with subsets sharded over the mesh, which is the
production path (each device == a stack of Hadoop reducers).

Two scale-out layers sit on top of the single mesh:

  * **pods** — ``ipkmeans_distributed(..., pod_axis="pods")`` on a
    ``(pods x devices)`` mesh (``distributed/sharding.kmeans_pod_mesh``)
    additionally shards each subset's POINTS over the slow cross-host axis.
    Each Lloyd iteration then reduces per-cluster (sums, counts) across
    pods — the one DCN cost of the whole solve — and ``cfg.reduce``
    chooses how: ``"exact"`` (f32 psum) or ``"int8ef"`` (int8
    error-feedback quantization via ``distributed/compress.ef_allreduce``,
    the quantization residual carried across iterations so the Lloyd fixed
    point stays unbiased while the wire payload drops ~4x).
  * **fault tolerance** — ``ipkmeans_recoverable`` drives the S2 stacks
    under the heartbeat Coordinator (``distributed/runtime``): a worker
    that misses its heartbeat is evicted and ONLY its own reducer stack
    re-solves from its last centroid snapshot.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import kdtree, merge, metrics
from repro.core.kmeans import KMeansParams, KMeansResult, kmeans_batched
from repro.kernels import engine as engines
from repro.kernels import ref

REDUCE_MODES = ("exact", "int8ef")
S1_MODES = ("auto", "sort", "histogram")


@dataclasses.dataclass(frozen=True)
class IPKMeansConfig:
    num_clusters: int                       # K — final clusters wanted
    num_subsets: int                        # M — parallel "reducers"
    partition: str = "kd_axis"              # 'kd_axis' | 'kd_random' | 'random'
    merge: str = "min_asse"                 # 'min_asse' | 'hierarchical'
    pack: str = "scatter"                   # 'scatter' | 'sorted' | 'a2a'
    reduce: str = "exact"                   # 'exact' | 'int8ef': cross-pod
                                            # stats reduction (pod_axis only)
    s1: str = "auto"                        # 'auto' | 'sort' | 'histogram':
                                            # tree build + labeling machinery
    leaf_capacity: int | None = None        # default: num_subsets (paper)
    label_axis: int = 0
    kmeans: KMeansParams = KMeansParams()

    def __post_init__(self):
        if self.reduce not in REDUCE_MODES:
            raise ValueError(f"unknown reduce: {self.reduce!r} "
                             f"(expected one of {REDUCE_MODES})")
        if self.s1 not in S1_MODES:
            raise ValueError(f"unknown s1: {self.s1!r} "
                             f"(expected one of {S1_MODES})")

    def with_s1(self, s1: str) -> "IPKMeansConfig":
        """Same config, different S1 machinery ('auto' | 'sort' | 'histogram').

        ``"sort"`` is the paper-faithful lexsort build + exact-key labeling;
        ``"histogram"`` is the radix-histogram build plus the bucketed-rank
        labeler — the pair whose cross-shard traffic is O(R * 256) summaries
        per round, and hence the only pair that can run sharded over the pod
        mesh.  ``"auto"`` (default) picks ``"histogram"`` when
        ``ipkmeans_distributed`` is given a ``pod_axis`` (where the sort
        paths would lower as dataset-sized DCN collectives) and ``"sort"``
        everywhere else, preserving the established single-mesh outputs.
        """
        return dataclasses.replace(self, s1=s1)

    def with_reduce(self, reduce: str) -> "IPKMeansConfig":
        """Same config, different cross-pod reduction ('exact' | 'int8ef').

        Only ``ipkmeans_distributed`` with a ``pod_axis`` performs a
        cross-host reduction, so only there does this knob act: ``"int8ef"``
        quantizes each pod's per-cluster (sums, counts) to int8 with
        per-row scales before the DCN all-gather and carries the
        quantization residual across Lloyd iterations
        (``distributed/compress.ef_allreduce`` — error feedback keeps the
        fixed point unbiased).  The single-process/single-mesh paths have
        no DCN hop and ignore it.
        """
        return dataclasses.replace(self, reduce=reduce)

    def with_backend(self, backend: str) -> "IPKMeansConfig":
        """Same config, different Lloyd engine ('jnp' | 'pallas' | 'fused' |
        'resident' | 'batched' | 'tuned' — any name in the
        ``kernels.engine`` registry).

        The engine is the hot-path choice every S2 reducer executes; this
        helper keeps it switchable without re-spelling the whole config.
        ``batched`` is the intended S2 engine on TPU: subsets are sized to
        fit VMEM, and each device's whole reducer STACK lowers to one
        pipelined multi-group kernel launch (``resident`` runs the same
        per-subset loop but one grid step per reducer, serialized).
        """
        return dataclasses.replace(
            self, kmeans=self.kmeans._replace(backend=backend))

    def with_prune(self, prune: str) -> "IPKMeansConfig":
        """Same config, different pruning mode ('none' | 'bounds').

        ``"bounds"`` turns on Hamerly-style bound-gated block skipping
        inside the whole-solve kernels' convergence loops (the
        ``resident``/``batched``/``tuned`` engines): late iterations of each
        S2 reducer skip the score pass for point blocks whose assignments
        provably cannot change.  Results are bit-for-bit identical to
        ``"none"`` — this is a pure perf knob, safe to flip on any config.
        """
        return dataclasses.replace(
            self, kmeans=self.kmeans._replace(prune=prune))

    def with_init(self, init: str) -> "IPKMeansConfig":
        """Same config, different seeding strategy ('given' | 'sample' |
        'kmeans++' | 'kmeans||').

        Non-``"given"`` strategies let ``ipkmeans``/``ipkmeans_distributed``
        derive the shared per-reducer seeds themselves (from their ``key``)
        instead of taking externally supplied ``init_centroids``.
        ``"kmeans||"`` is the oversampled Bahmani et al. init run as fused
        kernel round sweeps (``core/init.py`` / ``kernels/init.py``) —
        better seeds mean fewer Lloyd iterations per reducer, i.e. fewer
        on-chip while-loop trips per megakernel launch.
        """
        from repro.core.init import INIT_METHODS
        if init not in INIT_METHODS:
            raise ValueError(f"unknown init: {init!r} "
                             f"(expected one of {INIT_METHODS})")
        return dataclasses.replace(
            self, kmeans=self.kmeans._replace(init=init))

    @property
    def init(self) -> str:
        """The seeding strategy (lives on the nested ``KMeansParams``)."""
        return self.kmeans.init

    def subset_capacity(self, n: int) -> int:
        """Static bound on points per subset (tensor packing size)."""
        if self.partition == "random":
            return -(-n // self.num_subsets)                   # ceil
        cap = self.leaf_capacity or self.num_subsets
        depth = kdtree.required_depth(n, cap)
        # leaves hold <= ceil(n / 2^depth) points; labels wrap mod M, so a
        # leaf contributes <= ceil(max_leaf / M) points to each subset
        max_leaf = -(-n // (2 ** depth))
        return (2 ** depth) * (-(-max_leaf // self.num_subsets))


class IPKMeansResult(NamedTuple):
    centroids: jnp.ndarray                  # (K, d) final centroids
    sse: jnp.ndarray                        # () SSE over the FULL dataset
    intermediate: jnp.ndarray               # (M, K, d) per-subset centroids
    asses: jnp.ndarray                      # (M,) per-subset ASSE
    subset_iters: jnp.ndarray               # (M,) Lloyd iterations per subset
    kd_depth: int                           # static: tree levels ("jobs")


def _check_pack_complete(n: int, masks, dropped, pack: str) -> None:
    """Raise if the pack lost points (satellite of §Perf C3: a dropped point
    silently biases every downstream centroid).  Skipped under tracing —
    the distributed entry points run the pack eagerly, so production packs
    are always checked."""
    lost = dropped if dropped is not None else (
        jnp.int32(n) - masks.sum(dtype=jnp.int32))
    if isinstance(lost, jax.core.Tracer):
        return
    lost = int(lost)
    if lost:
        raise ValueError(
            f"pack={pack!r} dropped {lost} of {n} points (packed mask counts "
            f"{n - lost}): subset capacity or a2a slack is too small for "
            "this partition's skew")


def _partition_and_pack(points, key, cfg: IPKMeansConfig,
                        mesh=None, axis_names=None, pod_axis=None):
    """S1: partition, then route each subset to its reducer.

    With a ``mesh`` and ``cfg.s1`` resolving to ``"histogram"``, the whole
    stage runs sharded: the tree build and the labeler exchange only
    O(R * 256) histogram summaries per radix round (points sharded over
    ``(pod_axis,) + axis_names``), and the a2a pack routes each point to
    its subset's owner column inside its own pod — zero DCN payload.
    ``cfg.s1="auto"`` keeps the sort machinery everywhere except the
    pod path, where sorts would lower as dataset-sized DCN collectives.

    The shuffle strategy is ``cfg.pack`` (§Perf C2/C3 — previously
    reachable only from the kmeans_dryrun CLI):

      * ``scatter`` — the general pack (the name is historical): one sort
        and one row gather, no scatter (:func:`kdtree.pack_subsets`);
        always valid.
      * ``sorted``  — one sort + reshape.  Requires every subset to hold
        exactly ``capacity`` points (``n == M * capacity``, the static
        precondition the kernel itself asserts) — otherwise falls back to
        ``scatter``.
      * ``a2a``     — explicit shard_map all_to_all shuffle; needs a mesh
        (so the single-process :func:`ipkmeans` falls back to ``scatter``
        with a warning), and itself warns + falls back when M or n don't
        divide over the mesh.

    Every path's mask count is checked against ``n`` when running eagerly
    (:func:`_check_pack_complete`); the returned subsets' capacity axis is
    always a multiple of the pod count so the pod path can shard it.
    """
    if cfg.pack not in ("scatter", "sorted", "a2a"):
        raise ValueError(f"unknown pack: {cfg.pack!r} "
                         f"(expected 'scatter' | 'sorted' | 'a2a')")
    s1 = cfg.s1
    if s1 == "auto":
        s1 = "histogram" if pod_axis is not None else "sort"
    point_axes = ((pod_axis,) + tuple(axis_names)) if pod_axis \
        else tuple(axis_names or ())
    shard_s1 = (s1 == "histogram" and mesh is not None
                and cfg.partition == "kd_axis")
    part = kdtree.partition_dataset(
        points, key, cfg.num_subsets,
        leaf_capacity=cfg.leaf_capacity,
        strategy=cfg.partition, label_axis=cfg.label_axis,
        builder="histogram" if s1 == "histogram" else "sort",
        labeler="histogram" if s1 == "histogram" else "sort",
        mesh=mesh if shard_s1 else None,
        axis_names=point_axes if shard_s1 else None)
    n = points.shape[0]
    capacity = cfg.subset_capacity(n)
    n_pods = mesh.shape[pod_axis] if (mesh is not None and pod_axis) else 1
    with jax.named_scope("pack"):
        dropped = None
        if cfg.pack == "sorted" and n == cfg.num_subsets * capacity:
            subsets, masks = kdtree.pack_subsets_sorted(
                points, part.subset_ids, cfg.num_subsets, capacity)
        elif cfg.pack == "a2a" and mesh is not None:
            if n_pods > 1:
                # the pod a2a shards capacity over pods, and a pod's share of a
                # subset fluctuates around capacity/n_pods — provision the
                # per-pod slice with the same slack-plus-4-sigma headroom the
                # send buffers use (masked rows are free for the solve)
                mean = capacity / n_pods
                cap_loc = max(8, -(-int(mean * 1.3 + 4 * math.sqrt(mean))
                                   // 8) * 8)
                capacity = cap_loc * n_pods
            subsets, masks, dropped = kdtree.pack_subsets_a2a(
                points, part.subset_ids, cfg.num_subsets, capacity,
                mesh, axis_names, pod_axis=pod_axis)
        else:
            if cfg.pack == "a2a":
                warnings.warn(
                    "pack='a2a' needs a device mesh; using the gather pack "
                    "(one sort and one row gather) instead",
                    RuntimeWarning, stacklevel=2)
            subsets, masks = kdtree.pack_subsets(
                points, part.subset_ids, cfg.num_subsets, capacity)
        _check_pack_complete(n, masks, dropped, cfg.pack)
        pad = -subsets.shape[1] % n_pods
        if pad:
            subsets = jnp.pad(subsets, ((0, 0), (0, pad), (0, 0)))
            masks = jnp.pad(masks, ((0, 0), (0, pad)))
    return part, subsets, masks


def _merge_stage(res: KMeansResult, cfg: IPKMeansConfig):
    m, k, d = res.centroids.shape
    if cfg.merge == "min_asse":
        return merge.min_asse_merge(res.centroids, res.asse)
    if cfg.merge == "hierarchical":
        return merge.hierarchical_merge(res.centroids.reshape(m * k, d), k)
    raise ValueError(f"unknown merge: {cfg.merge}")


@jax.jit
def _total_sse(points: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """SSE of the final centroids over the full dataset.  Every entry point
    calls this one compiled program on its merged centroids, so equal
    centroids give a bit-for-bit equal SSE whichever path produced them."""
    return metrics.sse(points, centroids)


def _resolve_init_stage(points, init_centroids, key, cfg: IPKMeansConfig,
                        mesh=None, axis_names=("data",)):
    """Seeding stage shared by both entry points: when ``cfg.init`` is not
    ``"given"``, derive the shared per-reducer seeds on host (splitting the
    key so partitioning randomness is unchanged only in the "given" path)
    and hand back a ``"given"`` config for the jitted core.  With a mesh,
    the k-means|| round sweeps run per-shard under ``shard_map``."""
    if cfg.init == "given":
        if init_centroids is None:
            raise ValueError('cfg.init="given" needs init_centroids')
        return points, init_centroids, key, cfg
    from repro.core import init as init_mod
    from repro.core.kmeans import _init_backend
    key, ik = jax.random.split(key)
    init_centroids = init_mod.resolve_init(
        points, ik, cfg.num_clusters, cfg.init,
        backend=_init_backend(cfg.kmeans.backend),
        mesh=mesh, axis_names=tuple(axis_names))
    return points, init_centroids, key, cfg.with_init("given")


def ipkmeans(points: jnp.ndarray,
             init_centroids: jnp.ndarray | None,
             key: jax.Array,
             cfg: IPKMeansConfig) -> IPKMeansResult:
    """Single-process IPKMeans (also the distributed path's oracle).

    With ``cfg.init != "given"`` the shared per-reducer seeds are derived
    here on host (k-means|| rounds are a host loop over fused kernel
    sweeps) before the jitted S1-S3 core runs; ``init_centroids`` may then
    be ``None``.
    """
    with obs.span("ipkmeans"):
        points, init_centroids, key, cfg = _resolve_init_stage(
            points, init_centroids, key, cfg)
        with obs.span("ipkmeans.core"):
            res = _ipkmeans_core(points, init_centroids, key, cfg)
        with obs.span("ipkmeans.sse"):
            return res._replace(sse=_total_sse(points, res.centroids))


@partial(jax.jit, static_argnames=("cfg",))
def _ipkmeans_core(points: jnp.ndarray,
                   init_centroids: jnp.ndarray,
                   key: jax.Array,
                   cfg: IPKMeansConfig) -> IPKMeansResult:
    part, subsets, masks = _partition_and_pack(points, key, cfg)
    with jax.named_scope("s2"):
        res = kmeans_batched(subsets, masks, init_centroids, cfg.kmeans)
    with jax.named_scope("s3"):
        centroids = _merge_stage(res, cfg)
    return IPKMeansResult(centroids=centroids, sse=None,
                          intermediate=res.centroids, asses=res.asse,
                          subset_iters=res.iters, kd_depth=part.depth)


def _s2_cross_pod_solve(sub, msk, init_centroids, cfg: IPKMeansConfig,
                        pod_axis: str):
    """Per-program S2 body when each subset's points shard over a pod axis.

    ``sub``/``msk`` are the program's local slices — ``(M_loc, S_loc, d)`` /
    ``(M_loc, S_loc)`` with the subset axis over the in-pod devices and the
    point axis over pods.  Every Lloyd iteration computes local per-cluster
    stats with ``engine.step`` and reduces them over ``pod_axis``: f32 psum
    (``reduce="exact"``) or int8 error-feedback all-gather
    (``reduce="int8ef"``, per-row scales; the EFState residual rides the
    while-loop carry so the quantization error feeds back into the next
    iteration and the fixed point stays unbiased).  All pods receive the
    SAME reduced stats, so per-subset convergence decisions — and therefore
    the loop trip counts — stay consistent across pods without extra
    synchronization.  Returns ``(centroids (M_loc,k,d) f32, sse (M_loc,),
    asse (M_loc,), iters (M_loc,) i32, converged (M_loc,) bool)`` mirroring
    the host solve's semantics (divide-or-keep, max-shift stop criterion,
    final-centroid scoring pass).

    int8ef convergence: a quantized reduction can never place a centroid
    closer to the exact fixed point than the wire precision, so a ``tol``
    tighter than the quantization noise floor would spin to ``max_iters``
    chasing jitter.  Each iteration therefore widens the per-subset stop
    threshold to ``max(tol, noise floor)``, the floor derived from the
    dequantization error bound ``ef_allreduce`` reports: once the observed
    shift is inside the floor, further movement is indistinguishable from
    noise and the lane stops (converged=True — it IS at the fixed point to
    wire precision).
    """
    from repro.distributed import compress
    params = cfg.kmeans
    engine = engines.get_engine(params.backend)
    m_loc = sub.shape[0]
    k, d = init_centroids.shape
    w = msk.astype(sub.dtype)
    step_m = jax.vmap(engine.step)

    c0 = jnp.broadcast_to(init_centroids.astype(jnp.float32), (m_loc, k, d))
    stats0 = {"sums": jnp.zeros((m_loc, k, d), jnp.float32),
              "counts": jnp.zeros((m_loc, k), jnp.float32)}
    # per-row scales: one per (subset, cluster) sums row, one per subset
    # counts vector — empty clusters' all-zero rows round-trip to exact
    # zeros instead of inheriting a big cluster's scale
    axes_spec = {"sums": -1, "counts": -1}
    ef0 = compress.init_ef(stats0)
    tol0 = jnp.full((m_loc,), params.tol, jnp.float32)

    def cond(carry):
        c, iters, shift, eff_tol, ef = carry
        return jnp.any(jnp.logical_and(iters < params.max_iters,
                                       shift > eff_tol))

    def body(carry):
        c, iters, shift, eff_tol, ef = carry
        active = jnp.logical_and(iters < params.max_iters,
                                 shift > eff_tol)
        sums, counts, _ = step_m(sub, c, w)
        stats = {"sums": sums, "counts": counts}
        if cfg.reduce == "int8ef":
            red, ef, err = compress.ef_allreduce(
                stats, ef, pod_axis, axes=axes_spec,
                return_error_bound=True)
        else:
            red = jax.lax.psum(stats, pod_axis)
            err = None
        cnt = jnp.maximum(red["counts"], 0.0)
        upd = jax.vmap(ref.divide_or_keep)(red["sums"], cnt, c)
        if err is not None:
            # per-cluster centroid noise from the quantized (sums, counts):
            # |S~/N~ - S/N| <= (err_S + |c|*err_N) / (N - err_N) per
            # coordinate.  Empty clusters are excluded — divide_or_keep
            # pins them, so they contribute no jitter (their all-zero sums
            # rows quantize exactly anyway).
            e_s = err["sums"][..., 0]                         # (m, k)
            e_n = err["counts"]                               # (m, 1)
            cmax = jnp.max(jnp.abs(upd), axis=-1)             # (m, k)
            noise = jnp.where(
                cnt > 0.0,
                (e_s + cmax * e_n) / jnp.maximum(cnt - e_n, 1.0), 0.0)
            floor = jnp.sqrt(float(d)) * jnp.max(noise, axis=-1)
            eff_tol = jnp.where(active,
                                jnp.maximum(tol0, floor), eff_tol)
        new_c = jnp.where(active[:, None, None], upd, c)
        new_shift = jnp.where(
            active, jax.vmap(metrics.centroid_shift)(new_c, c), shift)
        return (new_c, iters + active.astype(jnp.int32), new_shift,
                eff_tol, ef)

    final_c, iters, shift, eff_tol, _ = jax.lax.while_loop(
        cond, body,
        (c0, jnp.zeros((m_loc,), jnp.int32),
         jnp.full((m_loc,), jnp.inf, jnp.float32), tol0, ef0))
    # final scoring pass at the converged centroids, like engine.solve
    sse = jax.lax.psum(jax.vmap(engine.sse)(sub, final_c, w), pod_axis)
    cnt = jax.lax.psum(jnp.sum(w.astype(jnp.float32), axis=1), pod_axis)
    asse = jnp.where(cnt > 0.0, sse / jnp.maximum(cnt, 1.0), jnp.inf)
    return final_c, sse, asse, iters, shift <= eff_tol


def ipkmeans_distributed(points: jnp.ndarray,
                         init_centroids: jnp.ndarray | None,
                         key: jax.Array,
                         cfg: IPKMeansConfig,
                         mesh,
                         axis_names: tuple[str, ...] = ("data",),
                         pod_axis: str | None = None) -> IPKMeansResult:
    """Production IPKMeans on a device mesh.

    S1 runs jit-sharded on the single-mesh path (sorts partition fine under
    SPMD); with a ``pod_axis`` it instead runs under ``shard_map`` with
    points sharded over ``(pod_axis,) + axis_names`` and the histogram
    build/labeler exchanging only O(R * 256) summaries per radix round —
    no stage ever materializes the dataset on one shard (``cfg.s1``
    controls this; see :meth:`IPKMeansConfig.with_s1`).  S2 runs under
    ``shard_map`` with the subset axis sharded over ``axis_names`` so each
    device drives its own ``lax.while_loop`` with NO collectives — the
    communication-avoidance that defines the paper.  The shard_map body is
    ``kmeans_batched``, so ``cfg.kmeans.backend`` picks how each device
    runs its local stack: per-subset engines vmap (one launch each), while
    ``"batched"`` lowers the whole per-device stack to one pipelined
    megakernel launch.  S3 is O(K*M) and runs replicated.

    ``num_subsets`` must be a multiple of the mesh size along ``axis_names``.

    With ``cfg.init != "given"``, the seeding stage runs first: each
    k-means|| round's fused sweep executes per-shard under ``shard_map``
    (points sharded over ``axis_names``, the round's candidates replicated,
    partial potentials psum'd), and the gathered candidates recluster on
    host — the same rounds the single-host path runs, so on a 1-device
    mesh the seeds (and hence the whole solve) match ``ipkmeans`` exactly.

    With ``pod_axis`` (a mesh axis NOT in ``axis_names``, e.g. from
    ``distributed/sharding.kmeans_pod_mesh``), each subset's points
    additionally shard over that slow cross-host axis and S2 switches to
    the cross-pod solve: one (sums, counts) reduction over ``pod_axis``
    per Lloyd iteration — the job's only DCN traffic — compressed per
    ``cfg.reduce`` (see :meth:`IPKMeansConfig.with_reduce`).  The subset
    capacity is padded up to a multiple of the pod count (masked rows,
    zero effect on the stats).
    """
    points, init_centroids, key, cfg = _resolve_init_stage(
        points, init_centroids, key, cfg, mesh=mesh, axis_names=axis_names)
    n_dev = 1
    for a in axis_names:
        n_dev *= mesh.shape[a]
    if cfg.num_subsets % n_dev:
        raise ValueError(
            f"num_subsets={cfg.num_subsets} not divisible by mesh size {n_dev}")
    if pod_axis is not None:
        if pod_axis in axis_names or pod_axis not in mesh.axis_names:
            raise ValueError(
                f"pod_axis={pod_axis!r} must be a mesh axis outside "
                f"axis_names={axis_names} (mesh has {mesh.axis_names})")
        if cfg.kmeans.reseed_empty:
            raise ValueError(
                "reseed_empty is not supported on the cross-pod S2 path: "
                "farthest-point selection needs a global view of the "
                "subset, but points are sharded over the pod axis")
    elif cfg.reduce != "exact":
        raise ValueError(
            f'reduce={cfg.reduce!r} needs pod_axis: compressed reduction '
            f'acts on the cross-pod stats all-reduce, and without a pod '
            f'axis S2 has no reduction at all (the paper\'s claim)')

    part, subsets, masks = _partition_and_pack(points, key, cfg,
                                               mesh=mesh,
                                               axis_names=axis_names,
                                               pod_axis=pod_axis)

    if pod_axis is None:
        def s2_body(sub, msk):                   # per-device stack of reducers
            return kmeans_batched(sub, msk, init_centroids, cfg.kmeans)

        spec = P(axis_names)
        s2 = jax.jit(jax.shard_map(
            s2_body, mesh=mesh, in_specs=(spec, spec),
            out_specs=KMeansResult(spec, spec, spec, spec, spec),
            check_vma=False))
        res = s2(subsets, masks)
    else:
        def s2_pod_body(sub, msk):
            c, sse, asse, iters, conv = _s2_cross_pod_solve(
                sub, msk, init_centroids, cfg, pod_axis)
            return KMeansResult(centroids=c.astype(init_centroids.dtype),
                                sse=sse, asse=asse, iters=iters,
                                converged=conv)

        sub_spec = P(axis_names, pod_axis, None)
        msk_spec = P(axis_names, pod_axis)
        out = P(axis_names)      # replicated over pods: same reduced stats
        s2 = jax.jit(jax.shard_map(
            s2_pod_body, mesh=mesh, in_specs=(sub_spec, msk_spec),
            out_specs=KMeansResult(out, out, out, out, out),
            check_vma=False))
        res = s2(subsets, masks)
    final = _merge_stage(res, cfg)
    return IPKMeansResult(centroids=final, sse=_total_sse(points, final),
                          intermediate=res.centroids, asses=res.asse,
                          subset_iters=res.iters, kd_depth=part.depth)


def ipkmeans_recoverable(points: jnp.ndarray,
                         init_centroids: jnp.ndarray | None,
                         key: jax.Array,
                         cfg: IPKMeansConfig,
                         *,
                         num_workers: int,
                         iters_per_round: int = 4,
                         snapshot_every: int = 2,
                         max_rounds: int = 200,
                         fail_at: dict | None = None,
                         rejoin_at: dict | None = None,
                         ft=None):
    """IPKMeans with S2 driven under the heartbeat-recovery protocol.

    The whole solve runs under ``distributed/runtime``'s Coordinator:
    ``num_workers`` workers own disjoint reducer stacks (contiguous slices
    of the M subsets — ``num_subsets`` must divide evenly), each round
    advances every unconverged subset by ``iters_per_round`` Lloyd
    iterations (Lloyd is Markov in the centroids, so the chunked advance
    replays exactly the unchunked iteration sequence), and per-stack
    centroid snapshots commit every ``snapshot_every`` rounds.  A worker
    that misses its heartbeat (``fail_at`` injects crashes as
    ``{round: worker_id}``) is evicted once ``ft.heartbeat_timeout``
    elapses and ONLY its own stack re-solves, from its last snapshot —
    survivors never recompute (assertable from the returned work log).

    Returns ``(IPKMeansResult, event log, work)`` — the result matches
    :func:`ipkmeans` on the same inputs; ``log``/``work`` come from
    :func:`repro.distributed.runtime.solve_stacks_with_recovery`.
    """
    from repro.distributed import runtime as rt
    if ft is None:
        ft = rt.FTConfig(heartbeat_timeout=2.5, min_workers=1)
    if cfg.num_subsets % num_workers:
        raise ValueError(f"num_subsets={cfg.num_subsets} not divisible by "
                         f"num_workers={num_workers}")
    points, init_centroids, key, cfg = _resolve_init_stage(
        points, init_centroids, key, cfg)
    part, subsets, masks = _partition_and_pack(points, key, cfg)
    params = cfg.kmeans
    engine = engines.get_engine(params.backend)
    per = cfg.num_subsets // num_workers
    k = init_centroids.shape[0]

    @jax.jit
    def _advance(sub, msk, cents, iters, conv):
        """Advance one stack by <= iters_per_round iterations per lane."""
        def one(p, m, c):
            return engine.solve(p, c, m.astype(p.dtype),
                                max_iters=iters_per_round, tol=params.tol,
                                reseed_empty=params.reseed_empty,
                                prune=params.prune)
        # mapped, not vmapped: see LloydEngine.solve_batched
        new_c, _, it, cv = jax.lax.map(lambda a: one(*a), (sub, msk, cents))
        # freeze already-converged lanes so iteration counts stay faithful
        keep = conv[:, None, None]
        return (jnp.where(keep, cents, new_c.astype(jnp.float32)),
                iters + jnp.where(conv, 0, it),
                jnp.logical_or(conv, cv))

    def advance(stack_id, state):
        cents, iters, conv = state
        sl = slice(stack_id * per, (stack_id + 1) * per)
        cents, iters, conv = _advance(subsets[sl], masks[sl],
                                      cents, iters, conv)
        return (cents, iters, conv), bool(jnp.all(conv))

    c0 = jnp.broadcast_to(init_centroids.astype(jnp.float32),
                          (per, k, init_centroids.shape[1]))
    init_states = [(c0, jnp.zeros((per,), jnp.int32),
                    jnp.zeros((per,), bool)) for _ in range(num_workers)]
    states, log, work = rt.solve_stacks_with_recovery(
        advance, init_states, num_workers=num_workers,
        max_rounds=max_rounds, snapshot_every=snapshot_every,
        fail_at=fail_at, rejoin_at=rejoin_at, cfg=ft)

    cents = jnp.concatenate([s[0] for s in states])
    iters = jnp.concatenate([s[1] for s in states])
    conv = jnp.concatenate([s[2] for s in states])
    w = masks.astype(subsets.dtype)
    sse_m = jax.vmap(engine.sse)(subsets, cents, w)
    cnt = jnp.sum(masks.astype(jnp.float32), axis=1)
    asse = jnp.where(cnt > 0.0, sse_m / jnp.maximum(cnt, 1.0), jnp.inf)
    res = KMeansResult(centroids=cents.astype(init_centroids.dtype),
                       sse=sse_m, asse=asse, iters=iters, converged=conv)
    final = _merge_stage(res, cfg)
    return (IPKMeansResult(centroids=final, sse=_total_sse(points, final),
                           intermediate=res.centroids, asses=res.asse,
                           subset_iters=res.iters, kd_depth=part.depth),
            log, work)
