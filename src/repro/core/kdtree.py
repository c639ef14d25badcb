"""Level-synchronous k-d tree partitioning + subset labeling (paper Algs 2-3).

The paper builds the tree with one MapReduce job per level: reducers split
each sub-region at the exact median along a cycling axis, appending one bit to
the region id.  The TPU adaptation keeps the *level-synchronous* schedule but
replaces the shuffle with sorts: per level, one (region, coord) sort computes
every region's exact median split simultaneously, and a second sort, keyed on
the first one's permutation, returns the new region ids to point order.
Exact median splits make every region's start and size a function of n and
the level alone, so a point's rank inside its region follows from its sorted
position by elementwise integer work (:func:`_median_segments`): no counts,
no scatter and no gather.  ``depth`` levels <=> the paper's O(log n)
MapReduce jobs.

Everything is pure jnp and jit-safe for a static ``depth`` / ``num_subsets``,
and — because sorts and scatters are SPMD-partitionable — runs sharded under
pjit on a mesh without modification.  Past one pod that is no longer enough:
GSPMD lowers the level sorts and the pack's sort and gather as dataset-sized
collectives over the slow DCN axis.  The ``*_sharded`` variants here run the
same algorithms under ``shard_map`` with points sharded over
``(pods, devices)`` and exchange only O(regions * 256) histogram summaries
per radix round — the whole S1 then scales past single-pod memory with
per-level cross-host traffic independent of n (see
:func:`build_kdtree_histogram_sharded`, :func:`label_regions_histogram_sharded`,
and the ``pod_axis`` mode of :func:`pack_subsets_a2a`).
"""
from __future__ import annotations

import math
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp



class Partition(NamedTuple):
    subset_ids: jnp.ndarray      # (n,) int32 in [0, num_subsets)
    region_ids: jnp.ndarray      # (n,) int32 in [0, 2**depth) — tree leaves
    depth: int                   # tree levels == number of "MapReduce jobs"


def _segment_rank(sort_primary: jnp.ndarray, order: jnp.ndarray, num_segments: int):
    """Given a permutation ``order`` that sorts by (segment, key), return for
    each *sorted* position its rank within its segment and the segment size."""
    n = sort_primary.shape[0]
    sorted_seg = sort_primary[order]
    counts = jnp.bincount(sort_primary, length=num_segments)           # (m,)
    starts = jnp.cumsum(counts) - counts                               # (m,)
    rank = jnp.arange(n, dtype=jnp.int32) - starts[sorted_seg].astype(jnp.int32)
    size = counts[sorted_seg].astype(jnp.int32)
    return sorted_seg, rank, size


def _median_step(pos: jnp.ndarray, start: jnp.ndarray, size: jnp.ndarray):
    """One exact median split of the segment [start, start+size) that holds
    sorted position ``pos``: the left child takes ceil(size/2).  Returns
    whether ``pos`` falls in the right child, and that child's start and
    size."""
    half = (size + 1) // 2
    right = (pos - start) >= half
    return (right, jnp.where(right, start + half, start),
            jnp.where(right, size - half, half))


def _median_segments(n: int, levels: int):
    """Start and size of the segment that holds each sorted position of n
    points after ``levels`` exact median splits — the positional form of
    :func:`_segment_rank` for any tree those splits build."""
    pos = jnp.arange(n, dtype=jnp.int32)
    start = jnp.zeros(n, jnp.int32)
    size = jnp.full(n, n, jnp.int32)
    for _ in range(levels):
        _, start, size = _median_step(pos, start, size)
    return start, size


@partial(jax.jit, static_argnames=("depth",))
def build_kdtree(points: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Assign every point a leaf region id via ``depth`` median-split rounds.

    Axes cycle x, y, x, y, ... exactly as in the paper's 2-D construction;
    the left child takes ceil(size/2) points ("split at median point").
    Returns (n,) int32 region ids in [0, 2**depth).

    A level is two sorts: a stable (region, coord) sort that carries the
    point index (ties keep index order, as ``jnp.lexsort``), and a sort on
    that permutation which returns the new ids to point order.
    """
    n, d = points.shape
    pos = jnp.arange(n, dtype=jnp.int32)
    start = jnp.zeros(n, jnp.int32)
    size = jnp.full(n, n, jnp.int32)
    region = jnp.zeros(n, dtype=jnp.int32)
    for level in range(depth):
        coord = points[:, level % d]
        sorted_seg, _, order = jax.lax.sort((region, coord, pos), num_keys=2)
        child, start, size = _median_step(pos, start, size)
        new_sorted = sorted_seg * 2 + child.astype(jnp.int32)
        _, region = jax.lax.sort((order, new_sorted), num_keys=1)
    return region


def _monotone_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving float32 -> uint32 mapping (IEEE-754 trick)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where((b >> 31) == 1, ~b, b | jnp.uint32(0x80000000))


def _histogram_median_go_right(key: jnp.ndarray, idx: jnp.ndarray,
                               region: jnp.ndarray, num_regions: int,
                               axis_names: tuple[str, ...] | None = None,
                               active: jnp.ndarray | None = None):
    """Exact per-region median split WITHOUT sorting.

    Radix-refines the median over 8 byte-rounds (4 bytes of the monotone
    float key + 4 bytes of the point index as a unique tie-break, matching
    the stable lexsort's ordering).  Per round: one histogram scatter-add
    of active points into (R, 256) bins — O(n) traffic and an O(R*256)
    reduction, vs a full O(n log n) global sort per tree level.  This is
    the §Perf cell-C optimization; equality with the sort-based splitter
    is asserted in tests.

    With ``axis_names`` the function runs inside ``shard_map`` over a
    points-sharded mesh: all per-point state stays shard-local and each
    (R, 256) histogram (plus the initial per-region counts) is psum'd over
    the mesh axes, so the cross-shard traffic per round is O(R * 256) ints
    regardless of n.  Because histogram entries are integer adds, the
    reduced counts — and therefore every median decision — are bit-for-bit
    identical to the single-device build as long as ``idx`` carries
    globally-unique point indices.  ``active`` masks shard-padding rows out
    of every count (their ``less``/``match`` outputs are meaningless).
    """
    n = key.shape[0]
    live = jnp.ones(n, bool) if active is None else active
    counts = jnp.zeros((num_regions,), jnp.int32).at[region].add(
        live.astype(jnp.int32))
    if axis_names is not None:
        counts = jax.lax.psum(counts, axis_names)
    remaining = ((counts + 1) // 2).astype(jnp.int32)     # ceil -> left
    match = live
    less = jnp.zeros(n, bool)
    for r in range(8):
        if r < 4:
            byte = (key >> (8 * (3 - r))) & jnp.uint32(0xFF)
        else:
            byte = (idx >> (8 * (7 - r))) & jnp.uint32(0xFF)
        byte = byte.astype(jnp.int32)
        hist = jnp.zeros((num_regions * 256,), jnp.int32).at[
            region * 256 + byte].add(match.astype(jnp.int32))
        hist = hist.reshape(num_regions, 256)
        if axis_names is not None:
            hist = jax.lax.psum(hist, axis_names)
        cum = jnp.cumsum(hist, axis=1)
        bstar = jnp.argmax(cum >= remaining[:, None], axis=1).astype(jnp.int32)
        below = jnp.where(bstar > 0,
                          jnp.take_along_axis(
                              cum, jnp.maximum(bstar - 1, 0)[:, None],
                              axis=1)[:, 0],
                          0)
        remaining = remaining - below.astype(jnp.int32)
        b_reg = bstar[region]
        less = less | (match & (byte < b_reg))
        match = match & (byte == b_reg)
    # the unique surviving point is the median element; it joins the left
    # half iff one left-slot remains (remaining == 1 by construction)
    left = less | (match & (remaining[region] > 0))
    return ~left


@partial(jax.jit, static_argnames=("depth",))
def build_kdtree_histogram(points: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Sort-free k-d tree build: identical output to :func:`build_kdtree`
    (exact medians, same tie-breaks) via radix-histogram median selection.
    O(depth * 8) histogram passes instead of O(depth) global sorts."""
    n, d = points.shape
    idx = jnp.arange(n, dtype=jnp.uint32)
    keys = _split_keys(points, depth)
    region = jnp.zeros(n, dtype=jnp.int32)
    for level in range(depth):
        key = keys[level % d]
        go_right = _histogram_median_go_right(key, idx, region, 2 ** level)
        region = region * 2 + go_right.astype(jnp.int32)
    return region


def _split_keys(points: jnp.ndarray, depth: int) -> dict:
    """Monotone u32 keys of the coordinates the first ``depth`` levels split
    on, ``{axis: (n,) keys}``.  Columns come from the transposed points: a
    column sliced as ``(n, 1)`` is laid out on a TPU in (8, 128) tiles, 128x
    its size (20 GiB of HBM temporaries at n = 2^22, d = 128)."""
    cols = points.T
    return {a: _monotone_u32(cols[a])
            for a in sorted({level % points.shape[1]
                             for level in range(depth)})}


def _shard_linear_index(mesh, axis_names: tuple[str, ...]):
    """Linearized (major-to-minor over ``axis_names``) program index inside a
    shard_map body — matches how ``P(axis_names)`` tiles a global array, so
    ``linear_index * n_loc`` is the shard's global row offset."""
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _mesh_size(mesh, axis_names: tuple[str, ...]) -> int:
    size = 1
    for a in axis_names:
        size *= mesh.shape[a]
    return size


def _pad_for_shards(arrs, n: int, n_shards: int):
    """Pad leading axis to a multiple of ``n_shards``; returns the padded
    arrays plus the (n_pad,) active mask (all-True when already even)."""
    pad = -n % n_shards
    if pad:
        arrs = [jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) for a in arrs]
    active = jnp.arange(n + pad) < n
    return arrs, active


def build_kdtree_histogram_sharded(points: jnp.ndarray, depth: int,
                                   mesh, axis_names: tuple[str, ...]
                                   ) -> jnp.ndarray:
    """Multi-host k-d tree build: :func:`build_kdtree_histogram` under
    ``shard_map`` with points sharded over ``axis_names`` (on the k-means pod
    mesh: ``("pods", "data")`` — the slow DCN axis plus the in-pod devices).

    Each shard radix-refines every region's median on its local points and
    psums the (R, 256) byte histograms across the mesh per round, so the
    cross-host traffic per tree level is depth-independent O(R * 256) ints —
    the dataset itself never moves.  Point indices are globally unique
    (shard offset + local arange), preserving the stable-sort tie-break:
    region ids are bit-for-bit identical to the single-device build
    (asserted in tests, ties and all).  n that doesn't divide the shard
    count is padded internally with masked rows.
    """
    n, d = points.shape
    axes = tuple(axis_names)
    n_shards = _mesh_size(mesh, axes)
    (pts,), active = _pad_for_shards([points], n, n_shards)
    n_loc = pts.shape[0] // n_shards

    def body(x_loc, act_loc):
        offset = (_shard_linear_index(mesh, axes) * n_loc).astype(jnp.uint32)
        idx = offset + jnp.arange(n_loc, dtype=jnp.uint32)
        keys = _split_keys(x_loc, depth)
        region = jnp.zeros(n_loc, dtype=jnp.int32)
        for level in range(depth):
            go_right = _histogram_median_go_right(
                keys[level % d], idx, region, 2 ** level,
                axis_names=axes, active=act_loc)
            region = region * 2 + go_right.astype(jnp.int32)
        return region

    from jax.sharding import PartitionSpec as P
    # jitted: the distributed entry points call this eagerly, and an eager
    # shard_map runs (and on a TPU compiles) its body op by op
    region = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P(axes, None), P(axes)),
                                   out_specs=P(axes), check_vma=False))(
        pts, active)
    return region[:n]


def required_depth(n: int, leaf_capacity: int) -> int:
    """Levels so leaves hold ~leaf_capacity points.

    The paper splits 'until every sub region contains at most M points' and
    its Table-3 arithmetic (58 reducers x 258-point subsets on 15000 pts)
    implies leaves of size *closest to* M — one split further would halve
    the leaves and leave subsets M/2..M-1 empty (labels are ranks within
    the leaf).  So: depth = round(log2(n / capacity)), leaf in (M/2, M]."""
    if n <= leaf_capacity:
        return 0
    return max(0, round(math.log2(n / leaf_capacity)))


def _label_key(points: jnp.ndarray, key: jax.Array, strategy: str,
               label_axis: int) -> jnp.ndarray:
    """The per-point labeling key for Algorithm 3's two variants."""
    if strategy == "axis":
        return points[:, label_axis]
    if strategy == "random":
        return jax.random.uniform(key, (points.shape[0],))
    raise ValueError(f"unknown labeling strategy: {strategy}")


@partial(jax.jit, static_argnames=("num_regions", "num_subsets", "strategy", "label_axis"))
def label_regions(points: jnp.ndarray,
                  region_ids: jnp.ndarray,
                  key: jax.Array,
                  num_regions: int,
                  num_subsets: int,
                  strategy: str = "axis",
                  label_axis: int = 0) -> jnp.ndarray:
    """Paper Algorithm 3: label points 1..M inside each leaf; label i forms
    subset i.  ``strategy``:

      * ``'axis'``   — variant (2): sort along ``label_axis`` inside the leaf
        and label left-to-right (the paper's winning variant).
      * ``'random'`` — variant (1): random permutation inside the leaf.

    Labels wrap mod ``num_subsets`` so leaf capacity need not equal M.

    Precondition: ``region_ids`` are the leaves of a depth-log2(num_regions)
    exact median-split tree over these same points (from either builder),
    so a point's rank inside its leaf follows from its sorted position; the
    labels return to point order by a sort keyed on the permutation.
    """
    depth = num_regions.bit_length() - 1
    if num_regions != 1 << depth:
        raise ValueError(f"num_regions must be a power of two, got {num_regions}")
    n = region_ids.shape[0]
    key2 = _label_key(points, key, strategy, label_axis)
    pos = jnp.arange(n, dtype=jnp.int32)
    _, _, order = jax.lax.sort((region_ids, key2, pos), num_keys=2)
    start, _ = _median_segments(n, depth)
    label_sorted = (pos - start) % num_subsets
    _, labels = jax.lax.sort((order, label_sorted), num_keys=1)
    return labels


# Number of histogram buckets per region for the sort-free labeler.  256
# matches the radix fan-out of the tree build; with leaf_capacity-sized
# regions each bucket holds only a handful of points, so the bucketed order
# is as stratified as the exact sort for the paper's labeling purpose.
_LABEL_BUCKETS = 256


def _region_buckets(key2: jnp.ndarray, region_ids: jnp.ndarray,
                    num_regions: int, active: jnp.ndarray | None = None,
                    axis_names: tuple[str, ...] | None = None) -> jnp.ndarray:
    """Per-point bucket id in [0, 256): the point's labeling key quantized
    against its region's [min, max] span.  Scatter-min/max per region, pmin /
    pmax across shards when ``axis_names`` is given — min/max are order-
    independent, so sharded and single-device buckets are bit-identical."""
    f = key2.astype(jnp.float32)
    reg = region_ids if active is None else jnp.where(
        active, region_ids, num_regions)
    lo = jnp.full((num_regions,), jnp.inf, f.dtype).at[reg].min(f, mode="drop")
    hi = jnp.full((num_regions,), -jnp.inf, f.dtype).at[reg].max(f, mode="drop")
    if axis_names:
        lo = jax.lax.pmin(lo, axis_names)
        hi = jax.lax.pmax(hi, axis_names)
    w = hi - lo
    t = (f - lo[region_ids]) / jnp.where(w > 0, w, 1.0)[region_ids]
    return jnp.clip((t * _LABEL_BUCKETS).astype(jnp.int32),
                    0, _LABEL_BUCKETS - 1)


@partial(jax.jit, static_argnames=("num_regions", "num_subsets", "strategy", "label_axis"))
def label_regions_histogram(points: jnp.ndarray,
                            region_ids: jnp.ndarray,
                            key: jax.Array,
                            num_regions: int,
                            num_subsets: int,
                            strategy: str = "axis",
                            label_axis: int = 0) -> jnp.ndarray:
    """Single-device reference for the sort-free labeling order.

    Canonical order inside a region: (bucket, original index), where bucket
    quantizes the labeling key against the region's span
    (:func:`_region_buckets`).  This is the order the distributed labeler
    (:func:`label_regions_histogram_sharded`) reproduces bit-for-bit from
    O(R * 256) summaries — the exact-key order of :func:`label_regions`
    cannot be recovered without a dataset-sized exchange, so the histogram
    pair defines its own (equally stratified) canonical order instead.
    """
    key2 = _label_key(points, key, strategy, label_axis)
    b = _region_buckets(key2, region_ids, num_regions)
    order = jnp.lexsort((b, region_ids))
    _, rank, _ = _segment_rank(region_ids, order, num_regions)
    label_sorted = (rank % num_subsets).astype(jnp.int32)
    return jnp.zeros_like(region_ids).at[order].set(label_sorted)


def _exclusive_shard_scan(x: jnp.ndarray, mesh,
                          axis_names: tuple[str, ...]) -> jnp.ndarray:
    """Sum of ``x`` over all shards with a strictly smaller linearized
    (major-to-minor over ``axis_names``) index.

    Hillis-Steele doubling over ``lax.ppermute`` per axis — ceil(log2 P)
    rounds of O(|x|) messages, instead of the all-gather of every shard's
    copy (which at production shapes is a multi-GB blow-up)."""
    axes = tuple(axis_names)
    out = jnp.zeros_like(x)
    for pos, a in enumerate(axes):
        inner = axes[pos + 1:]
        t = jax.lax.psum(x, inner) if inner else x
        inc = t
        shift = 1
        while shift < mesh.shape[a]:
            perm = [(s, s + shift) for s in range(mesh.shape[a] - shift)]
            inc = inc + jax.lax.ppermute(inc, a, perm)
            shift *= 2
        out = out + (inc - t)
    return out


def label_regions_histogram_sharded(points: jnp.ndarray,
                                    region_ids: jnp.ndarray,
                                    num_regions: int,
                                    num_subsets: int,
                                    mesh,
                                    axis_names: tuple[str, ...],
                                    label_axis: int = 0) -> jnp.ndarray:
    """Distributed Algorithm 3 (axis variant) without the global lexsort.

    A point's rank inside its region decomposes into three order-independent
    pieces: (a) the count of region points in strictly smaller buckets — an
    exclusive cumsum of the psum'd (R, 256) histogram; (b) the count of
    same-(region, bucket) points on shards with smaller linear index — an
    exclusive shard scan of the local histogram; (c) its stable local rank
    within the (region, bucket) cell.  Because global point order is
    shard-major, (a)+(b)+(c) equals the single-device
    :func:`label_regions_histogram` rank exactly, so subset ids are
    bit-for-bit identical — while cross-shard traffic is O(R * 256) ints
    instead of the dataset-sized all-gather GSPMD makes of a lexsort.
    """
    n = points.shape[0]
    axes = tuple(axis_names)
    n_shards = _mesh_size(mesh, axes)
    (pts, reg), active = _pad_for_shards([points, region_ids], n, n_shards)
    nb = num_regions * _LABEL_BUCKETS

    def body(x_loc, reg_loc, act_loc):
        b = _region_buckets(x_loc[:, label_axis], reg_loc, num_regions,
                            active=act_loc, axis_names=axes)
        rb = jnp.where(act_loc, reg_loc * _LABEL_BUCKETS + b, nb)
        hist_loc = jnp.zeros(nb, jnp.int32).at[rb].add(
            act_loc.astype(jnp.int32), mode="drop")
        hist = jax.lax.psum(hist_loc, axes)
        h2 = hist.reshape(num_regions, _LABEL_BUCKETS)
        base = (jnp.cumsum(h2, axis=1) - h2).reshape(-1)   # (a): bucket start
        pref = _exclusive_shard_scan(hist_loc, mesh, axes)  # (b): shard prefix
        order = jnp.argsort(rb, stable=True)                # (c): local rank
        _, lrank_sorted, _ = _segment_rank(rb, order, nb + 1)
        lrank = jnp.zeros_like(lrank_sorted).at[order].set(lrank_sorted)
        rbc = jnp.minimum(rb, nb - 1)  # padded rows: any in-range cell
        rank = base[rbc] + pref[rbc] + lrank
        return (rank % num_subsets).astype(jnp.int32)

    from jax.sharding import PartitionSpec as P
    label = jax.jit(jax.shard_map(body, mesh=mesh,
                                  in_specs=(P(axes, None), P(axes), P(axes)),
                                  out_specs=P(axes), check_vma=False))(
        pts, reg, active)
    return label[:n]


@partial(jax.jit, static_argnames=("num_subsets",))
def random_partition(points: jnp.ndarray, key: jax.Array, num_subsets: int):
    """Variant (3): global random partition, no k-d tree (ablation baseline).

    Uses a random permutation + round-robin so subset sizes stay balanced,
    matching how HashPartitioner would spread records across reducers."""
    n = points.shape[0]
    perm = jax.random.permutation(key, n)
    ids = jnp.zeros(n, dtype=jnp.int32).at[perm].set(
        (jnp.arange(n) % num_subsets).astype(jnp.int32))
    return ids


@partial(jax.jit, static_argnames=("num_subsets", "capacity"))
def pack_subsets(points: jnp.ndarray,
                 subset_ids: jnp.ndarray,
                 num_subsets: int,
                 capacity: int):
    """Gather points into a rectangular (M, capacity, d) tensor + bool mask.

    This is the shuffle that routes each subset to its reducer: one stable
    sort of the ids carries the point index, a search of the sorted ids
    gives every subset's start and count, and slot (s, j) gathers the row
    at sorted position ``starts[s] + j``.  Within a subset rows keep point
    order; slots past a subset's count hold zeros with mask False; a subset
    with more than ``capacity`` points loses its highest-index rows (cannot
    happen for kd-tree labeling with capacity >= ceil(num_leaves * leaf_cap
    / M); asserted in tests).  No scatter: under GSPMD the gather moves the
    data once, where a scatter lowers as a dataset-sized all-reduce.

    When every subset holds exactly ``capacity`` points (full kd leaves,
    n == M * capacity), the sorted order reshaped is already the source
    map, so the index arithmetic and its gather are skipped.
    """
    n = subset_ids.shape[0]              # ids label the first n points
    ids, order = jax.lax.sort(
        (subset_ids.astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1)
    starts = jnp.searchsorted(
        ids, jnp.arange(num_subsets + 1, dtype=jnp.int32), method="scan")
    counts = starts[1:] - starts[:-1]
    slot = jnp.arange(capacity, dtype=jnp.int32)
    mask = slot < counts[:, None]

    def ragged_source(order):
        pos = jnp.minimum(starts[:-1, None] + slot, n - 1)
        # an empty slot reads row points.shape[0], out of range: zero fill
        return jnp.where(mask, order[pos], points.shape[0])

    if n == num_subsets * capacity:
        src = jax.lax.cond(jnp.all(counts == capacity),
                           lambda o: o.reshape(num_subsets, capacity),
                           ragged_source, order)
    else:
        src = ragged_source(order)
    packed = points.at[src].get(mode="fill", fill_value=0)
    return packed, mask


@partial(jax.jit, static_argnames=("num_subsets", "capacity"))
def pack_subsets_sorted(points: jnp.ndarray,
                        subset_ids: jnp.ndarray,
                        num_subsets: int,
                        capacity: int):
    """Equal-size pack via one sort + reshape (no scatter).

    Valid when every subset holds exactly ``capacity`` points (true for the
    kd-tree labeling whenever n == num_subsets * capacity, i.e. full
    leaves).  GSPMD would lower a scatter pack as a local-scatter +
    full-output ALL-REDUCE (a dataset-sized reduction); the sort+gather
    formulation moves the data once instead — §Perf C2.
    """
    n, d = points.shape
    assert n == num_subsets * capacity, (n, num_subsets, capacity)
    order = jnp.argsort(subset_ids, stable=True)
    packed = points[order].reshape(num_subsets, capacity, d)
    return packed, jnp.ones((num_subsets, capacity), bool)


def pack_subsets_a2a(points: jnp.ndarray,
                     subset_ids: jnp.ndarray,
                     num_subsets: int,
                     capacity: int,
                     mesh,
                     axis_names: tuple[str, ...],
                     slack: float = 1.3,
                     pod_axis: str | None = None):
    """Communication-optimal pack: explicit all_to_all shuffle (§Perf C3).

    GSPMD lowers both the gather- and the sort-based packs as dataset-
    sized all-reduce/all-gather; but the shuffle's destinations are known
    (subset s lives on device s // (M/R)), so a capacity-padded shard_map
    all_to_all moves each point exactly once — the same dispatch pattern as
    the MoE layer.  Per-(src,dst) capacity is n_loc/R * slack; overflow
    drops are impossible for region-aligned inputs and negligible for
    random order (the returned ``dropped`` count makes any loss loud).

    With ``pod_axis`` (the slow DCN axis of a pods x devices mesh, points
    sharded over ``(pod_axis,) + axis_names``) the all_to_all runs only
    over the in-pod ``axis_names``: a point moves to its subset's owner
    *column* inside its own pod row, and the packed tensor's capacity axis
    is sharded over pods (each pod owns a ``capacity // n_pods`` slice of
    every subset).  The pack itself therefore costs ZERO DCN payload —
    exactly the property the S2 cross-pod solve expects, since it reduces
    per-subset stats over the pod axis anyway.

    Returns ``(packed (M, capacity, d), mask (M, capacity), dropped)`` —
    packed/mask sharded (M over ``axis_names``, capacity over ``pod_axis``),
    ``dropped`` a replicated scalar count of points lost to slot or
    capacity overflow (0 in healthy configurations; callers should check).

    Preconditions (else: warn + :func:`pack_subsets` fallback):
    ``num_subsets`` divides by the in-pod device count, ``n`` by the total
    device count, and ``capacity`` by the pod count.
    """
    from jax.sharding import PartitionSpec as P

    n, d = points.shape
    r = _mesh_size(mesh, tuple(axis_names))
    n_pods = mesh.shape[pod_axis] if pod_axis else 1
    n_dev = r * n_pods
    precondition = None
    if num_subsets % r:
        precondition = f"num_subsets={num_subsets} % in-pod devices={r} != 0"
    elif n % n_dev:
        precondition = f"n={n} % devices={n_dev} != 0"
    elif capacity % n_pods:
        precondition = f"capacity={capacity} % pods={n_pods} != 0"
    if precondition:
        warnings.warn(
            "pack_subsets_a2a: falling back to the gather pack "
            f"(pack_subsets) because {precondition}",
            RuntimeWarning, stacklevel=2)
        out, msk = pack_subsets(points, subset_ids, num_subsets, capacity)
        return out, msk, jnp.int32(n) - msk.sum(dtype=jnp.int32)
    m_loc = num_subsets // r
    n_loc = n // n_dev
    cap_loc = capacity // n_pods
    # per-(src, dst) send slots: mean * slack plus a 4-sigma binomial floor —
    # at small per-destination means the multiplicative slack alone is tighter
    # than ordinary statistical fluctuation (send buffers are r*c_send*d
    # floats, so the extra headroom is noise)
    mean = n_loc / r
    c_send = max(8, -(-int(mean * slack + 4 * math.sqrt(mean)) // 8) * 8)
    axes = tuple(axis_names)
    all_axes = ((pod_axis,) + axes) if pod_axis else axes

    def body(pts_loc, ids_loc):
        # route local points to the in-pod device owning their subset
        dst = (ids_loc // m_loc).astype(jnp.int32)
        order = jnp.argsort(dst, stable=True)
        _, slot_sorted, _ = _segment_rank(dst, order, r)
        slot = jnp.zeros(n_loc, jnp.int32).at[order].set(slot_sorted)
        slot = jnp.where(slot < c_send, slot, c_send)        # drop overflow
        send_x = jnp.zeros((r, c_send, d), pts_loc.dtype).at[
            dst, slot].set(pts_loc, mode="drop")
        send_id = jnp.full((r, c_send), -1, jnp.int32).at[
            dst, slot].set(ids_loc.astype(jnp.int32), mode="drop")
        recv_x = jax.lax.all_to_all(send_x, axes, 0, 0, tiled=True)
        recv_id = jax.lax.all_to_all(send_id, axes, 0, 0, tiled=True)
        # local re-pack into (m_loc, cap_loc, d)
        flat_x = recv_x.reshape(r * c_send, d)
        flat_id = recv_id.reshape(r * c_send)
        local_sub = jnp.where(flat_id >= 0, flat_id % m_loc, m_loc)
        order2 = jnp.argsort(local_sub, stable=True)
        _, rank_sorted, _ = _segment_rank(local_sub, order2, m_loc + 1)
        rank = jnp.zeros(r * c_send, jnp.int32).at[order2].set(rank_sorted)
        valid = (flat_id >= 0) & (rank < cap_loc)
        out = jnp.zeros((m_loc, cap_loc, d), pts_loc.dtype).at[
            jnp.where(valid, local_sub, m_loc),
            jnp.where(valid, rank, cap_loc)].set(flat_x, mode="drop")
        msk = jnp.zeros((m_loc, cap_loc), bool).at[
            jnp.where(valid, local_sub, m_loc),
            jnp.where(valid, rank, cap_loc)].set(True, mode="drop")
        total = jax.lax.psum(msk.sum(dtype=jnp.int32), all_axes)
        return out, msk, total

    spec = P(all_axes)
    out, msk, total = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(P(axes, pod_axis, None), P(axes, pod_axis), P()),
        check_vma=False))(points, subset_ids)
    return out, msk, jnp.int32(n) - total


def partition_dataset(points: jnp.ndarray,
                      key: jax.Array,
                      num_subsets: int,
                      leaf_capacity: int | None = None,
                      strategy: str = "kd_axis",
                      label_axis: int = 0,
                      builder: str = "sort",
                      labeler: str = "sort",
                      mesh=None,
                      axis_names: tuple[str, ...] | None = None) -> Partition:
    """Full stage-1 pipeline: tree build + labeling (or random partition).

    ``strategy`` in {'kd_axis', 'kd_random', 'random'} — the paper's variants
    (2), (1) and (3) respectively.  ``builder``: 'sort' (paper-faithful
    level-sync sorts) or 'histogram' (identical output, sort-free — §Perf).
    ``labeler``: 'sort' (exact-key order) or 'histogram' (bucketed order,
    required for the distributed path).

    With ``mesh`` + ``axis_names`` the whole stage runs under ``shard_map``
    with points sharded over ``axis_names`` (e.g. ``("pods", "data")`` on the
    k-means pod mesh): per-level cross-shard traffic is the O(R * 256)
    histogram summaries, never the points.  Requires
    ``builder == labeler == 'histogram'`` and ``strategy == 'kd_axis'`` —
    the sort build/labeling would be lowered as dataset-sized collectives,
    and the random variants have no shard-invariant key stream.
    """
    n = points.shape[0]
    cap = num_subsets if leaf_capacity is None else leaf_capacity
    if strategy == "random":
        with jax.named_scope("s1_label"):
            ids = random_partition(points, key, num_subsets)
        return Partition(subset_ids=ids,
                         region_ids=jnp.zeros(n, jnp.int32), depth=0)
    depth = required_depth(n, cap)
    if mesh is not None:
        if axis_names is None:
            raise ValueError("sharded partition_dataset needs axis_names")
        if builder != "histogram" or labeler != "histogram":
            raise ValueError(
                "sharded partition_dataset requires builder='histogram' and "
                f"labeler='histogram' (got {builder!r}/{labeler!r}); the "
                "sort paths lower as dataset-sized collectives")
        if strategy != "kd_axis":
            raise ValueError(
                f"sharded partition_dataset supports strategy='kd_axis' "
                f"only (got {strategy!r})")
        with jax.named_scope("s1_tree"):
            region = build_kdtree_histogram_sharded(points, depth, mesh,
                                                    tuple(axis_names))
        with jax.named_scope("s1_label"):
            ids = label_regions_histogram_sharded(
                points, region, 2 ** depth, num_subsets, mesh,
                tuple(axis_names), label_axis=label_axis)
        return Partition(subset_ids=ids, region_ids=region, depth=depth)
    build = build_kdtree_histogram if builder == "histogram" else build_kdtree
    with jax.named_scope("s1_tree"):
        region = build(points, depth)
    label_strategy = "axis" if strategy == "kd_axis" else "random"
    label = label_regions_histogram if labeler == "histogram" else label_regions
    with jax.named_scope("s1_label"):
        ids = label(points, region, key, 2 ** depth, num_subsets,
                    strategy=label_strategy, label_axis=label_axis)
    return Partition(subset_ids=ids, region_ids=region, depth=depth)
