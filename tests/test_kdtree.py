"""k-d tree partitioning invariants (paper Algorithms 2-3)."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kdtree


@pytest.fixture(scope="module")
def pts():
    return jax.random.normal(jax.random.key(1), (1000, 2)) * 5


def test_median_split_balance(pts):
    region = kdtree.build_kdtree(pts, depth=4)
    counts = np.bincount(np.asarray(region), minlength=16)
    # exact median splits keep every leaf within +-1 of n/2^d at each level
    assert counts.min() >= 62 and counts.max() <= 63, counts


def test_leaves_are_spatial_boxes(pts):
    """Points in the same leaf after 2 levels share the x-median side and
    their region's y-median side (i.e., splits really are spatial)."""
    region = kdtree.build_kdtree(pts, depth=1)
    x = np.asarray(pts[:, 0])
    r = np.asarray(region)
    assert x[r == 0].max() <= x[r == 1].min() + 1e-6


def test_required_depth():
    assert kdtree.required_depth(3000, 6) == 9     # 3000/2^9 = 5.86 <= 6
    assert kdtree.required_depth(64, 8) == 3
    assert kdtree.required_depth(5, 6) == 0


@pytest.mark.parametrize("strategy", ["kd_axis", "kd_random", "random"])
def test_partition_is_exhaustive(pts, strategy):
    part = kdtree.partition_dataset(pts, jax.random.key(2), 8,
                                    strategy=strategy)
    ids = np.asarray(part.subset_ids)
    assert ids.min() >= 0 and ids.max() < 8
    counts = np.bincount(ids, minlength=8)
    assert counts.sum() == 1000
    # balanced to within one point per leaf
    assert counts.max() - counts.min() <= (2 ** part.depth if part.depth
                                           else 1)


def test_axis_labeling_is_stratified(pts):
    """Every leaf contributes at most ceil(leaf/M) points to each subset —
    the representativeness guarantee random partitioning lacks."""
    m = 8
    part = kdtree.partition_dataset(pts, jax.random.key(3), m)
    region = np.asarray(part.region_ids)
    ids = np.asarray(part.subset_ids)
    for r in np.unique(region):
        sel = ids[region == r]
        per = np.bincount(sel, minlength=m)
        assert per.max() <= -(-len(sel) // m)


def test_pack_subsets_roundtrip(pts):
    m = 8
    part = kdtree.partition_dataset(pts, jax.random.key(4), m)
    cap = 2 ** part.depth
    packed, mask = kdtree.pack_subsets(pts, part.subset_ids, m, cap)
    assert packed.shape == (m, cap, 2)
    # every original point appears exactly once among masked entries
    got = np.asarray(packed[np.asarray(mask)])
    orig = np.asarray(pts)
    got_sorted = got[np.lexsort(got.T)]
    orig_sorted = orig[np.lexsort(orig.T)]
    np.testing.assert_allclose(got_sorted, orig_sorted, rtol=1e-6)
    assert int(mask.sum()) == 1000


def test_histogram_labeling_is_stratified(pts):
    """The bucketed-rank labeler keeps the paper's representativeness
    guarantee: every leaf contributes at most ceil(leaf/M) points per
    subset, exactly like the exact-sort labeler."""
    m = 8
    part = kdtree.partition_dataset(pts, jax.random.key(3), m,
                                    builder="histogram", labeler="histogram")
    region = np.asarray(part.region_ids)
    ids = np.asarray(part.subset_ids)
    assert np.bincount(ids, minlength=m).sum() == 1000
    for r in np.unique(region):
        sel = ids[region == r]
        per = np.bincount(sel, minlength=m)
        assert per.max() <= -(-len(sel) // m)


def test_histogram_labeler_matches_sort_on_distinct_buckets():
    """When every point in a region lands in its own bucket the bucketed
    order IS the key order, so the two labelers agree exactly.  linspace
    keys with < 256 points per region guarantee distinct buckets."""
    n, m, depth = 512, 4, 2
    x = jnp.linspace(0.0, 1.0, n)
    pts = jnp.stack([x, jnp.sin(x * 9.0)], axis=1)
    region = kdtree.build_kdtree_histogram(pts, depth)
    key = jax.random.key(0)
    a = kdtree.label_regions(pts, region, key, 2 ** depth, m)
    b = kdtree.label_regions_histogram(pts, region, key, 2 ** depth, m)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_partition_dataset_sharded_requires_histogram():
    pts = jax.random.normal(jax.random.key(0), (64, 2))

    class FakeMesh:
        shape = {"data": 1}
    with pytest.raises(ValueError, match="histogram"):
        kdtree.partition_dataset(pts, jax.random.key(1), 4,
                                 mesh=FakeMesh(), axis_names=("data",))
    with pytest.raises(ValueError, match="kd_axis"):
        kdtree.partition_dataset(pts, jax.random.key(1), 4,
                                 strategy="kd_random",
                                 builder="histogram", labeler="histogram",
                                 mesh=FakeMesh(), axis_names=("data",))
    with pytest.raises(ValueError, match="axis_names"):
        kdtree.partition_dataset(pts, jax.random.key(1), 4,
                                 builder="histogram", labeler="histogram",
                                 mesh=FakeMesh())


def test_pack_a2a_fallback_warns_and_counts():
    """The a2a preconditions failing must be LOUD: a RuntimeWarning naming
    the failed precondition, plus the 3-tuple contract with a dropped
    count (0 — the scatter fallback at adequate capacity loses nothing)."""
    n, m = 1000, 9                                  # n % devices != 0
    pts = jax.random.normal(jax.random.key(0), (n, 2))
    ids = (jnp.arange(n) % m).astype(jnp.int32)

    class FakeMesh:
        shape = {"data": 3}
    with pytest.warns(RuntimeWarning, match="n=1000"):
        packed, mask, dropped = kdtree.pack_subsets_a2a(
            pts, ids, m, 128, FakeMesh(), ("data",))
    assert int(dropped) == 0
    assert int(mask.sum()) == n
    with pytest.warns(RuntimeWarning, match="num_subsets=8"):
        kdtree.pack_subsets_a2a(pts, ids[:999] % 8, 8, 128,
                                FakeMesh(), ("data",))


# -- the sort-only S1 against the counting formulation it replaced ----------

def _oracle_segment_rank(sort_primary, order, num_segments):
    sorted_seg = sort_primary[order]
    counts = jnp.bincount(sort_primary, length=num_segments)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(sort_primary.shape[0]) - starts[sorted_seg]
    return sorted_seg, rank.astype(jnp.int32), counts[sorted_seg].astype(jnp.int32)


@partial(jax.jit, static_argnames=("depth",))
def _oracle_build(points, depth):
    """lexsort + bincount + scatter back to point order, per level."""
    n, d = points.shape
    region = jnp.zeros(n, dtype=jnp.int32)
    for level in range(depth):
        order = jnp.lexsort((points[:, level % d], region))
        sorted_seg, rank, size = _oracle_segment_rank(region, order, 2 ** level)
        child = (rank >= (size + 1) // 2).astype(jnp.int32)
        region = jnp.zeros_like(region).at[order].set(sorted_seg * 2 + child)
    return region


@partial(jax.jit, static_argnames=("num_regions", "num_subsets", "strategy"))
def _oracle_label(points, region_ids, key, num_regions, num_subsets, strategy):
    key2 = kdtree._label_key(points, key, strategy, 0)
    order = jnp.lexsort((key2, region_ids))
    _, rank, _ = _oracle_segment_rank(region_ids, order, num_regions)
    label_sorted = (rank % num_subsets).astype(jnp.int32)
    return jnp.zeros_like(region_ids).at[order].set(label_sorted)


def _points(n, ties, seed=0):
    x = jax.random.normal(jax.random.key(seed), (n, 3))
    # ties: four distinct values per axis, so most comparisons are equal
    return jnp.floor(jnp.abs(x) * 2.0).clip(0, 3) if ties else x


# (n, depth, forced ties): odd and even n, every depth 1-9
_ORACLE_CASES = [(777, 1, False), (777, 4, True), (777, 9, False),
                 (3001, 2, True), (3001, 7, False), (3001, 9, True),
                 (1000, 3, False), (1000, 5, True),
                 (4096, 6, True), (4096, 8, False)]


@pytest.mark.parametrize("builder", ["sort", "histogram"])
@pytest.mark.parametrize("strategy", ["axis", "random"])
@pytest.mark.parametrize("n,depth,ties", _ORACLE_CASES)
def test_sort_only_s1_matches_counting_oracle(n, depth, ties, strategy,
                                              builder):
    """Positional ranks and sort-restored point order give bit-identical
    region ids and labels to lexsort + bincount + scatter, on leaves from
    either builder."""
    pts = _points(n, ties, seed=n + depth)
    build = (kdtree.build_kdtree if builder == "sort"
             else kdtree.build_kdtree_histogram)
    region = build(pts, depth)
    np.testing.assert_array_equal(np.asarray(region),
                                  np.asarray(_oracle_build(pts, depth)))
    key = jax.random.key(depth)
    m = 7 if ties else 8                     # labels wrap mod M either way
    got = kdtree.label_regions(pts, region, key, 2 ** depth, m,
                               strategy=strategy)
    want = _oracle_label(pts, region, key, 2 ** depth, m, strategy)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _np_tree(x, depth):
    region = np.zeros(len(x), np.int64)
    for level in range(depth):
        order = np.lexsort((x[:, level % x.shape[1]], region))
        counts = np.bincount(region, minlength=2 ** level)
        starts = np.cumsum(counts) - counts
        sorted_seg = region[order]
        rank = np.arange(len(x)) - starts[sorted_seg]
        region[order] = sorted_seg * 2 + (rank >= (counts[sorted_seg] + 1) // 2)
    return region


@pytest.mark.parametrize("n", [1, 2, 3, 5, 64, 777, 1000, 3001, 4096])
def test_median_segments_match_bincount(n):
    """The start and size of each sorted position's segment, from the
    position alone, equal those counted from a real tree's leaves."""
    x = np.random.default_rng(n).standard_normal((n, 2))
    for levels in range(0, 11):
        sorted_leaf = np.sort(_np_tree(x, levels))
        counts = np.bincount(sorted_leaf, minlength=2 ** levels)
        starts = np.cumsum(counts) - counts
        start, size = kdtree._median_segments(n, levels)
        np.testing.assert_array_equal(np.asarray(start), starts[sorted_leaf])
        np.testing.assert_array_equal(np.asarray(size), counts[sorted_leaf])


def test_label_regions_needs_power_of_two_regions(pts):
    region = kdtree.build_kdtree(pts, depth=2)
    with pytest.raises(ValueError, match="power of two"):
        kdtree.label_regions(pts, region, jax.random.key(0), 3, 4)


_INDEXED_ACCESS = re.compile(r"\b(scatter|gather)\(")


@pytest.mark.parametrize("fn", ["build_kdtree", "label_regions_axis",
                                "label_regions_random"])
def test_sort_only_s1_compiles_without_scatter_or_gather(fn):
    """The compiled tree build and labeler move data by sorts alone: two a
    level for the build, two for the labeler."""
    depth, n = 6, 2 ** 12
    pts = jax.ShapeDtypeStruct((n, 16), jnp.float32)
    if fn == "build_kdtree":
        lowered = kdtree.build_kdtree.lower(pts, depth)
        sorts = 2 * depth
    else:
        region = jax.ShapeDtypeStruct((n,), jnp.int32)
        lowered = kdtree.label_regions.lower(
            pts, region, jax.random.key(0), 2 ** depth, 16,
            strategy=fn.rsplit("_", 1)[1])
        sorts = 2
    text = lowered.compile().as_text()
    assert not _INDEXED_ACCESS.search(text), _INDEXED_ACCESS.findall(text)
    assert len(re.findall(r"\bsort\(", text)) == sorts


# -- the gather pack against the scatter pack it replaced -------------------

@partial(jax.jit, static_argnames=("num_subsets", "capacity"))
def _oracle_pack(points, subset_ids, num_subsets, capacity):
    """argsort + bincount ranks + row scatter into zeros, dropping ranks
    past ``capacity``."""
    order = jnp.argsort(subset_ids, stable=True)
    sorted_sub, rank, _ = _oracle_segment_rank(subset_ids, order, num_subsets)
    out = jnp.zeros((num_subsets, capacity, points.shape[1]), points.dtype)
    msk = jnp.zeros((num_subsets, capacity), bool)
    out = out.at[sorted_sub, rank].set(points[order], mode="drop")
    msk = msk.at[sorted_sub, rank].set(True, mode="drop")
    return out, msk


def _pack_case(case, d):
    """(points, subset ids, M, capacity) for one named case."""
    rng = np.random.default_rng(abs(hash((case, d))) % 2 ** 32)
    if case == "kd_full":                     # 256 leaves of 16, 16 subsets
        n, m = 4096, 16
    elif case == "random_partition":
        n, m = 1000, 8
    else:                                     # 777 / 2^7: leaves of 6 or 7
        n, m = 777, 8
    pts = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    key = jax.random.key(n + d)
    if case in ("kd_full", "kd_uneven", "roomy"):
        part = kdtree.partition_dataset(pts, key, m, leaf_capacity=16 if
                                        case == "kd_full" else None)
        ids = part.subset_ids
    elif case == "random_partition":
        ids = kdtree.partition_dataset(pts, key, m, strategy="random").subset_ids
    elif case == "overflow":                  # uneven ids, n == M * capacity
        n, m = 1024, 8
        pts = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, m, n), jnp.int32)
        return pts, ids, m, n // m
    else:                                     # "empty": odd subsets get none
        ids = jnp.asarray(rng.integers(0, m // 2, n) * 2, jnp.int32)
    largest = int(np.bincount(np.asarray(ids), minlength=m).max())
    return pts, ids, m, largest + (5 if case == "roomy" else 0)


# (case, whether every subset is full: the reshape branch of the cond)
_PACK_CASES = [("kd_full", True), ("kd_uneven", False),
               ("random_partition", True), ("overflow", False),
               ("empty", False), ("roomy", False)]


@pytest.mark.parametrize("d", [1, 128])
@pytest.mark.parametrize("case,full", _PACK_CASES)
def test_pack_matches_scatter_oracle(case, full, d):
    """One sort and one row gather give the scatter pack's tensor and mask
    in every bit: point order within a subset, zero rows past a subset's
    count, overflow rows dropped from the highest index."""
    pts, ids, m, cap = _pack_case(case, d)
    counts = np.bincount(np.asarray(ids), minlength=m)
    assert (pts.shape[0] == m * cap and (counts == cap).all()) == full
    if case == "overflow":
        assert counts.max() > cap
    if case == "empty":
        assert (counts[1::2] == 0).all()
    packed, mask = kdtree.pack_subsets(pts, ids, m, cap)
    want_p, want_m = _oracle_pack(pts, ids, m, cap)
    assert packed.dtype == want_p.dtype and packed.shape == (m, cap, d)
    np.testing.assert_array_equal(np.asarray(packed).view(np.uint32),
                                  np.asarray(want_p).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want_m))


@pytest.mark.parametrize("capacity", [1024, 1500])
def test_pack_compiles_without_scatter(capacity):
    """The compiled pack holds no scatter and one sort of n keys, with and
    without the full-subset cond (n == M * capacity)."""
    n, m = 2 ** 14, 16
    lowered = kdtree.pack_subsets.lower(
        jax.ShapeDtypeStruct((n, 128), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32), m, capacity)
    text = lowered.compile().as_text()
    assert "scatter(" not in text
    sorts = [ln for ln in text.splitlines() if re.search(r"\bsort\(", ln)]
    assert len(sorts) == 1, sorts
    assert f"s32[{n}]" in sorts[0]
