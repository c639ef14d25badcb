"""A run whose timed path is broken underneath must come out not correct.

Each test skips the harness's look for a chip, plants one fault in the
program (a Lloyd update that returns its state unchanged, half of every
batch left out of the means, the reported SSE altered where it is
produced, the S3 pick of another subset than the least ASSE's) and drives
the rest of a run at a small size: the comparison with the reference, under the cells' own limits, has
to fail.  A clean run of the same small cell passes.  One chip has no
exchange between chips, so that fault has no test.
"""
import importlib
import time

import jax
import jax.numpy as jnp
import pytest

import cells
import run

JOB_CELLS = [("sift1m-k256.ipkmeans", "ipkmeans"),
             ("sift1m-ivf4096.train", "train")]
SEED = 2 ** 31 + 77


def drive(name, traffic, seconds=1.0):
    jax.clear_caches()
    cell = cells.small(name, traffic)
    line = run.execute(cell, jax.devices(), cells.CPU_PEAKS, SEED, seconds,
                       False, time.perf_counter())
    jax.clear_caches()
    return line


@pytest.mark.parametrize("name,traffic", JOB_CELLS)
def test_clean_small_run_is_correct(name, traffic):
    line = drive(name, traffic)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name,traffic", JOB_CELLS)
def test_state_left_unchanged_fails(name, traffic, monkeypatch):
    from repro.kernels import ref
    monkeypatch.setattr(ref, "divide_or_keep",
                        lambda sums, counts, old: old)
    assert not drive(name, traffic)["correct"]


@pytest.mark.parametrize("name,traffic", JOB_CELLS)
def test_half_the_batch_left_out_fails(name, traffic, monkeypatch):
    ipkmeans = importlib.import_module("repro.core.ipkmeans")
    pkmeans = importlib.import_module("repro.core.pkmeans")
    local_stats = pkmeans._local_stats
    batched = ipkmeans.kmeans_batched

    def half_stats(points, centroids, mask, backend):
        keep = jnp.arange(points.shape[0]) < points.shape[0] // 2
        return local_stats(points, centroids, keep, backend)

    def half_batched(subsets, masks, init, params):
        keep = jnp.arange(masks.shape[1]) < masks.shape[1] // 2
        return batched(subsets, masks & keep[None, :], init, params)

    monkeypatch.setattr(pkmeans, "_local_stats", half_stats)
    monkeypatch.setattr(ipkmeans, "kmeans_batched", half_batched)
    assert not drive(name, traffic)["correct"]


@pytest.mark.parametrize("name,traffic", JOB_CELLS)
def test_answer_altered_where_produced_fails(name, traffic, monkeypatch):
    from repro.core import metrics
    sse = metrics.sse

    def altered(points, centroids, mask=None):
        return sse(points, centroids, mask) * 1.001

    monkeypatch.setattr(metrics, "sse", altered)
    assert not drive(name, traffic)["correct"]


def test_wrong_subset_picked_fails(monkeypatch):
    from repro.core import merge

    def most_asse(centroid_sets, asses):
        return centroid_sets[jnp.argmax(asses)]

    monkeypatch.setattr(merge, "min_asse_merge", most_asse)
    assert not drive(*JOB_CELLS[0])["correct"]
