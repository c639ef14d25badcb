"""Program spans, counters and device stage scopes (``repro.obs``)."""
import gc
import importlib.util
import json
import os
import re
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import IPKMeansConfig, KMeansParams, ipkmeans, pkmeans
from repro.core.init import resolve_init
from repro.core.ipkmeans import _ipkmeans_core

ROOT = Path(__file__).resolve().parents[1]
IPKMEANS_SCOPES = ("s1_tree", "s1_label", "pack", "s2", "s3")
PKMEANS_SCOPES = ("lloyd", "sse")
_OP_NAME = re.compile(r'op_name="(jit\([^"]*)"')


def _named(since, *names):
    return [s for s in obs.spans(since_ns=since) if s.name in names]


def test_spans_nest_with_parent_and_root_ids():
    t0 = time.time_ns()
    with obs.span("t.outer"):
        with obs.span("t.inner"):
            with obs.span("t.leaf"):
                pass
        with obs.span("t.sibling"):
            pass
    with obs.span("t.next"):
        pass
    got = {s.name: s for s in _named(t0, "t.outer", "t.inner", "t.leaf",
                                     "t.sibling", "t.next")}
    outer, inner, leaf = got["t.outer"], got["t.inner"], got["t.leaf"]
    assert outer.parent_id is None and outer.root_id == outer.span_id
    assert inner.parent_id == outer.span_id
    assert leaf.parent_id == inner.span_id
    assert got["t.sibling"].parent_id == outer.span_id
    assert {inner.root_id, leaf.root_id, got["t.sibling"].root_id} == {
        outer.span_id}
    assert got["t.next"].parent_id is None
    assert got["t.next"].root_id == got["t.next"].span_id != outer.span_id
    assert outer.start_ns <= inner.start_ns <= leaf.start_ns
    assert leaf.end_ns <= inner.end_ns <= outer.end_ns
    # the ring holds spans in the order they ended
    order = [s.name for s in _named(t0, "t.leaf", "t.inner", "t.outer")]
    assert order == ["t.leaf", "t.inner", "t.outer"]


def test_a_span_closes_when_its_body_raises():
    t0 = time.time_ns()
    with pytest.raises(KeyError):
        with obs.span("t.raises"):
            raise KeyError("x")
    with obs.span("t.after"):
        pass
    (after,) = _named(t0, "t.after")
    assert after.parent_id is None
    assert len(_named(t0, "t.raises")) == 1


def test_the_ring_stays_bounded():
    t0 = time.time_ns()
    for _ in range(obs.RING + 10):
        with obs.span("t.flood"):
            pass
    got = obs.spans()
    assert len(got) == obs.RING
    assert all(s.name == "t.flood" for s in got[-100:])
    assert len(_named(t0, "t.flood")) <= obs.RING


def test_a_fresh_jit_traces_and_compiles_once_and_a_cached_call_neither():
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: jax.lax.sin(jax.lax.cos(v)))   # no nested jit
    t0 = time.time_ns()
    before = obs.snapshot()
    jax.block_until_ready(f(x))
    mid = obs.snapshot()
    jax.block_until_ready(f(x))
    after = obs.snapshot()
    assert mid["traces"] - before["traces"] == 1
    assert mid["compiles"] - before["compiles"] == 1
    assert mid["compile_s"] > before["compile_s"]
    assert (after["traces"], after["compiles"]) == (mid["traces"],
                                                    mid["compiles"])
    assert len(_named(t0, "trace")) == 1
    (compile_,) = _named(t0, "compile")
    assert t0 <= compile_.start_ns <= compile_.end_ns


def test_counters_lose_no_update_across_threads():
    event = "/jax/core/compile/backend_compile_duration"
    threads, each = 2 * (os.cpu_count() or 4), 2000
    before = obs.snapshot()["compiles"]

    def work():
        for _ in range(each):
            obs._on_duration(event, 0.0)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in pool)
    assert obs.snapshot()["compiles"] - before == threads * each


def test_a_collection_is_a_gc_span():
    t0 = time.time_ns()
    before = obs.snapshot()
    gc.collect()
    after = obs.snapshot()
    assert after["gc_collections"] >= before["gc_collections"] + 1
    assert after["gc_s"] > before["gc_s"]
    spans = _named(t0, "gc")
    assert spans and all(s.end_ns >= s.start_ns for s in spans)


def test_a_span_nested_in_the_caller_takes_its_root():
    t0 = time.time_ns()
    with obs.span("t.caller"):
        gc.collect()
    (caller,) = _named(t0, "t.caller")
    inside = [s for s in _named(t0, "gc")
              if caller.start_ns <= s.start_ns <= caller.end_ns]
    assert inside
    assert all(s.parent_id == caller.span_id == s.root_id for s in inside)


def test_entry_calls_record_their_spans():
    pts = jax.random.normal(jax.random.key(0), (512, 4))
    cfg = IPKMeansConfig(num_clusters=4, num_subsets=4,
                         kmeans=KMeansParams(max_iters=5))
    seeds = resolve_init(pts, jax.random.key(1), 4, "sample")
    t0 = time.time_ns()
    seeds = resolve_init(pts, jax.random.key(2), 4, "sample")
    jax.block_until_ready(ipkmeans(pts, seeds, jax.random.key(3), cfg))
    got = {s.name: s for s in _named(t0, "init", "ipkmeans",
                                     "ipkmeans.core", "ipkmeans.sse")}
    assert set(got) == {"init", "ipkmeans", "ipkmeans.core", "ipkmeans.sse"}
    root = got["ipkmeans"]
    assert got["init"].parent_id is None
    for child in ("ipkmeans.core", "ipkmeans.sse"):
        assert got[child].parent_id == root.span_id == got[child].root_id
    assert got["ipkmeans.core"].end_ns <= got["ipkmeans.sse"].start_ns


def test_spans_show_in_a_profile(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.time_ns()
    with obs.span("t.profiled"):
        time.sleep(0.002)
    jax.profiler.stop_trace()
    (mine,) = _named(t0, "t.profiled")
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    events = [e for p in pd.planes for line in p.lines for e in line.events
              if e.name == "repro.t.profiled"]
    assert len(events) == 1
    assert events[0].duration_ns == pytest.approx(
        mine.end_ns - mine.start_ns, abs=200_000)


# -------------------------------------------------- device stage scopes ----

def _small_cell(config_name: str, traffic: str) -> dict:
    path = ROOT / "bench" / "tests" / "cells.py"
    spec = importlib.util.spec_from_file_location("bench_cells", path)
    cells = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cells)
    n, k, _ = cells.SHAPES[traffic]
    config = json.loads(
        (ROOT / "bench" / "configs" / f"{config_name}.json").read_text())
    config.update(n_points=n, clusters=k)
    return config


def _params(s: dict) -> KMeansParams:
    return KMeansParams(max_iters=int(s["max_iters"]), tol=float(s["tol"]),
                        backend=s["engine"],
                        reseed_empty=bool(s["reseed_empty"]))


def _scopes_of(compiled_text: str, scopes) -> list:
    """For each instruction's op_name under a program, the scopes of
    ``scopes`` among its path's components."""
    return [(op, [p for p in op.split("/") if p in scopes])
            for op in _OP_NAME.findall(compiled_text)]


def test_every_ipkmeans_core_instruction_is_in_one_stage_scope():
    config = _small_cell("sift1m-k256", "ipkmeans")
    s = config["ipkmeans"]
    cfg = IPKMeansConfig(
        num_clusters=config["clusters"], num_subsets=16,
        partition=s["partition"], merge=s["merge"], s1=s["s1"],
        label_axis=int(s["label_axis"]), kmeans=_params(s))
    n, d, k = config["n_points"], config["dim"], config["clusters"]
    with jax.default_matmul_precision(config["precision"]):
        text = _ipkmeans_core.lower(
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.random.key(0), cfg).compile().as_text()
    found = _scopes_of(text, IPKMEANS_SCOPES)
    assert len(found) > 500
    assert [op for op, hits in found if len(hits) != 1] == []
    assert {hits[0] for _, hits in found} == set(IPKMEANS_SCOPES)


def test_every_pkmeans_instruction_is_in_one_stage_scope():
    config = _small_cell("sift1m-ivf4096", "train")
    n, d, k = config["n_points"], config["dim"], config["clusters"]
    with jax.default_matmul_precision(config["precision"]):
        text = pkmeans.lower(
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            params=_params(config["pkmeans"])).compile().as_text()
    found = _scopes_of(text, PKMEANS_SCOPES)
    assert len(found) > 50
    assert [op for op, hits in found if len(hits) != 1] == []
    assert {hits[0] for _, hits in found} == set(PKMEANS_SCOPES)
