"""The trace reduction and the roofline arithmetic, on a trace recorded on
one TPU v5e: a `--trace 1` run of `sift1m-ivf4096.train` with a 3-second
window, two jobs of 25 fused Lloyd steps each (`data/train.xplane.pb`)."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import trace
import work

BENCH = Path(__file__).resolve().parents[1]
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_file(BENCH / "tests" / "data" / "train.xplane.pb")


def reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_busy_and_idle_share(reduced):
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx(5.724043494, abs=1e-9)
    assert reduced.busy_s == pytest.approx(5.708253163, abs=1e-9)
    assert 0.0 < reduced.busy_s <= reduced.window_s
    assert reduced.idle_share == pytest.approx(0.0027586, abs=1e-6)


def test_kernel_time_by_name_and_program(reduced):
    steps = reduced.kernel("_lloyd_step_fused", module="jit_pkmeans")
    assert len(steps) == 2 * 25
    assert reduced.seconds(steps) == pytest.approx(5.633242374, abs=1e-9)
    assert reduced.kernel("_lloyd_step_fused", module="jit_run") == []
    assert reduced.kernel("_lloyd_solve_batched") == []
    top = reduced.breakdown()["device_ops"]
    assert top[0][0] == "jit_pkmeans/_lloyd_step_fused"
    assert len(top) <= 10


def test_idle_gaps_are_named_by_host_spans(reduced):
    gaps = reduced.breakdown()["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in gaps) <= reduced.window_s - reduced.busy_s + 1e-9
    assert {g[0] for g in gaps} <= {"bench.init", "bench.pkmeans",
                                    "between benchmark spans"}


def test_roofline_arithmetic():
    # one second of work at exactly the bf16 peak is 100 %, compute-bound
    assert trace.roofline_share(PEAKS["bf16_flops_per_s"], 0.0, 1.0,
                                PEAKS) == (pytest.approx(100.0), "compute")
    # bytes at the HBM bandwidth over twice the time: 50 %, memory-bound
    assert trace.roofline_share(0.0, PEAKS["hbm_bytes_per_s"], 2.0,
                                PEAKS) == (pytest.approx(50.0), "memory")
    assert trace.roofline_share(1.0, 1.0, 0.0, PEAKS) is None
    f, b = work.lloyd_step(2 ** 20, 4096, 128)
    assert f == 2.0 * 2 ** 20 * 4096 * 128 + 2 ** 20 * 128
    assert b == 4 * (2 ** 20 * 128 + 2 * 4096 * 128)


def test_fused_roofline_reader_on_the_recorded_window(reduced):
    class Run:
        config = {"n_points": 2 ** 20, "clusters": 4096, "dim": 128}
        peaks = PEAKS
        trace = reduced
        counters = {"iters": [np.int32(25), np.int32(25)], "jobs": 2}

    share = reader("lloyd_fused_roofline.train")(Run)
    f, _ = work.lloyd_step(2 ** 20, 4096, 128)
    want = 100.0 * 50 * f / PEAKS["bf16_flops_per_s"] / 5.633242374
    assert share == pytest.approx(want, rel=1e-9)
    assert 4.9 < share < 5.0
    assert reader("idle_share.train")(Run) == pytest.approx(0.27586, abs=1e-4)
