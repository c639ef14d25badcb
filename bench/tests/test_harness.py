"""Checks of the harness that need no chip: refusals, files found by name,
and inputs that follow from the seed."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         spec()["workloads"][0]["name"], "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    r = run_cli(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_checkout_without_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_cli(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_unknown_device_kind_is_refused():
    import run
    with pytest.raises(SystemExit):
        run.load_peaks("TPU v99")
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 1.97e14


def test_every_cell_finds_its_files_by_name():
    s = spec()
    import run
    assert s["command"] == ["python3", "bench/run.py"]
    metric_names = {m["name"] for m in s["end_to_end"] + s["per_layer"]}
    assert len(metric_names) == len(s["end_to_end"]) + len(s["per_layer"])
    for w in s["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = run.load_cell(w["name"])
        assert cell["limits"]["limits"], w["name"]
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"], w["name"]
    for m in s["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in s["end_to_end"]}
        for w in m["workloads"]:
            e2e = next(e for e in s["end_to_end"] if e["name"] == m["moves"])
            assert w in e2e.get("workloads", [w])
    for c in s["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) == set(conf["reduced"])


def test_same_seed_same_inputs_and_keys():
    import jax
    import numpy as np
    import data
    import jobs
    config = {"n_points": 256, "dim": 8,
              "data": {"components": 4, "spread": 6.0, "sigma": 1.0}}
    for seed in (0, 2 ** 31 + 11, 2 ** 33 + 3):
        a, b = data.make_points(seed, config), data.make_points(seed, config)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # every job of a run draws its own init and solve keys from the seed
    job = jobs.SolveJob({"clusters": 4, "init": "sample",
                         "precision": "highest",
                         "pkmeans": {"max_iters": 1, "tol": 0.0,
                                     "engine": "jnp", "reseed_empty": False}},
                        {"solver": "pkmeans"}, None, 2 ** 31 + 11)
    keys = [tuple(np.asarray(jax.random.key_data(k)).tobytes()
                  for k in job.keys(j)) for j in range(3)]
    assert len({k for pair in keys for k in pair}) == 6
    assert keys[1] == tuple(np.asarray(jax.random.key_data(k)).tobytes()
                            for k in job.keys(1))
