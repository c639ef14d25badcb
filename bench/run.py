"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, whose sizes are in ``bench/configs/<config>.json``, and a
traffic mix, whose parameters are in ``bench/traffic/<traffic>.json``.  The
limits of the comparison with the reference are in
``bench/limits/<cell>.json``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new configuration, mix, cell or metric is
a new file and a new entry; nothing here changes.

Set-up (``setup_s``) makes the data on the device from ``--seed`` and runs
the cell's programs once, so that every shape the window uses is compiled.
The window then runs for ``--seconds``.  Once it has closed, the peak device
memory is read, and the window's outputs are compared with the plain
reference in ``bench/reference.py``.  The last line of standard output is
one JSON object; the numbers compared, each beside its limit, are the last
lines of standard error and the last key of that object.

With ``--trace 1`` the window runs under the profiler, and the line carries
the cell's per-layer metrics, the device's busy seconds and a breakdown.
Without a TPU, or with fewer chips than the cell asks for, or on a TPU kind
missing from ``bench/peaks.json``, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def fail(msg: str) -> "SystemExit":
    return SystemExit(f"bench: {msg}; no result")


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise fail(f"{path.relative_to(ROOT)} is missing")
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration, traffic, limits and the
    metrics it reports, all found by name."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise fail(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")

    def here(m):
        return name in m.get("workloads", [name])

    reported = {m["name"] for m in spec["end_to_end"] if here(m)}
    per_layer = [m for m in spec["per_layer"]
                 if here(m) and m["moves"] in reported]
    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": [m for m in spec["end_to_end"]
                                             if here(m)],
            "per_layer": per_layer}


def require_chips(count: int):
    """The TPU devices this run may use; exits when there are too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise fail(f"no TPU (jax sees {devs[0].platform!r} devices)")
    if len(devs) < count:
        raise fail(f"the cell needs {count} chips, jax sees {len(devs)}")
    return devs[:count]


def load_peaks(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise fail(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where it is
    set, else ``<checkout>/.jax_cache``, a fixed path, since the path is part
    of what a later run has to find again."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def import_program():
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise fail("the program (src/repro) is not in this checkout")
    sys.path.insert(0, str(src))


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """What a run hands to the per-layer readers: the cell, the device's
    peaks, the benchmark's counters, and the reduced trace."""

    def __init__(self, cell: dict, peaks: dict):
        self.config = cell["config"]
        self.peaks = peaks
        self.counters: dict[str, object] = {}
        self.trace = None            # trace.Reduced of the traced window


def start_trace(trace_dir: Path):
    """Profile the window: device and host activity, no Python tracer."""
    import shutil
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def run_jobs(cell, run: Run, points, seed, seconds, traced, trace_dir):
    import jax
    import jobs
    job = jobs.SolveJob(cell["config"], cell["traffic"], points, seed)
    jax.block_until_ready(job(0))                  # compiles: set-up
    setup_end = time.perf_counter()
    if traced:
        start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        outs, window, each = jobs.run_window(job, seconds)
    if traced:
        jax.profiler.stop_trace()
    run.counters["jobs"] = len(outs)
    run.counters["iters"] = [jax.device_get(o["iters"]) for o in outs]
    slow = sorted(range(len(each)), key=each.__getitem__)[-3:][::-1]
    print(f"bench: {len(outs)} jobs, seconds each: min {min(each)!r}, "
          f"median {sorted(each)[len(each) // 2]!r}, slowest "
          + ", ".join(f"job {j} {each[j]!r} (iters max "
                      f"{int(run.counters['iters'][j].max())})"
                      for j in slow), file=sys.stderr, flush=True)
    end_to_end = {cell["traffic"]["metric"]: window / len(outs)}

    def check():
        import numpy as np
        j = int(np.random.default_rng([seed, 7]).integers(len(outs)))
        got = jax.device_get(outs[j])
        seeds = jobs.reference_seeds(job, j)
        want = dict(jobs.reference_outputs(job, seeds, "highest"),
                    seeds=seeds)
        return jobs.compare(job, got, want), len(outs), 0

    return setup_end, end_to_end, check


def judge(numbers: dict, limits: dict):
    """(correct, {name: {value, limit}}) for every number with a limit;
    a number with no limit, or not finite, fails."""
    checks, ok = {}, True
    for name, limit in limits["limits"].items():
        v = numbers.get(name, math.inf)
        passed = v is not None and math.isfinite(v) and v <= limit
        ok = ok and passed
        checks[name] = {"value": v, "limit": limit}
    return ok, checks


def execute(cell: dict, devs, peaks: dict, seed: int, seconds: float,
            traced: bool, t0: float) -> dict:
    """Set-up, window and check of one run -> the result's line."""
    import data
    run = Run(cell, peaks)
    points = data.make_points(seed, cell["config"])
    trace_dir = ROOT / "bench_out" / "trace" / cell["cell"]["name"]
    setup_end, end_to_end, check = run_jobs(cell, run, points, seed,
                                            seconds, traced, trace_dir)
    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    metrics, breakdown = {}, None
    if traced:
        import shutil
        import trace
        run.trace = trace.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        end_to_end["setup_s"] = setup_end - t0
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}
    del points
    numbers, attempted, failed = check()
    correct, checks = judge(numbers, cell["limits"])
    shown = {k: v for k, v in numbers.items() if k not in checks}
    if shown:
        print(f"bench: not compared: {json.dumps(shown)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    cell = load_cell(args.workload)
    import_program()
    devs = require_chips(int(cell["cell"]["chips"]))
    peaks = load_peaks(devs[0].device_kind)
    cache = enable_compile_cache()
    print(f"bench: {args.workload} seed {args.seed} on "
          f"{devs[0].device_kind} x{len(devs)}, compile cache {cache}",
          file=sys.stderr, flush=True)
    line = execute(cell, devs, peaks, args.seed, args.seconds,
                   args.trace == 1, t0)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
