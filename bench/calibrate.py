"""Readings that the limits of a cell are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6

For every seed of ``--seeds`` it runs the cell's timed entry at the cell's
size (one job) and prints the numbers of its comparison with the
reference: the readings of sound runs.
For every seed of ``--control-seeds`` it puts the reference, computed at
``high`` precision (three bf16 passes, the step below the configuration's
``highest``), in the program's place and prints the same numbers: the
control's readings.  One JSON object a line, on standard output.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def job_readings(cell, seed: int, control: bool) -> dict:
    import jax
    import data
    import jobs
    points = data.make_points(seed, cell["config"])
    job = jobs.SolveJob(cell["config"], cell["traffic"], points, seed)
    t0 = time.perf_counter()
    got = jax.device_get(job(0))
    secs = time.perf_counter() - t0
    seeds = jobs.reference_seeds(job, 0)
    t1 = time.perf_counter()
    want = dict(jobs.reference_outputs(job, seeds, "highest"), seeds=seeds)
    ref_s = time.perf_counter() - t1
    if control:
        got = jobs.reference_outputs(job, want["seeds"], "high")
        got = jax.device_get({k: v for k, v in got.items()
                              if k != "subset_centroids"})
    numbers = jobs.compare(job, got, want)
    return dict(numbers, job_s=secs, reference_s=ref_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.import_program()
    run.require_chips(int(cell["cell"]["chips"]))
    run.enable_compile_cache()
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for s in filter(None, seeds.split(",")):
            seed = int(s)
            r = job_readings(cell, seed, kind == "control")
            print(json.dumps(dict(r, workload=args.workload, kind=kind,
                                  seed=seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
