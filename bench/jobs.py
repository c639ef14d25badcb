"""Closed-loop solve jobs: one caller runs whole jobs back to back.

A job is what a user of the library runs to cluster a data set that is
already on the device: the init stage (``resolve_init``), then the solver
entry point (``ipkmeans`` or ``pkmeans``), ending when the centroids and the
full-data SSE are ready.  Every job draws a fresh init key and a fresh
solve key from the run's seed.  The traffic file names the solver; the
configuration gives every size, the init method and the precision the
deployment runs its jobs at (``jax.default_matmul_precision``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

import data
import reference

SOLVERS = ("ipkmeans", "pkmeans")
#: init methods whose seeds the reference draws itself
INITS = ("sample",)


class SolveJob:
    """The timed entry for one cell: ``job(j)`` runs job ``j`` and returns
    its outputs on the device."""

    def __init__(self, config: dict, traffic: dict, points, seed: int):
        from repro.core import IPKMeansConfig, KMeansParams
        self.solver = traffic["solver"]
        if self.solver not in SOLVERS:
            raise ValueError(f"unknown solver {self.solver!r}")
        if config["init"] not in INITS:
            raise ValueError(f"init {config['init']!r} has no reference")
        self.config = config
        self.points = points
        self.seed = seed
        self.k = int(config["clusters"])
        self.init = config["init"]
        self.precision = config["precision"]
        s = config[self.solver]
        params = KMeansParams(max_iters=int(s["max_iters"]),
                              tol=float(s["tol"]), backend=s["engine"],
                              reseed_empty=bool(s["reseed_empty"]))
        if self.solver == "ipkmeans":
            self.cfg = IPKMeansConfig(
                num_clusters=self.k, num_subsets=int(s["num_subsets"]),
                partition=s["partition"], merge=s["merge"], s1=s["s1"],
                label_axis=int(s["label_axis"]), kmeans=params)
        else:
            self.cfg = params

    def keys(self, j: int):
        """(init key, solve key) of job ``j``."""
        return (jax.random.fold_in(data.run_key(self.seed, data.INIT), j),
                jax.random.fold_in(data.run_key(self.seed, data.SOLVE), j))

    def __call__(self, j: int) -> dict:
        with jax.default_matmul_precision(self.precision):
            return self._run(j)

    def _run(self, j: int) -> dict:
        from repro.core import ipkmeans, pkmeans
        from repro.core.init import resolve_init
        ik, sk = self.keys(j)
        with jax.profiler.TraceAnnotation("bench.init"):
            seeds = resolve_init(self.points, ik, self.k, self.init,
                                 backend="kernel")
        with jax.profiler.TraceAnnotation(f"bench.{self.solver}"):
            if self.solver == "ipkmeans":
                r = ipkmeans(self.points, seeds, sk, self.cfg)
                out = {"seeds": seeds, "centroids": r.centroids,
                       "sse": r.sse, "asses": r.asses,
                       "iters": r.subset_iters}
            else:
                r = pkmeans(self.points, seeds, params=self.cfg)
                out = {"seeds": seeds, "centroids": r.centroids,
                       "sse": r.sse, "iters": r.iters}
            jax.block_until_ready(out)
        return out


def run_window(job: SolveJob, seconds: float):
    """Whole jobs back to back until ``seconds`` have passed -> (outputs of
    every job, seconds from the first start to the last end, each job's
    own seconds)."""
    outs, each = [], []
    t0 = t = time.perf_counter()
    while True:
        outs.append(job(len(outs)))
        t1 = time.perf_counter()
        each.append(t1 - t)
        t = t1
        if t1 - t0 >= seconds:
            return outs, t1 - t0, each


# ------------------------------------------------------------- checking ----

def reference_outputs(job: SolveJob, seeds, precision: str) -> dict:
    """The reference's answer to the job, from the job's seeds, with the
    fields of the program's answer (the control runs this at ``high``)."""
    s = job.config[job.solver]
    pts = job.points
    if job.solver == "ipkmeans":
        r = reference.ipkmeans(
            pts, seeds, num_subsets=int(s["num_subsets"]),
            max_iters=int(s["max_iters"]), tol=float(s["tol"]),
            reseed=bool(s["reseed_empty"]), precision=precision,
            label_axis=int(s["label_axis"]))
        out = {"seeds": seeds, "centroids": r["centroids"],
               "asses": r["asses"], "iters": r["iters"],
               "subset_centroids": r["subset_centroids"]}
    else:
        if bool(s["reseed_empty"]):
            raise ValueError("the whole-data reference has no reseed")
        c, it = reference.lloyd(pts, jax.numpy.asarray(seeds),
                                max_iters=int(s["max_iters"]),
                                tol=float(s["tol"]), precision=precision)
        out = {"seeds": seeds, "centroids": c, "iters": it}
    _, mind = reference.assign(pts, out["centroids"], precision)
    out["sse"] = float(np.sum(np.asarray(mind, np.float64)))
    return out


def reference_seeds(job: SolveJob, j: int):
    """The init stage's answer, drawn by the reference itself (``sample``:
    the same uniform k-subset of rows)."""
    rows = reference.sample_rows(job.keys(j)[0], job.points.shape[0], job.k)
    return job.points[rows]


def compare(job: SolveJob, got: dict, want: dict) -> dict:
    """The numbers the limits hold: how far the job's answer ``got`` lies
    from the reference's ``want`` (both from the same seeds).

    ``sse_vs_reference`` is the reported SSE against the exact SSE of the
    reference's own final centroids: it carries the trajectory (a wrong
    step or pick moves the centroids), the S3 pick and the precision of the
    scoring pass in one number.  Per subset, ``subset_asse_gap_median`` and
    ``subsets_apart`` carry S1 and S2.  The rest is printed, not compared.
    """
    pts = job.points
    out = {"seeds_wrong": int(np.sum(np.any(
        np.asarray(got["seeds"]) != np.asarray(want["seeds"]), axis=1)))}
    reported = float(got["sse"])
    out["sse_vs_reference"] = reference.rel(reported, float(want["sse"]))
    out["sse_gap"] = reference.rel(
        reported, reference.exact_sse(pts, got["centroids"]))
    if job.solver == "ipkmeans":
        a = np.asarray(got["asses"], np.float64)
        b = np.asarray(want["asses"], np.float64)
        gaps = np.abs(a - b) / np.maximum(b, 1e-30)
        out["subset_asse_gap_median"] = float(np.median(gaps))
        out["subsets_apart"] = int(np.sum(gaps > 1e-4))
        out["subset_iters_differ"] = int(np.sum(
            np.asarray(got["iters"]) != np.asarray(want["iters"])))
        pick = int(np.argmin(a))
        out["pick_asse_gap"] = reference.rel(b[pick], float(b.min()))
        cg = reference.centroid_gaps(got["centroids"],
                                     want["subset_centroids"][pick])
    else:
        out["iters_differ"] = int(int(got["iters"]) != int(want["iters"]))
        cg = reference.centroid_gaps(got["centroids"], want["centroids"])
    out["centroid_gap_median"] = float(np.median(cg))
    out["centroid_gap_max"] = float(np.max(cg))
    out["centroids_apart"] = int(np.sum(cg > 1e-4))
    return out

