"""Roofline share of the fused Lloyd step kernel (``kernels/fused.py``) in
the PKMeans program of the training cell: its device time in the trace against the work of the
window's iterations (the result's ``iters``)."""
import trace
import work


def read(run):
    ops = run.trace.kernel("_lloyd_step_fused", module="jit_pkmeans")
    cfg = run.config
    n, k, d = int(cfg["n_points"]), int(cfg["clusters"]), int(cfg["dim"])
    steps = sum(int(i) for i in run.counters["iters"])
    f, b = work.lloyd_step(n, k, d)
    share = trace.roofline_share(steps * f, steps * b,
                                 run.trace.seconds(ops), run.peaks)
    return None if share is None else share[0]
