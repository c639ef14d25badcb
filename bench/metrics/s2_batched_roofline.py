"""Roofline share of the S2 stack kernel (``kernels/batch_resident.py``):
its device time in the trace against the work of every subset solve of the
window's jobs, from the iterations each subset took (the result's
``subset_iters``)."""
import trace
import work


def read(run):
    ops = run.trace.kernel("_lloyd_solve_batched")
    cfg = run.config
    k, d = int(cfg["clusters"]), int(cfg["dim"])
    s = int(cfg["n_points"]) // int(cfg["ipkmeans"]["num_subsets"])
    flops = bytes_ = 0.0
    for iters in run.counters["iters"]:
        for it in iters.reshape(-1):
            f, b = work.subset_solve(s, k, d, int(it))
            flops, bytes_ = flops + f, bytes_ + b
    share = trace.roofline_share(flops, bytes_, run.trace.seconds(ops),
                                 run.peaks)
    return None if share is None else share[0]
