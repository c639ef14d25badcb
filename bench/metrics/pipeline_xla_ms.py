"""Device milliseconds per job of the IPKMeans core program's operations
other than the S2 kernel: the S1 kd-tree sorts and labels, the pack, the
S3 pick (``core/kdtree.py``, ``core/merge.py``), from the trace."""


def read(run):
    ops = [o for o in run.trace.module_ops("jit__ipkmeans_core")
           if o.name != "_lloyd_solve_batched"]
    if not ops:
        return None
    return 1e3 * run.trace.seconds(ops) / run.counters["jobs"]
