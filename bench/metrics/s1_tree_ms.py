"""Device milliseconds per job of the IPKMeans core program's operations under
the stage scope ``s1_tree``: the S1 kd-tree build (``core/kdtree.py``: the
median splits, level by level); from the trace and the compiled program's
metadata (``scopes.py``)."""
import scopes


def read(run):
    return scopes.stage_ms(run, "s1_tree")
