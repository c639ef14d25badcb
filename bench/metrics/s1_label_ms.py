"""Device milliseconds per job of the IPKMeans core program's operations under
the stage scope ``s1_label``: the S1 labels (``core/kdtree.py``: each
point's subset, from its region); from the trace and the compiled program's
metadata (``scopes.py``)."""
import scopes


def read(run):
    return scopes.stage_ms(run, "s1_label")
