"""Device milliseconds per job of the IPKMeans core program's operations under
the stage scope ``s3``: the S3 pick (``core/merge.py``: the subset of least
ASSE); from the trace and the compiled program's metadata
(``scopes.py``)."""
import scopes


def read(run):
    return scopes.stage_ms(run, "s3")
