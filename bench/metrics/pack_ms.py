"""Device milliseconds per job of the IPKMeans core program's operations under
the stage scope ``pack``: the pack (``core/ipkmeans.py``: each subset's
points into its reducer's slots); from the trace and the compiled program's
metadata (``scopes.py``)."""
import scopes


def read(run):
    return scopes.stage_ms(run, "pack")
