"""Device-idle milliseconds per job while a span of the program (``repro.obs``:
an entry call, a stage, a garbage collection or a compile) was open on the
host, while whole solve jobs ran back to back; from the trace and the
program's spans (``scopes.py``)."""
import scopes


def read(run):
    return scopes.idle_in_program_ms(run)
