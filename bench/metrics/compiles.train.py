"""Functions traced and programs compiled inside the window, while whole
quantizer trainings ran back to back: the program's ``trace`` and
``compile`` spans (``repro.obs``), placed on the trace's clock
(``scopes.py``)."""
import scopes


def read(run):
    return scopes.compiles_in_window(run)
