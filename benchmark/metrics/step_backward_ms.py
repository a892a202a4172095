"""Device ms a step in operations of the backward pass
(``transpose(jvp(...))``, not recomputed), by the program's own
operation table joined to the trace's ``XLA Ops`` spans
(``_scopes.py``)."""
from benchmark.metrics._scopes import phase_ms


def read(run):
    return phase_ms(run, "backward")
