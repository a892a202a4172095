"""Device ms a step in operations of the first forward pass
(``jvp(...)`` and no ``transpose(``: the model's and the loss's), by
the program's own operation table joined to the trace's ``XLA Ops``
spans (``_scopes.py``)."""
from benchmark.metrics._scopes import phase_ms


def read(run):
    return phase_ms(run, "forward")
