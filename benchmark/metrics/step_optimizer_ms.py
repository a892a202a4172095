"""Device ms a step in operations of the optimizer (the named scope
``core/step.py`` applies the gradients under), by the program's own
operation table joined to the trace's ``XLA Ops`` spans
(``_scopes.py``)."""
from benchmark.metrics._scopes import phase_ms


def read(run):
    return phase_ms(run, "optimizer")
