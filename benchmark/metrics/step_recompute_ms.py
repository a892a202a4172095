"""Device ms a step in operations of a forward pass run again under
remat (``rematted_computation``), by the program's own operation table
joined to the trace's ``XLA Ops`` spans (``_scopes.py``)."""
from benchmark.metrics._scopes import phase_ms


def read(run):
    return phase_ms(run, "recompute")
