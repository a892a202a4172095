"""Of the device time of the task programs' operations, the share that
lies in operations of one phase (forward, recomputed forward, backward,
optimizer): what fusions over several phases (``mixed``) and operations
of no phase (``other``) leave. The device side's ``gap_attributed_pct``."""
from benchmark.metrics._scopes import scoped_pct


def read(run):
    return scoped_pct(run)
