"""Token-choices that fell on experts this chip holds, per optimizer
step, summed over the expert layers and the MTP block (the program's
``moe_rows`` counter through the master's page). The drop-free witness:
the grouped products run over exactly these rows."""
from benchmark.metrics._mla_moe import routed_rows_per_step


def read(run):
    return routed_rows_per_step(run)
