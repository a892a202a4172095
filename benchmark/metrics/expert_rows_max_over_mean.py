"""Imbalance over the held experts: the fullest expert's rows (summed
over the expert layers, the largest step of the last task before the
window closed) over the mean rows an expert got a step."""
from benchmark.metrics._mla_moe import (
    fullest_expert_rows,
    routed_rows_per_step,
)


def read(run):
    rows, fullest = routed_rows_per_step(run), fullest_expert_rows(run)
    if not rows or fullest is None:
        return None
    return fullest / (rows / run["cfg"]["n_routed_experts"])
