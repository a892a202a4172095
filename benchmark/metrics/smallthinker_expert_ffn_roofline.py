"""The grouped products' share of their roofline with ReLU-gated experts
under a recomputed layer: the least time the chip could take for the
rows the program counted (``lib/counts_smallthinker.py::
expert_ffn_step``: three products an expert, four passes under remat,
what the ``ragged-dot`` calls do) over the time those calls took."""
from benchmark.lib import counts_smallthinker
from benchmark.metrics._mla_moe import (
    grouped_product_seconds_per_step,
    roofline_pct,
    routed_rows_per_step,
)


def read(run):
    seconds = grouped_product_seconds_per_step(run)
    rows = routed_rows_per_step(run)
    if seconds is None or rows is None:
        return None
    need = counts_smallthinker.expert_ffn_step(run["cfg"], rows)
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "smallthinker_expert_ffn_roofline")
