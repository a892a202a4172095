"""The grouped products' share of their roofline with relu^2 experts,
two products an expert: the least time the chip could take for the rows
the program counted (``lib/counts_nemotron_h.py::expert_ffn_step``) over
the time the ``ragged-dot`` kernels took."""
from benchmark.lib import counts_nemotron_h
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
    need = counts_nemotron_h.expert_ffn_step(run["cfg"], rows)
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "relu2_expert_ffn_roofline")
