"""The grouped expert products' share of their roofline: the least
time the chip could take for the rows the program counted
(``lib/counts_mla_moe.py::expert_ffn_step``) over the time the products
took."""
from benchmark.lib import counts_mla_moe
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
    need = counts_mla_moe.expert_ffn_step(run["cfg"], rows)
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "expert_ffn_roofline")
