"""The attention kernels' share of their roofline where window and full
causal layers are mixed (28 query heads over 4 key/value heads of 128):
``lib/counts_smallthinker.py::attention_kernel_step``, the visible
pairs' six matmuls of both kinds of layer and k and v fetched once a
group, whatever grid tiles the kernels visit, over the time of all the
``attn.N`` custom calls."""
from benchmark.lib import counts_smallthinker
from benchmark.metrics._common import attention_seconds_per_step
from benchmark.metrics._mla_moe import roofline_pct


def read(run):
    seconds = attention_seconds_per_step(run)
    if seconds is None:
        return None
    cfg = run["cfg"]
    need = counts_smallthinker.attention_kernel_step(
        cfg, cfg["minibatch"], cfg["seq_len"])
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "swa_attn_kernel_roofline")
