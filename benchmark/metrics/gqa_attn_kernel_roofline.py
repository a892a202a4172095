"""The attention kernels' share of their roofline where a group of
query heads shares a key/value head (32 over 2 as published):
``lib/counts_nemotron_h.py::attention_kernel_step``, k and v fetched
once a group, over the time of the ``attn.N`` custom calls."""
from benchmark.lib import counts_nemotron_h
from benchmark.metrics._common import attention_seconds_per_step
from benchmark.metrics._mla_moe import roofline_pct


def read(run):
    seconds = attention_seconds_per_step(run)
    if seconds is None:
        return None
    cfg = run["cfg"]
    need = counts_nemotron_h.attention_kernel_step(
        cfg, cfg["minibatch"], cfg["seq_len"])
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "gqa_attn_kernel_roofline")
