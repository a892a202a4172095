"""The attention kernels' share of their roofline with latent
attention's two head sizes (q/k 192, v 128 as published):
``lib/counts_mla_moe.py::attention_kernel_step`` over the time of the
``attn.N`` custom calls."""
from benchmark.lib import counts_mla_moe
from benchmark.metrics._common import attention_seconds_per_step
from benchmark.metrics._mla_moe import roofline_pct


def read(run):
    seconds = attention_seconds_per_step(run)
    if seconds is None:
        return None
    cfg = run["cfg"]
    need = counts_mla_moe.attention_kernel_step(
        cfg, cfg["minibatch"], cfg["seq_len"])
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "mla_attn_kernel_roofline")
