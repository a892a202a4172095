"""The band's kernels' share of their roofline: the window layers'
``attn.N`` calls (``_smallthinker.py``) against
``lib/counts_smallthinker.py::window_kernel_step``, the band's visible
pairs' six matmuls and those layers' bytes alone: what the static plan
with dead steps on both sides of the band makes of its tiles."""
from benchmark.lib import counts_smallthinker
from benchmark.metrics._mla_moe import roofline_pct
from benchmark.metrics._smallthinker import window_attention_seconds_per_step


def read(run):
    seconds = window_attention_seconds_per_step(run)
    if seconds is None:
        return None
    cfg = run["cfg"]
    need = counts_smallthinker.window_kernel_step(
        cfg, cfg["minibatch"], cfg["seq_len"])
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "window_attn_kernel_roofline")
