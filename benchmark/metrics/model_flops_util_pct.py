"""Model FLOP/s utilisation: the operations the forward and backward
passes require per token (``lib/counts.py``, from shapes, recomputation
excluded) times the window's tokens per second, over the chip's
published bfloat16 peak (``lib/peaks.py``)."""
from benchmark.lib import counts, peaks
from benchmark.metrics._common import tokens_per_second


def read(run):
    rate = tokens_per_second(run)
    if rate is None:
        return None
    cfg, device = run["cfg"], run["device"]
    peak = peaks.peak(device["device_kind"], "bf16_flops_per_s")
    flops = counts.train_flops_per_token(cfg, cfg["seq_len"])
    return 100.0 * flops * rate / (peak * device["device_count"])
