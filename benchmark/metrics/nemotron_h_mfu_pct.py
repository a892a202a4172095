"""Model FLOP/s utilisation of the hybrid state-space / attention /
expert family: the operations a step requires of what this chip holds
(``lib/counts_nemotron_h.py``, recomputation excluded, the scan as the
recurrence, the routed experts' for the rows the program counted) times
the window's steps per second, over the chip's published bfloat16
peak."""
from benchmark.lib import counts_nemotron_h, peaks
from benchmark.metrics._common import tokens_per_second
from benchmark.metrics._mla_moe import routed_rows_per_step


def read(run):
    rate, rows = tokens_per_second(run), routed_rows_per_step(run)
    if rate is None or rows is None:
        return None
    cfg, device = run["cfg"], run["device"]
    step_tokens = cfg["minibatch"] * cfg["seq_len"]
    flops = counts_nemotron_h.train_flops_per_step(
        cfg, cfg["minibatch"], cfg["seq_len"], rows)
    peak = peaks.peak(device["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops * (rate / step_tokens) / (
        peak * device["device_count"])
