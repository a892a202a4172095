"""Model FLOP/s utilisation of the window-and-global sparse-expert
family: the operations a step requires of what this chip holds
(``lib/counts_smallthinker.py``: recomputation excluded, the attention's
visible pairs only, band and causal layer by layer, the routed experts'
for the rows the program counted) times the window's steps per second,
over the chip's published bfloat16 peak: the share of the whole step."""
from benchmark.lib import counts_smallthinker, peaks
from benchmark.metrics._common import tokens_per_second
from benchmark.metrics._mla_moe import routed_rows_per_step


def read(run):
    rate, rows = tokens_per_second(run), routed_rows_per_step(run)
    if rate is None or rows is None:
        return None
    cfg, device = run["cfg"], run["device"]
    step_tokens = cfg["minibatch"] * cfg["seq_len"]
    flops = counts_smallthinker.train_flops_per_step(
        cfg, cfg["minibatch"], cfg["seq_len"], rows)
    peak = peaks.peak(device["device_kind"], "bf16_flops_per_s")
    return 100.0 * flops * (rate / step_tokens) / (
        peak * device["device_count"])
