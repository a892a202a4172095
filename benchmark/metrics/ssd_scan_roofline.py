"""The chunked-scan kernels' share of their roofline: the least time
the chip could take for what the ``ssd.N`` calls do
(``lib/counts_nemotron_h.py::ssd_kernel_step``, the recomputed forward
counted because it is among the calls) over the time they took."""
from benchmark.lib import counts_nemotron_h
from benchmark.metrics._mla_moe import roofline_pct
from benchmark.metrics._nemotron_h import ssd_seconds_per_step


def read(run):
    seconds = ssd_seconds_per_step(run)
    if seconds is None:
        return None
    cfg = run["cfg"]
    need = counts_nemotron_h.ssd_kernel_step(
        cfg, cfg["minibatch"], cfg["seq_len"])
    return roofline_pct(need, seconds, run["device"]["device_kind"],
                        "ssd_scan_roofline")
