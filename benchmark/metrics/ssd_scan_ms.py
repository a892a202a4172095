"""Device time of the chunked-scan kernels (the ``ssd.N`` custom calls
of ``ops/ssd_scan.py``: forward, the recomputed forward, backward,
every Mamba-2 layer) per optimizer step."""
from benchmark.metrics._nemotron_h import ssd_seconds_per_step


def read(run):
    seconds = ssd_seconds_per_step(run)
    return None if seconds is None else 1e3 * seconds
