"""Of the time between consecutive task programs on the device's
``XLA Modules`` lane, the share that the worker's own phases account
for: the union of the ``edl:<leaf>`` spans on the host lanes of the same
trace, on the same clock. What is left is host time no phase names."""
from benchmark.metrics._phases import gap_cover


def read(run):
    cover = gap_cover(run)
    return None if cover is None else 100.0 * cover[1] / cover[0]
