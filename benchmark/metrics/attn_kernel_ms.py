"""Device time of the attention kernels (forward, dq, dk/dv custom
calls of ``ops/flash_attention.py``) per optimizer step."""
from benchmark.metrics._common import attention_seconds_per_step


def read(run):
    seconds = attention_seconds_per_step(run)
    return None if seconds is None else 1e3 * seconds
