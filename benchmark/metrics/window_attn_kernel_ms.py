"""Device time per optimizer step of the WINDOW layers' attention
kernels: the ``attn.N`` custom calls whose row in the task program's
operation table has a window layer's module (``_smallthinker.py``). With
the global layers' calls it makes ``attn_kernel_ms``."""
from benchmark.metrics._smallthinker import window_attention_seconds_per_step


def read(run):
    seconds = window_attention_seconds_per_step(run)
    return None if seconds is None else 1e3 * seconds
