"""Visible (query, key) pairs a head's attention calls were handed, per
optimizer step (the program's ``attn_visible_pairs`` counter through the
master's page), summed over rows and layers: the witness that the band
is in the timed path. A window layer run as full causal reads S (S + 1)
/ 2 where the band reads W (W + 1) / 2 + (S - W) W. No better
direction: it is what the configuration says, or the path is wrong."""
from benchmark.metrics._smallthinker import visible_pairs_per_step


def read(run):
    return visible_pairs_per_step(run)
