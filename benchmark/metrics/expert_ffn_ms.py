"""Device time of the grouped expert products (gate+up and down,
forward and backward, every expert layer) per optimizer step."""
from benchmark.metrics._mla_moe import grouped_product_seconds_per_step


def read(run):
    seconds = grouped_product_seconds_per_step(run)
    return None if seconds is None else 1e3 * seconds
