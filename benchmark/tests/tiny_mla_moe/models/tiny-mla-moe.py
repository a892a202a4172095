"""Zoo-contract module of the second family's test size."""

from benchmark.lib.zoo_mla_moe import contract

globals().update(contract(__file__))
