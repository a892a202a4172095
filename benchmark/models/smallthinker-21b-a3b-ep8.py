"""Zoo-contract module of the configuration this file is named after
(``--model_zoo benchmark/models --model_def <config>.model``)."""

from benchmark.lib.zoo_smallthinker import contract

globals().update(contract(__file__))
