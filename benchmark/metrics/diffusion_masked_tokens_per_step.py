"""Tokens the model's own noising masked, per optimizer step (the
program's ``diffusion_masked_tokens`` counter through the master's
page): the positions the loss is taken over, about half of ``minibatch
x seq_len``. The witness that the noise runs in the timed path."""
from benchmark.metrics._sdar_moe import masked_tokens_per_step


def read(run):
    return masked_tokens_per_step(run)
