"""What the readers of the block-diffusion sparse-expert family share.
The expert layer is the second family's, so its counters and its
grouped products are read by ``_mla_moe.py``'s functions; the attention
kernels are ``attn.N`` as everywhere (``_common.py``). New here: the
tokens the model's own noising masked, as they reach the master's page.

Counter (``elasticdl_tpu/worker/worker.py::_log_task_counters``):
``edl_tpu_worker_diffusion_masked_tokens_total`` grows with every
trained task by the tokens the noise masked, summed over the task's
steps: the positions the loss is taken over. It is counted in the
worker's ``task_log`` phase, as the routed rows are, so the growth of
that phase's count between the two scrapes is the number of tasks the
growth belongs to. A program without the counter has no such series:
the reader returns None."""

from benchmark.metrics._common import master_delta
from benchmark.metrics._phases import _delta

MASKED_TOTAL = "edl_tpu_worker_diffusion_masked_tokens_total"


def masked_tokens_per_step(run):
    """Tokens the noise masked per optimizer step, averaged over the
    tasks between the scrapes."""
    tasks = _delta(run, "_count", "task_log")
    if not tasks or tasks <= 0:
        return None
    if not any(k.startswith(MASKED_TOTAL)
               for k in run.get("master_close", {})):
        return None
    masked = master_delta(run, MASKED_TOTAL, "master_close")
    return masked / (tasks * run["steps_per_task"])
