"""The attention kernels' share of their roofline: the least time the
chip could take for a step's attention (the larger of required
operations over peak FLOP/s and required bytes over peak bytes/s,
``lib/counts.py::attention_kernel_step``) over the time the kernels
took. At head size 64 and sequence 1,024 the operations bound it."""
from benchmark.lib import counts, peaks
from benchmark.metrics._common import attention_seconds_per_step


def read(run):
    seconds = attention_seconds_per_step(run)
    if seconds is None:
        return None
    cfg, kind = run["cfg"], run["device"]["device_kind"]
    need = counts.attention_kernel_step(cfg, cfg["minibatch"],
                                        cfg["seq_len"])
    by_flops = need["flops"] / peaks.peak(kind, "bf16_flops_per_s")
    by_bytes = need["bytes"] / peaks.peak(kind, "hbm_bytes_per_s")
    print(f"attn_kernel_roofline: bound by "
          f"{'operations' if by_flops >= by_bytes else 'bytes'} "
          f"({by_flops * 1e3:.3f} ms against {by_bytes * 1e3:.3f} ms)",
          flush=True)
    return 100.0 * max(by_flops, by_bytes) / seconds
