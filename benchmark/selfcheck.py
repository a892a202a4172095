"""Checks of the yardstick itself, by hand-worked values.

    python benchmark/selfcheck.py

1. ``lib/trace.py`` on ``lib/data/small_trace.json.gz``, a cut of a real
   trace of ``gpt2m_steady`` on a TPU v5e (PR 23): the expected numbers
   were worked out from the cut's rows with a separate few lines, and by
   eye for the three programs and the two gaps between them.
2. ``lib/counts.py`` for gpt2-medium against sums worked out by hand
   (written out below).
3. The record container written by ``lib/records.py`` read back by a
   reader written from the format's description.

Needs no jax and no chip. Exits non-zero on the first mismatch.
"""

import math
import os
import struct
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import counts, paths, records, trace  # noqa: E402


def close(got, want, what, rel=1e-9):
    if not math.isclose(got, want, rel_tol=rel, abs_tol=1e-12):
        raise SystemExit(f"MISMATCH {what}: got {got!r}, expected {want!r}")
    print(f"ok {what}: {got!r}")


def check_trace():
    t = trace.Trace.load(os.path.join(
        paths.BENCH, "lib", "data", "small_trace.json.gz"))
    assert t.device_pids == [3], t.device_pids
    programs = t.module_events("multi_step")
    assert len(programs) == 3 and len(t.module_events()) == 12
    # Three executions of jit_multi_step: 1.504586 s, 1.504387 s,
    # 1.504404 s, starting at 0.056613 s, 1.582842 s, 3.101481 s.
    for got, want in zip(programs, (1.504585577156, 1.50438657625,
                                    1.504403951672)):
        close(got[1], want, "program seconds")
    gaps = trace.program_gaps(programs)
    # 1.582841630 - (0.056613424 + 1.504585577) and the like.
    close(gaps[0], 0.021642628672, "first gap between programs", 1e-6)
    close(gaps[1], 0.014252591328, "second gap between programs", 1e-6)
    # The three `while` spans hold every other op of their program, so
    # the union is theirs plus the few spans outside them.
    close(trace.busy_seconds(t), 4.5132118775, "busy seconds", 1e-9)
    # The idle share is taken from the first task program's start to
    # the last's end: 4.605884749 - 0.056613424 s; every operation of the
    # cut falls inside, so the busy seconds are the same and the idle
    # share is the two gaps: 1 - 4.513212 / 4.549271 = 0.79%.
    window = (programs[0][0], programs[-1][0] + programs[-1][1])
    close(window[1] - window[0], 4.549271325078, "traced window")
    close(trace.busy_seconds(t, *window), 4.5132118775,
          "busy seconds inside the window", 1e-9)
    close(trace.busy_seconds(t, window[0], window[0] + 2.0),
          1.504530334578 + (2.0 - (1.58289509325 - 0.056613423672)),
          "busy seconds of the window's first two seconds (the first "
          "program and the part of the second; the microsecond of "
          "operations between programs is within the tolerance)", 1e-6)
    top = trace.top_ops(t, 3)
    assert [n for n, _ in top] == ["attn.2021", "attn.2020", "attn.2016"], top
    close(top[0][1], 447.406328e-6, "longest op's seconds")
    attention = [d for _, d, n in t.lane("XLA Ops") if n.startswith("attn")]
    close(sum(attention), 2684.429062e-6, "six attention kernels' seconds")
    idle = dict(trace.idle_gaps(t))
    # The first gap falls inside `_process_train_task` (the loop waits
    # for the task's batches); no host span of the cut covers the second.
    # On the ops lane: 1,582,895.087 - 1,561,203.440 us and
    # 3,101,534.253 - 3,087,226.503 us.
    close(sum(idle.values()), 0.021691647578 + 0.014307749922,
          "idle seconds between programs", 1e-9)
    assert "$worker.py:670 _process_train_task" in idle, idle
    close(trace.union_seconds([(0, 2), (1, 2), (5, 1)]), 4.0, "union")


def check_counts():
    cfg = paths.load_json(paths.config_path("gpt2-medium"))
    # Per layer 4 x 1024^2 + 2 x 1024 x 4096 = 12,582,912; x 24 =
    # 301,989,888; head 1024 x 50257 = 51,463,168.
    close(counts.matmul_params(cfg), 353453056, "matmul weights")
    # Attention forward per token: 24 layers x 2 matmuls x 2 ops x 1024
    # x (1024 + 1) / 2 visible positions = 50,380,800.
    close(counts.attention_flops_per_token_fwd(cfg, 1024), 50380800.0,
          "attention forward operations per token")
    # 3 x (2 x 353,453,056 + 50,380,800) = 2,271,860,736.
    close(counts.train_flops_per_token(cfg, 1024), 2271860736.0,
          "training operations per token")
    # 51,463,168 + 1,048,576 + 24 x 12,596,224 + 2,048 + 51,513,425.
    close(counts.param_count(cfg), 406336593, "parameters")
    need = counts.attention_kernel_step(cfg, rows=8, seq_len=1024)
    # One matmul: 2 x 8 x 1024 x 512.5 x 1024 = 8,598,323,200; six a
    # layer, 24 layers.
    close(need["flops"], 1238158540800.0, "attention operations a step")
    # Tensor 8 x 1024 x 1024 x 2 = 16,777,216 B; logsumexp 8 x 16 x 1024
    # x 4 = 524,288 B; (12 x 16,777,216 + 2 x 524,288) x 24.
    close(need["bytes"], 4857004032.0, "attention bytes a step")
    # gpt2-large's widths: 36 x (6,553,600 + 13,107,200) + 1280 x 50257
    # = 707,788,800 + 64,328,960.
    large = dict(cfg, n_embd=1280, n_inner=5120, n_head=20, n_layer=36)
    close(counts.matmul_params(large), 772117760,
          "matmul weights at gpt2-large's widths")


def check_records():
    cfg = {"seq_len": 16, "vocab_size": 97}
    traffic = {"records": {"count": 5, "distribution": "zipf",
                           "exponent": 1.1}}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "r.rec")
        made = records.generate(path, traffic, cfg, 2 ** 31 + 11)
        again = records.token_rows(5, 16, 97, traffic["records"],
                                   2 ** 31 + 11)
        with open(path, "rb") as f:
            blob = f.read()
    assert made["bytes"] == len(blob) and blob[:4] == b"EDLR"
    index_offset, count, magic = struct.unpack("<QQ4s", blob[-20:])
    assert (count, magic) == (5, b"EDLI")
    first = struct.unpack("<Q", blob[index_offset:index_offset + 8])[0]
    (length,) = struct.unpack("<I", blob[first:first + 4])
    import msgpack
    import numpy as np

    payload = msgpack.unpackb(blob[first + 4:first + 4 + length], raw=False)
    tokens = np.frombuffer(payload["tokens"]["__nd__"]["data"], np.int32)
    assert tokens.shape == (17,) and (tokens == again[0]).all()
    assert 0 <= tokens.min() and tokens.max() < 97
    print("ok record file: 5 records of 17 tokens read back")


if __name__ == "__main__":
    check_trace()
    check_counts()
    check_records()
    print("selfcheck passed")
