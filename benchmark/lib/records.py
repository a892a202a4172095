"""The one traffic generator: a record file from a seed.

A traffic mix's ``records`` block says how many sequences and from what
distribution; the configuration says how long a sequence is and how
large the vocabulary. Records are written in the program's own container
(``data/record_file.py``: header, length-prefixed payloads, offset
index, footer) and payload (``common/tensor_utils.dumps``: msgpack of
``{"tokens": ndarray}``); both are written out here, so that a change to
the program's test helpers cannot change the traffic. numpy only.
"""

import struct

import msgpack
import numpy as np


def token_rows(count: int, seq_len: int, vocab: int, spec: dict, seed: int):
    """(count, seq_len + 1) int32 tokens. ``zipf``: ranks drawn with
    probability proportional to 1/(rank+1)**exponent, and the seed also
    decides which token has which rank; every seed gives the same
    sizes and the same amount of work."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    if spec["distribution"] != "zipf":
        raise ValueError(f"unknown distribution {spec['distribution']!r}")
    weights = 1.0 / np.arange(1, vocab + 1) ** float(spec["exponent"])
    ranks = rng.choice(vocab, size=(count, seq_len + 1),
                       p=weights / weights.sum())
    return rng.permutation(vocab).astype(np.int32)[ranks]


def write_record_file(path: str, rows) -> int:
    """Writes ``rows`` as one record each; returns the bytes written."""
    header = struct.Struct("<4sI")
    length = struct.Struct("<I")
    offsets = []
    with open(path, "wb") as f:
        f.write(header.pack(b"EDLR", 1))
        for row in rows:
            payload = msgpack.packb({"tokens": {"__nd__": {
                "dtype": "int32", "shape": [int(row.shape[0])],
                "data": np.ascontiguousarray(row, np.int32).tobytes(),
            }}}, use_bin_type=True)
            offsets.append(f.tell())
            f.write(length.pack(len(payload)))
            f.write(payload)
        index_offset = f.tell()
        f.write(np.asarray(offsets, "<u8").tobytes())
        f.write(struct.pack("<QQ4s", index_offset, len(offsets), b"EDLI"))
        return f.tell()


def generate(path: str, traffic: dict, cfg: dict, seed: int) -> dict:
    spec = traffic["records"]
    rows = token_rows(spec["count"], cfg["seq_len"], cfg["vocab_size"],
                      spec, seed)
    return {"path": path, "records": int(rows.shape[0]),
            "bytes": write_record_file(path, rows)}
