"""Which rows the worker's reader fed to the job's first steps.

The master hands tasks out in an order of its own and prints no task's
records, so the harness cannot know from outside what the first task
trained on. Where ``$BENCH_FEED_FILE`` is set (the harness sets it for
the worker alone), the configuration's zoo module wraps the program's
``dataset_fn``: for the first ``$BENCH_FEED_BATCHES`` minibatches it
appends one line, the CRC-32 of every row, as the reader hands them
over; after that it is one comparison a minibatch, on the prefetch
thread. The reference looks the rows up among the records the harness
made from the seed and replays those steps; a row it cannot find, or
finds twice, is a fault of the reader and is counted. numpy only.
"""

import json
import os
import zlib

import numpy as np

FEED_ENV = "BENCH_FEED_FILE"
BATCHES_ENV = "BENCH_FEED_BATCHES"


def row_marks(rows) -> list:
    rows = np.ascontiguousarray(rows, np.int32)
    return [zlib.crc32(row.tobytes()) for row in rows]


def wrap(dataset_fn):
    path = os.environ.get(FEED_ENV)
    if not path:
        return dataset_fn
    left = [int(os.environ.get(BATCHES_ENV, "0"))]

    def logged(records, mode, metadata):
        features, labels = dataset_fn(records, mode, metadata)
        if left[0] > 0:
            left[0] -= 1
            rows = np.concatenate([features, labels[:, -1:]], axis=1)
            with open(path, "a") as f:
                f.write(json.dumps(row_marks(rows)) + "\n")
        return features, labels

    return logged


def read(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def resolve(fed: list, rows) -> tuple:
    """(record indices of every fed minibatch, the count of fed rows
    that are no record of ``rows`` or were fed before)."""
    index = {}
    for i, mark in enumerate(row_marks(rows)):
        index.setdefault(mark, i)
    seen, strays, batches = set(), 0, []
    for marks in fed:
        found = [index.get(mark) for mark in marks]
        strays += sum(i is None or i in seen for i in found)
        seen.update(i for i in found if i is not None)
        batches.append([i for i in found if i is not None])
    return batches, strays
