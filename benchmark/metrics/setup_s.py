"""Seconds from the launch of the benchmark to the opening of the
window: records, process start, compile or cache load, warm-up tasks."""


def read(run):
    return run["open_t"] - run["launched"]
