"""95th percentile of the window's task times (boundary to boundary).
The count is printed beside it on an earlier line."""
from benchmark.metrics._common import percentile, window_tasks


def read(run):
    tasks = window_tasks(run)
    ends = [run["open_t"]] + [t["t"] for t in tasks]
    times = [b - a for a, b in zip(ends, ends[1:])]
    if not times:
        return None
    print(f"task_p95_ms: over {len(times)} tasks", flush=True)
    return 1e3 * percentile(times, 0.95)
