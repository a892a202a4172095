"""Programs traced and compiled inside the window: JAX's own
'Compiling ...' lines (JAX_LOG_COMPILES=1) stamped inside it, or new
entries in the compile cache over it, whichever is more. Must be 0."""


def read(run):
    lines = sum(1 for t, _ in run["events"]["compiling"]
                if run["open_t"] < t <= run["close_t"])
    entries = run.get("cache_entries_close", 0) - run.get(
        "cache_entries_open", 0)
    return max(lines, entries, 0)
