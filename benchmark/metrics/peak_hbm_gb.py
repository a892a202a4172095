"""Peak device memory of the worker, ``memory_stats()
['peak_bytes_in_use']`` of the fullest chip, read at the window's end."""


def read(run):
    peaks = [p for r in run.get("probe") or []
             for p in (r.get("peak_bytes_in_use") or []) if p is not None]
    return None if not peaks else max(peaks) / 1e9
