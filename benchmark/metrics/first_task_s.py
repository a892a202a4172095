"""Worker launch to its first 'Task ... trained' line."""


def read(run):
    return run["first_task_t"] - run["worker_launched"]
