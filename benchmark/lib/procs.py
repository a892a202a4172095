"""Children as real processes, and what their logs say.

The process driver is copied from ``chip_smoke.py`` (``_Child``,
``_free_port``, ``_child_env``) and not imported: later PRs may change
the smoke, and the yardstick must not move with it. Nothing here
imports jax: the parent of a worker must never touch the chip.
"""

import ast
import os
import re
import signal
import socket
import subprocess
import threading
import time

from benchmark.lib import paths

# Lines the program writes (worker.py, main.py) and lines JAX writes
# with JAX_LOG_COMPILES=1. Each pattern's first groups
# are what the harness reads.
PATTERNS = {
    "task": re.compile(
        r"Task (\d+) trained: batches=(\d+) version=(\d+) mean_loss=(\S+)"),
    "runs_on": re.compile(r"Worker \d+ runs on (\{.*\})"),
    "compiling": re.compile(r"Compiling (jit\([^)]*\)|\S+) with global"),
    "task_failed": re.compile(r"Task (\d+) failed"),
    "profiler": re.compile(r"profiler: (tracing steps|trace written)"),
}
# "[2026-09-27 14:07:59,604] [INFO] ..." (program) and
# "WARNING:2026-09-27 14:09:01,513:jax..." (JAX): local wall clock, ms.
_STAMP = re.compile(r"(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})")


def line_time(line: str):
    """The wall-clock second (epoch) a log line carries, or None."""
    m = _STAMP.search(line[:64])
    if not m:
        return None
    return time.mktime(time.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")) \
        + int(m.group(2)) / 1000.0


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        return sock.getsockname()[1]


def child_env(**overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = paths.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update({k: str(v) for k, v in overrides.items()})
    return env


class Child:
    """A child process in its own session, logging to a file, that is
    always stopped (with everything it started) when the run ends."""

    def __init__(self, argv, log_path, env):
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.started = time.time()
        self.proc = subprocess.Popen(
            argv, cwd=paths.ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def signal(self, signum):
        if self.proc.poll() is None:
            os.kill(self.proc.pid, signum)

    def stop(self):
        """SIGKILL the whole session and wait until it has ended."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        if not self._log.closed:
            self._log.close()
        return time.time()


class Event(dict):
    """One recognised log line: kind, groups, t (the line's own stamp
    where it has one, else its arrival), arrival."""

    __getattr__ = dict.__getitem__


class LogTail:
    """Follows a child's log file on a thread; every line that matches
    one of PATTERNS becomes an Event, in order."""

    def __init__(self, path, poll_secs=0.004):
        self._path = path
        self._poll = poll_secs
        self.events = []
        self._cond = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not os.path.exists(self._path) and not self._stop:
            time.sleep(self._poll)
        with open(self._path, errors="replace") as f:
            pending = ""
            while True:
                chunk = f.readline()
                if not chunk:
                    if self._stop:
                        return
                    time.sleep(self._poll)
                    continue
                pending += chunk
                if not pending.endswith("\n"):
                    continue
                line, pending = pending, ""
                arrival = time.time()
                for kind, pattern in PATTERNS.items():
                    m = pattern.search(line)
                    if m:
                        stamp = line_time(line)
                        event = Event(
                            kind=kind, groups=m.groups(), arrival=arrival,
                            t=stamp if stamp is not None else arrival)
                        with self._cond:
                            self.events.append(event)
                            self._cond.notify_all()
                        break

    def of(self, kind):
        with self._cond:
            return [e for e in self.events if e.kind == kind]

    def wait_for(self, predicate, deadline, alive=None):
        """The first event (old or new) for which ``predicate`` holds;
        None at the deadline (epoch seconds) or when ``alive()`` turns
        false with nothing found."""
        seen = 0
        with self._cond:
            while True:
                for event in self.events[seen:]:
                    if predicate(event):
                        return event
                seen = len(self.events)
                if time.time() >= deadline:
                    return None
                if alive is not None and not alive():
                    # One more look: the last lines may have landed.
                    self._cond.wait(0.2)
                    for event in self.events[seen:]:
                        if predicate(event):
                            return event
                    return None
                self._cond.wait(0.05)

    def close(self):
        self._stop = True
        self._thread.join(timeout=2.0)


def parse_runs_on(event) -> dict:
    """The device report of the worker's 'runs on' line (a Python dict
    literal)."""
    return ast.literal_eval(event.groups[0])
