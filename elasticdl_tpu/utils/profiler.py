"""Native profiler integration (beyond-parity for SURVEY.md §5 tracing).

The reference's only tracing is wall-clock phase accumulators
(common/timing_utils.py, mirrored by common/timing.py here). On TPU the
interesting time is *inside* the XLA program, which host timers cannot
see — so this wraps ``jax.profiler``: a step-window trace capturing
device timelines (HBM transfers, fusions, collective overlap) viewable
in TensorBoard/Perfetto. While the window is open the worker's phases
(``observability/tracing.py``, ``Phases``) also enter
``TraceAnnotation("edl:<name>")``, so the host's side of a task cycle
lies in the same file, on the same clock, as the device's lanes.

Wired via ``--profile_dir`` (+ ``--profile_start_step/--profile_steps``):
the worker starts the trace when the step window opens and stops it when
it closes, so steady-state steps are captured rather than compile time.
"""

from typing import Optional

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import tracing

logger = get_logger("profiler")


class Profiler:
    """Step-windowed jax.profiler trace.

    ``observe_step(step)`` is called once per training step; the trace
    runs for steps [start_step, start_step + num_steps). The window is
    closed by ``stop()`` — the worker calls it on loop exit so a
    training run that ends (or is preempted) before the window fills
    still lands its trace, and a later ``start_trace`` in the process
    doesn't raise "already started".

    ``backend`` defaults to ``jax.profiler`` (imported lazily); tests
    inject a fake with the same ``start_trace``/``stop_trace`` surface
    (and, where they want the phases' annotations, ``TraceAnnotation``).
    """

    def __init__(self, profile_dir: str = "", start_step: int = 5,
                 num_steps: int = 5, backend=None):
        self.profile_dir = profile_dir
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self._backend = backend
        self._active = False
        self._done = False
        self._window_end = None

    @property
    def enabled(self) -> bool:
        return bool(self.profile_dir)

    def _get_backend(self):
        if self._backend is None:
            import jax

            self._backend = jax.profiler
        return self._backend

    def observe_step(self, step: int):
        if not self.enabled or self._done:
            return
        if not self._active and step >= self.start_step:
            backend = self._get_backend()
            backend.start_trace(self.profile_dir)
            tracing.open_trace_window(
                getattr(backend, "TraceAnnotation", None)
            )
            self._active = True
            self._window_end = step + self.num_steps
            logger.info(
                "profiler: tracing steps %d..%d to %s",
                step, self._window_end - 1, self.profile_dir,
            )
        elif self._active and step >= self._window_end:
            self.stop()
        # step < window_end while active (out-of-order final steps — a
        # restored state can rewind the counter): keep tracing; stop()
        # on loop exit closes the window regardless.

    def stop(self):
        if self._active:
            tracing.close_trace_window()
            self._get_backend().stop_trace()
            self._active = False
            self._done = True
            logger.info("profiler: trace written to %s", self.profile_dir)


def from_args(args) -> Optional[Profiler]:
    profile_dir = getattr(args, "profile_dir", "")
    if not profile_dir:
        return None
    return Profiler(
        profile_dir,
        start_step=getattr(args, "profile_start_step", 5),
        num_steps=getattr(args, "profile_steps", 5),
    )
