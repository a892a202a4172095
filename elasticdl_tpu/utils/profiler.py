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

The trace names a device operation and nothing else (``fusion.6760``),
so the window also leaves what each name belongs to: the worker tells
the profiler which training program it runs (``note_program``), and
``stop()`` writes ``<profile_dir>/programs/<module>.ops.json``
(``utils/hlo_ops.py``: forward, recomputed forward, backward, optimizer
and the Flax module of every instruction of the compiled program),
``<module>`` being the program's name on the trace's ``XLA Modules``
lane without its id (``jit_multi_step``, ``jit_train_step``: the name
``core/step.py::jit_task`` gives, by which the benchmark finds the
programs too). ``tools/step_breakdown.py <profile_dir>`` joins the two.

Wired via ``--profile_dir`` (+ ``--profile_start_step/--profile_steps``):
the worker starts the trace when the step window opens and stops it when
it closes, so steady-state steps are captured rather than compile time.
"""

import json
import os
import time
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
        self._program = None  # (jitted callable, its arguments' shapes)

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

    def note_program(self, program, *args):
        """The training program the worker runs, with the arguments of
        this call: kept as shapes, the first time, for ``stop()``'s
        operation table. A step that is no one compiled program (the
        host tier's runner pulls rows around its own) has no table."""
        if (self._program is None and not self._done
                and hasattr(program, "lower")):
            import jax

            def shape_of(x):
                # Committed to its devices where the array is.
                committed = getattr(x, "committed", False)
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if committed else None,
                    weak_type=getattr(x, "weak_type", False),
                )

            self._program = (program, jax.tree.map(shape_of, args))

    def _write_operation_table(self):
        """``<profile_dir>/programs/<module>.ops.json`` for the program
        told to ``note_program``: its compiled text, lowered again at
        the same shapes (the executable that runs: JAX's caches answer),
        parsed by ``utils/hlo_ops.py``. The second executable is dropped
        at once."""
        from elasticdl_tpu.utils import hlo_ops

        program, shapes = self._program
        started = time.monotonic()
        text = program.lower(*shapes).compile().as_text()
        table = hlo_ops.table_of(text)
        directory = os.path.join(self.profile_dir, "programs")
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, table["module"] + ".ops.json")
        with open(path, "w") as f:
            json.dump(table, f)
        logger.info(
            "profiler: operation table of %s (%d operations) written to "
            "%s in %.2fs", table["module"], len(table["ops"]), path,
            time.monotonic() - started,
        )

    def stop(self):
        if self._active:
            tracing.close_trace_window()
            self._get_backend().stop_trace()
            self._active = False
            self._done = True
            if self._program is not None:
                try:
                    self._write_operation_table()
                except Exception as exc:  # the trace itself is written
                    logger.warning(
                        "profiler: no operation table: %s: %s",
                        type(exc).__name__, exc,
                    )
                self._program = None
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
