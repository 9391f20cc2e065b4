"""The phase clock of a pump loop: one leaf phase, written three ways
from one pair of clock reads.

The serving engine's tick and the daemon's tick are each cut into
**leaf** phases (``schedule`` / ``prefill`` / ``dispatch`` /
``device_wait`` / ``deliver`` / ``record`` in the engine; ``lock_wait`` /
``journal`` / ``fsync`` / ``housekeeping`` in the daemon — the table is
in docs/11_observability.md).  :class:`phase` marks one of them::

    with phase(sink, tracer, "scheduler", "deliver", clock,
               annotation="engine.tick.deliver"):
        ...

and, from the two reads of ``clock`` at entry and exit:

- calls ``sink(name, seconds)`` — always.  The daemon's sink observes an
  exact-sum registry histogram at once; the engine's adds to the pending
  tick, which observes its histograms at the tick's end if it was busy;
- records a :class:`~tpu_parallel.obs.tracer.Tracer` span named
  ``tick.<name>`` on ``track`` through ``Tracer.record`` (no second
  clock read) — only when ``tracer.enabled``.  The prefix keeps a phase
  apart from the per-request spans (``prefill``, ``decode``...) that
  :func:`~tpu_parallel.obs.stitch.phase_breakdown` sums by name;
- holds a ``jax.profiler.TraceAnnotation`` named ``annotation`` over the
  block — always; outside a profiler session entering one is a flag test.
  Inside one (the benchmark's, or ``utils.profiling.trace``) the phase
  shows on the host plane of the device trace, on the profiler's clock,
  beside the ``XLA Ops`` line: no rebasing between two clocks.

The annotation names are a contract with the trace reduction that names
the device's idle gaps (``benchmarks/lib/xplane.py``): every annotation
under ``engine.tick.`` / ``daemon.tick.`` is a leaf (never nested in
another), is emitted by the pump thread only, and no enclosing span
shares the prefix.  A phase that CONTAINS others (the daemon's ``step``
holds the engine's phases) or runs on another thread (the daemon's
public calls) passes ``annotation=None``.
"""

from __future__ import annotations

from typing import Callable, Optional

from jax.profiler import TraceAnnotation

ENGINE_PREFIX = "engine.tick."
DAEMON_PREFIX = "daemon.tick."
SPAN_PREFIX = "tick."


class phase:
    """Context manager over one leaf phase; ``start`` / ``end`` keep the
    two clock reads, so the caller can reuse them (the engine's tick
    start is its first phase's start) instead of reading again."""

    __slots__ = ("sink", "tracer", "track", "name", "clock", "start",
                 "end", "_annotation")

    def __init__(
        self,
        sink: Callable[[str, float], None],
        tracer,
        track: str,
        name: str,
        clock: Callable[[], float],
        annotation: Optional[str] = None,
    ):
        self.sink = sink
        self.tracer = tracer
        self.track = track
        self.name = name
        self.clock = clock
        self.start = self.end = 0.0
        self._annotation = (
            TraceAnnotation(annotation) if annotation is not None else None
        )

    def __enter__(self) -> "phase":
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start = self.clock()
        return self

    def __exit__(self, *exc) -> None:
        self.end = end = self.clock()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self.sink(self.name, end - self.start)
        if self.tracer.enabled:
            self.tracer.record(
                SPAN_PREFIX + self.name, self.track, self.start, end
            )
