"""The completion clock: what the DEVICE was running, by program, timed
from inside the process that dispatched it.

:class:`~tpu_parallel.obs.phases.phase` cuts the pump thread's loop into
phases; none of them is the device time of anything (``device_wait`` is
what the host happened to block for, a busy tick's period runs collect
to collect).  The serving engine launches every device program through
one method (``ServingEngine._run``), which hands this clock ``(kind,
shape, t_dispatch, leaf)``: the engine's clock read when the call
returned, and one output array of the program that no later program
donates.  ONE daemon thread an engine takes the entries in dispatch
order; for each it waits until ``leaf`` is ready (the wait releases the
GIL), reads the engine's clock once (``done``) and sets ``start =
max(previous done, t_dispatch)``: the device runs one program at a time,
in order, so a program began when its predecessor ended, or when it was
dispatched if the device had nothing to do - and then ``[previous done,
t_dispatch)`` was device idle time.

From that one read, as :class:`phase` does from its two:

- always: ``record(kind, shape, idle_from, start, done)``, the owner's:
  the exact-sum histogram ``serving_device_seconds{program, shape}`` and
  ``serving_device_idle_seconds_total`` of the record it holds at
  completion, which clips what began before it was opened;
- when ``tracer.enabled``: a span ``device.<kind>`` on the ``device``
  track through ``Tracer.record`` (no second clock read);
- always: a ``jax.profiler.TraceAnnotation`` named ``device.run.<kind>``
  held from when the thread starts to wait until ``done`` (outside a
  profiler session a flag test): what the program BELIEVES the device
  is running, under the ``XLA Ops`` line that says what it ran.  The
  prefix is not ``engine.`` / ``daemon.``: the reduction that names idle
  gaps takes every annotation under those two for a leaf of the pump.

A stamp is taken when this thread gets the GIL back: lateness moves time
from a program to the one before it and creates no idle.  Programs that
are not watched (no output that survives: the row scatters, the
first-token sampler, the small uploads) fall to the watched program
that follows.  The pump never waits for the clock: a full queue drops
the entry, an exception in the wait (a deleted array, a failed program)
leaves it unstamped, both are counted (``lost``) and the thread goes on.
The clock holds a device array only from dispatch to completion, and its
thread lives only while there is something to wait for: it starts at a
dispatch and ends, its queue empty, when the owner says it has drained
(:meth:`DeviceClock.rest`) or is gone (its methods are held weakly).
"""

from __future__ import annotations

import collections
import threading
import weakref
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

ANNOTATION_PREFIX = "device.run."
SPAN_PREFIX = "device."
TRACK = "device"
# queued behind the one waited for: an engine has a tick and its prefills
CAPACITY = 256


def _until_ready(leaf) -> None:
    leaf.block_until_ready()  # host-sync: the completion clock's wait, on its own thread (releases the GIL)


class DeviceClock:
    """One owner's completion clock, given what it writes to as
    :class:`phase` is: ``clock`` (a callable), ``tracer``, and two bound
    methods of the owner, held weakly: ``record(kind, shape, idle_from,
    start, done)`` (the owner looks its current record up at completion,
    so one swapped meanwhile gets what completes after the swap) and
    ``lost(dropped)`` (a full queue dropped an entry, or a wait raised).
    ``wait`` blocks until a leaf is ready (tests script it).
    :meth:`watch` and :meth:`rest` are the owner's pump thread's."""

    def __init__(
        self, clock: Callable[[], float], tracer,
        record: Callable[..., None], lost: Callable[[bool], None],
        wait: Callable[[object], None] = _until_ready,
    ):
        self.thread: Optional[threading.Thread] = None
        self._clock = clock
        self._tracer = tracer
        self._entries: collections.deque = collections.deque()
        self._wake = threading.Event()
        self._wait = wait
        # held where the thread starts and ends: no entry is lost between
        self._handoff = threading.Lock()
        self._resting = False
        self._prev_done: Optional[float] = None
        # the thread sleeps on the event: the owner's end wakes it
        self._record = weakref.WeakMethod(
            record, lambda _, wake=self._wake: wake.set()
        )
        self._lost = weakref.WeakMethod(lost)

    def watch(self, kind: str, shape: str, dispatched: float, leaf) -> None:
        """Right after a dispatch returned; never blocks on the device."""
        if len(self._entries) >= CAPACITY:
            lost = self._lost()
            if lost is not None:
                lost(True)
            return
        with self._handoff:
            self._entries.append((kind, shape, dispatched, leaf))
            self._resting = False
            if self.thread is None:
                self.thread = threading.Thread(
                    target=self._drain, name="device-clock", daemon=True
                )
                self.thread.start()
        self._wake.set()

    def rest(self) -> None:
        """The owner has drained: nothing is in flight, so every queued
        leaf is ready.  The thread stamps what is queued and ends; the
        next :meth:`watch` starts another."""
        if self.thread is not None:
            self._resting = True
            self._wake.set()

    def _drain(self) -> None:
        """The thread's body: nothing here keeps the owner alive."""
        while True:
            self._wake.wait()
            self._wake.clear()
            while self._entries:  # this thread alone pops
                kind, shape, dispatched, leaf = self._entries.popleft()
                failed = False
                with TraceAnnotation(ANNOTATION_PREFIX + kind):
                    try:
                        self._wait(leaf)
                    except Exception:  # a deleted array, a failed program
                        failed = True
                    del leaf
                    done = self._clock()
                record, lost = self._record(), self._lost()
                if record is None or lost is None:
                    break
                try:
                    if failed:
                        # no stamp: the time falls to the program behind
                        lost(False)
                    else:
                        self._stamp(record, kind, shape, dispatched, done)
                except Exception:  # counted, never fatal to the thread
                    lost(False)
                del record, lost
            with self._handoff:
                gone = self._record() is None
                if gone:
                    self._entries.clear()
                if gone or (self._resting and not self._entries):
                    self.thread = None
                    return

    def _stamp(self, record, kind, shape, dispatched, done) -> None:
        """One completed program, written three ways from ``done``."""
        prev = self._prev_done
        if prev is None or dispatched > prev:
            start, idle_from = dispatched, prev
        else:
            start, idle_from = prev, None
        self._prev_done = done
        record(kind, shape, idle_from, start, done)
        if self._tracer.enabled:
            self._tracer.record(
                SPAN_PREFIX + kind, TRACK, start, done, shape=shape
            )
