"""The completion clock (``obs/device_clock.py``): the clock alone over a
scripted stream of (dispatch, ready) times, then on toy engines of every
tick kind: what it calls the programs, that its seconds tile the run, that
a record swap starts from zero, that its thread ends when its engine drains
or goes and leaves no device array behind, and the five benchmark readers
over whatever ``summary()`` may hold."""

import gc
import importlib.util
import json
import math
import os
import sys
import threading
import time
import types
import weakref

import jax
import jax.numpy as jnp
import pytest

from tpu_parallel.models import GPTLM, tiny_test
from tpu_parallel.models.gpt import tiny_block_diffusion
from tpu_parallel.obs import NULL_TRACER, DeviceClock, Tracer
from tpu_parallel.obs import device_clock as device_clock_mod
from tpu_parallel.obs.device_clock import CAPACITY
from tpu_parallel.serving import (
    FINISHED,
    Request,
    SchedulerConfig,
    ServingEngine,
    ServingMetrics,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the clock alone -----------------------------------------------------------


class _Clock:
    """Time that moves only when the script says so."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Leaf:
    """A program's output that is ready when the script says so."""

    def __init__(self, fails=False):
        self.ready = threading.Event()
        self.fails = fails


def _scripted_wait(leaf):
    assert leaf.ready.wait(10), "the script never completed this program"
    if leaf.fails:
        raise RuntimeError("Array has been deleted")


class _Owner:
    """What an engine is to its clock: a clock, a tracer, the record that
    is current, and the two methods the clock holds weakly."""

    def __init__(self, tracer=True):
        self.clock = _Clock()
        self.metrics = ServingMetrics()
        self.tracer = Tracer(clock=self.clock) if tracer else NULL_TRACER

    def ran(self, *interval):
        self.metrics.record_device(*interval)

    def lost(self, dropped):
        if dropped:
            self.metrics.record_device_dropped()
        else:
            self.metrics.record_device_fault()

    def device_clock(self, wait):
        return DeviceClock(
            self.clock, self.tracer, self.ran, self.lost, wait=wait
        )


def _settle(check, what="the clock's thread to catch up"):
    deadline = time.monotonic() + 10
    while not check():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.001)


def _play(owner, clock, script, kinds=None):
    """Dispatch every program of ``script`` = [(t_dispatch, t_ready)] at
    its time, then complete them in order at theirs; returns the device
    spans the tracer got, as (name, start, end, shape)."""
    leaves = []
    for i, (dispatched, _) in enumerate(script):
        kind = kinds[i] if kinds else "tick"
        leaf = _Leaf()
        leaves.append(leaf)
        clock.watch(kind, f"shape{i}", dispatched, leaf)
    for i, (leaf, (_, ready)) in enumerate(zip(leaves, script)):
        owner.clock.t = ready
        leaf.ready.set()
        _settle(lambda: owner.metrics.summary()["device_programs"] == i + 1)
    return [
        (s.name, s.start, s.end, s.attrs["shape"])
        for s in owner.tracer.spans
    ]


def _idle_seconds(metrics):
    return metrics._device_idle.value


STREAMS = {
    # the device is behind: every program starts where the last ended
    "backlogged": (
        [(1.0, 2.0), (1.1, 3.5), (1.2, 4.0)],
        [(1.0, 2.0), (2.0, 3.5), (3.5, 4.0)], 0.0,
    ),
    # the second dispatch follows the first completion: 0.5 s idle
    "a_gap": (
        [(1.0, 2.0), (2.5, 3.0)],
        [(1.0, 2.0), (2.5, 3.0)], 0.5,
    ),
    # a gap, then a backlog behind it
    "a_gap_then_behind": (
        [(0.0, 1.0), (4.0, 5.0), (4.1, 7.0), (4.2, 7.5)],
        [(0.0, 1.0), (4.0, 5.0), (5.0, 7.0), (7.0, 7.5)], 3.0,
    ),
    # dispatched exactly at the completion: neither idle nor overlap
    "back_to_back": (
        [(1.0, 2.0), (2.0, 2.25)],
        [(1.0, 2.0), (2.0, 2.25)], 0.0,
    ),
}


@pytest.mark.parametrize("name", sorted(STREAMS))
def test_a_scripted_stream_is_stamped_in_order(name):
    """``start = max(previous done, dispatch)``, idle only where a dispatch
    follows the completion before it, order kept, and the histogram, the
    idle counter and the spans all come from the same reads."""
    script, want, idle = STREAMS[name]
    owner = _Owner()
    clock = owner.device_clock(_scripted_wait)
    spans = _play(owner, clock, script)
    assert [(s, e) for _, s, e, _ in spans] == want
    assert [n for n, *_ in spans] == ["device.tick"] * len(script)
    assert [shape for *_, shape in spans] == [
        f"shape{i}" for i in range(len(script))
    ]
    assert _idle_seconds(owner.metrics) == pytest.approx(idle)
    s = owner.metrics.summary()
    watched = sum(e - b for b, e in want)
    assert s["device_tick_ms_mean"] == pytest.approx(
        1e3 * watched / len(script), abs=1e-3
    )
    elapsed = want[-1][1] - want[0][0]
    assert watched + idle == pytest.approx(elapsed)
    assert s["device_idle_share"] == pytest.approx(idle / elapsed, abs=1e-5)
    hists = [
        h for h in owner.metrics.registry.snapshot()["histograms"]
        if h["name"] == "serving_device_seconds"
    ]
    assert sorted(h["labels"]["shape"] for h in hists) == [
        f"shape{i}" for i in range(len(script))
    ]
    assert {h["labels"]["program"] for h in hists} == {"tick"}


def test_the_summary_splits_the_programs_by_kind():
    owner = _Owner()
    clock = owner.device_clock(_scripted_wait)
    owner.metrics.record_prefill_call(real=500, padded=12)
    _play(
        owner, clock,
        [(0.0, 1.0), (0.1, 1.5), (0.2, 2.5), (0.3, 2.75), (0.4, 4.0)],
        kinds=["tick", "prefill", "tick", "extend", "tick_chunk"],
    )
    s = owner.metrics.summary()
    assert s["device_tick_ms_mean"] == pytest.approx(1000.0)
    assert s["device_prefill_ms_mean"] == pytest.approx(500.0)
    assert s["device_tick_chunk_ms_mean"] == pytest.approx(1250.0)
    # prefill + extend seconds over the elapsed time and over real tokens
    assert s["device_prefill_share"] == pytest.approx(0.75 / 4.0)
    assert s["device_prefill_ms_per_ktok"] == pytest.approx(1500.0)
    assert s["device_programs"] == 5 and s["device_clock_dropped"] == 0
    assert s["device_idle_share"] == 0.0
    # the split by compiled shape: "<program> <shape>" -> [calls, seconds]
    assert s["device_by_shape"] == {
        "tick shape0": [1, 1.0], "prefill shape1": [1, 0.5],
        "tick shape2": [1, 1.0], "extend shape3": [1, 0.25],
        "tick_chunk shape4": [1, 1.25],
    }
    json.dumps(s["device_by_shape"])


def test_a_record_opened_mid_flight_clips_what_began_before_it():
    """``reset_metrics()`` at a window's opening: the record in force at a
    COMPLETION gets the program, from the record's opening on."""
    owner = _Owner()
    old = owner.metrics
    clock = owner.device_clock(_scripted_wait)
    first, second, third = _Leaf(), _Leaf(), _Leaf()
    clock.watch("tick", "8", 1.0, first)
    clock.watch("prefill", "16x1", 1.1, second)
    owner.clock.t = 2.0
    first.ready.set()
    _settle(lambda: old.summary()["device_programs"] == 1)
    # the window opens at 2.5, inside the prefill (2.0 to 3.0)
    fresh = ServingMetrics()
    fresh.open_device_window(2.5)
    owner.metrics = fresh
    owner.clock.t = 3.0
    second.ready.set()
    _settle(lambda: fresh.summary()["device_programs"] == 1)
    # an idle stretch that straddles an opening is clipped too
    newest = ServingMetrics()
    newest.open_device_window(3.5)
    owner.metrics = newest
    clock.watch("tick", "8", 4.0, third)
    owner.clock.t = 5.0
    third.ready.set()
    _settle(lambda: newest.summary()["device_programs"] == 1)
    assert old.summary()["device_tick_ms_mean"] == pytest.approx(1000.0)
    assert old.summary()["device_prefill_ms_mean"] is None
    s = fresh.summary()
    assert s["device_prefill_ms_mean"] == pytest.approx(500.0)
    assert s["device_prefill_share"] == pytest.approx(1.0)
    assert s["device_idle_share"] == 0.0
    s = newest.summary()
    assert s["device_tick_ms_mean"] == pytest.approx(1000.0)
    assert _idle_seconds(newest) == pytest.approx(0.5)  # 3.5 to 4.0 of 3.0 to 4.0
    assert s["device_idle_share"] == pytest.approx(0.5 / 1.5, abs=1e-5)


def test_a_full_queue_drops_and_counts_and_the_pump_never_waits():
    owner = _Owner()
    clock = owner.device_clock(_scripted_wait)
    leaves = [_Leaf() for _ in range(CAPACITY + 3)]
    clock.watch("tick", "8", 0.0, leaves[0])
    _settle(lambda: not clock._entries)  # the thread is waiting for it
    t0 = time.monotonic()
    for i, leaf in enumerate(leaves[1:]):
        clock.watch("tick", "8", 1.0 + i, leaf)
    assert time.monotonic() - t0 < 1.0
    # the first is being waited for, CAPACITY are queued, two were dropped
    assert len(clock._entries) == CAPACITY
    assert owner.metrics.summary()["device_clock_dropped"] == 2
    for t, leaf in enumerate(leaves):
        owner.clock.t = 10.0 + t
        leaf.ready.set()
    _settle(
        lambda: owner.metrics.summary()["device_programs"] == CAPACITY + 1
    )
    assert clock.thread.is_alive()


def test_a_wait_that_raises_is_counted_and_the_thread_lives():
    """A deleted array, a failed program: no completion is stamped, the
    time falls to the program that follows, nothing reaches the pump."""
    owner = _Owner()
    clock = owner.device_clock(_scripted_wait)
    good, bad, after = _Leaf(), _Leaf(fails=True), _Leaf()
    clock.watch("tick", "8", 0.0, good)
    clock.watch("prefill", "16x1", 0.1, bad)
    clock.watch("tick", "8", 0.2, after)
    for t, leaf in ((1.0, good), (2.0, bad), (3.0, after)):
        owner.clock.t = t
        leaf.ready.set()
        time.sleep(0.01)
    _settle(lambda: owner.metrics.summary()["device_programs"] == 2)
    assert owner.metrics._device_faults.value == 1
    s = owner.metrics.summary()
    assert s["device_prefill_ms_mean"] is None
    assert s["device_tick_ms_mean"] == pytest.approx(1500.0)  # 1.0 and 2.0
    assert clock.thread.is_alive()


def test_the_thread_starts_with_the_first_program_and_ends_with_its_owner():
    owner = _Owner(tracer=False)
    clock = owner.device_clock(_scripted_wait)
    assert clock.thread is None
    leaf = _Leaf()
    clock.watch("tick", "8", 0.0, leaf)
    thread = clock.thread
    assert thread.is_alive() and thread.daemon
    owner.clock.t = 1.0
    leaf.ready.set()
    _settle(lambda: owner.metrics.summary()["device_programs"] == 1)
    del owner
    gc.collect()
    thread.join(5)
    assert not thread.is_alive()


def test_a_drained_owner_ends_the_thread_and_the_next_program_starts_another():
    """``rest()``: what is queued is stamped, then the thread ends and the
    queue is empty; the next program starts another thread, and the idle
    stretch between the two is counted from the last completion."""
    owner = _Owner()
    clock = owner.device_clock(_scripted_wait)
    clock.rest()  # nothing was ever dispatched: nothing to end
    assert clock.thread is None
    first, second = _Leaf(), _Leaf()
    clock.watch("tick", "8", 0.0, first)
    clock.watch("tick", "8", 0.5, second)
    thread = clock.thread
    clock.rest()  # before either completed: both are stamped all the same
    owner.clock.t = 1.0
    first.ready.set()
    _settle(lambda: owner.metrics.summary()["device_programs"] == 1)
    assert thread.is_alive()
    owner.clock.t = 2.0
    second.ready.set()
    thread.join(10)
    assert not thread.is_alive() and clock.thread is None
    assert not clock._entries
    assert owner.metrics.summary()["device_programs"] == 2
    third = _Leaf()
    clock.watch("prefill", "16x1", 5.0, third)
    assert clock.thread is not thread and clock.thread.is_alive()
    owner.clock.t = 6.0
    third.ready.set()
    _settle(lambda: owner.metrics.summary()["device_programs"] == 3)
    assert _idle_seconds(owner.metrics) == pytest.approx(3.0)  # 2.0 to 5.0
    assert clock.thread.is_alive()  # nobody said the owner had drained


def test_no_program_is_lost_where_a_dispatch_meets_the_threads_end():
    """The pump rests the clock and, as soon as the thread has taken the
    last entry and is on its way out, dispatches again, over and over, with
    the interpreter switching threads as often as it can: every program is
    stamped, by one thread at a time."""
    owner = _Owner(tracer=False)
    clock = owner.device_clock(lambda leaf: None)
    rounds = 400
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for i in range(rounds):
            owner.clock.t = float(i)
            clock.watch("tick", "8", float(i), object())
            clock.rest()
            _settle(lambda: not clock._entries)
        _settle(lambda: owner.metrics.summary()["device_programs"] == rounds)
    finally:
        sys.setswitchinterval(interval)
    _settle(lambda: clock.thread is None, "the last thread to end")
    assert not clock._entries
    assert owner.metrics._device_faults.value == 0
    assert owner.metrics.summary()["device_clock_dropped"] == 0


def test_the_annotations_are_not_leaves_of_the_pump_thread(monkeypatch):
    """``device.run.<kind>``: held over the wait, on the clock's own
    thread, and under neither prefix by which the trace reduction names the
    device's idle gaps."""
    held = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            held.append(("enter", self.name, threading.current_thread().name))

        def __exit__(self, *exc):
            held.append(("exit", self.name, threading.current_thread().name))

    monkeypatch.setattr(device_clock_mod, "TraceAnnotation", Recorder)
    owner = _Owner()
    clock = owner.device_clock(_scripted_wait)
    _play(owner, clock, [(0.0, 1.0), (0.5, 2.0)], kinds=["tick", "prefill"])
    _settle(lambda: len(held) == 4)
    assert [(what, name) for what, name, _ in held] == [
        ("enter", "device.run.tick"), ("exit", "device.run.tick"),
        ("enter", "device.run.prefill"), ("exit", "device.run.prefill"),
    ]
    assert {thread for *_, thread in held} == {"device-clock"}
    assert not any(
        name.startswith(("engine.", "daemon.")) for _, name, _ in held
    )


def test_the_clocks_wait_is_the_marked_read_off_the_pump_thread():
    """``check_host_sync`` walks ``obs/`` too: the clock's wait passes by
    its mark alone, and the engine's dispatch point holds no sync."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import check_host_sync
    finally:
        sys.path.pop(0)
    assert "tpu_parallel/obs" in check_host_sync.DEFAULT_PATHS
    path = os.path.join(REPO_ROOT, "tpu_parallel", "obs", "device_clock.py")
    source = open(path).read()
    assert check_host_sync.check_source(source, path) == []
    assert source.count(check_host_sync.WHITELIST_MARK) == 1
    unmarked = source.replace(check_host_sync.WHITELIST_MARK, "#")
    # the helper is not in a loop of its own: put the call where it runs
    looped = unmarked.replace("wait(leaf)", "leaf.block_until_ready()")
    assert len(check_host_sync.check_source(looped, path)) == 1


# -- on a toy engine -----------------------------------------------------------


def _gpt(**overrides):
    cfg = tiny_test(dtype=jnp.float32, remat=False, **overrides)
    model = GPTLM(cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )["params"]
    return model, params


def _block():
    model = GPTLM(tiny_block_diffusion(block_len=4))
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
        train=False,
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def gpt():
    return _gpt()


@pytest.fixture(scope="module")
def block():
    return _block()


def _prompt(n, seed=0):
    return [int(1 + (7 * i + 3 * seed) % 200) for i in range(n)]


# engine knobs, the kinds of program such an engine can dispatch, and the
# prompts that make it dispatch all of them
ENGINES = {
    "fused": (dict(), {"tick", "prefill"}, (5, 6)),
    "per_step": (dict(decode_steps_per_tick=1), {"tick", "prefill"}, (5, 6)),
    "unified_with_a_chunk": (
        dict(prefill_chunk_tokens=4), {"tick", "tick_chunk", "prefill"},
        (11, 3),
    ),
    "per_phase_chunks": (
        dict(prefill_chunk_tokens=4, unified_tick=False),
        {"tick", "extend", "prefill"}, (11, 3),
    ),
    "speculative": (dict(draft_tokens=3), {"tick", "prefill"}, (5, 6)),
    "speculative_fused": (
        dict(draft_tokens=2, decode_steps_per_tick=2), {"tick", "prefill"},
        (5, 6),
    ),
    "paged": (dict(kv_block_tokens=4), {"tick", "extend"}, (5, 6)),
    "prefix_hit": (
        dict(prefix_cache_size=2), {"tick", "prefill", "extend"}, (10, 10),
    ),
    "block": (dict(), {"tick", "prefill"}, (9, 18)),
}


def _serve(kind, gpt, block, tracer=None, clock=time.monotonic):
    knobs, kinds, lengths = ENGINES[kind]
    model, params = block if kind == "block" else gpt
    buckets = (16, 32) if kind == "block" else (8, 16)
    eng = ServingEngine(
        model, params, n_slots=2, prefill_buckets=buckets, tracer=tracer,
        clock=clock, scheduler=SchedulerConfig(max_prefills_per_tick=1),
        **knobs,
    )
    outs = [
        eng.add_request(Request(
            prompt=_prompt(n, seed=0 if kind == "prefix_hit" else i),
            max_new_tokens=12,
        ))
        for i, n in enumerate(lengths)
    ]
    return eng, outs, kinds


def _drain(eng):
    eng.run()
    done = eng.metrics.host_dispatches
    _settle(
        lambda: eng.metrics.summary()["device_programs"] >= done,
        "every dispatched program to be stamped",
    )


def _kinds_seen(eng):
    return {
        h["labels"]["program"]
        for h in eng.registry.snapshot()["histograms"]
        if h["name"] == "serving_device_seconds" and h["count"]
    }


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_an_engine_names_exactly_the_programs_it_can_dispatch(
    kind, gpt, block
):
    """Every program of every tick kind goes through the one dispatch point
    under the kind its configuration says; the seconds and the idle time
    tile the run; the tracer gets one span a program."""
    tracer = Tracer()
    t0 = time.monotonic()
    eng, outs, kinds = _serve(kind, gpt, block, tracer=tracer)
    _drain(eng)
    elapsed = time.monotonic() - t0
    assert all(out.status == FINISHED for out in outs)
    assert _kinds_seen(eng) == kinds
    s = eng.metrics.summary()
    # every model forward the engine counts as a dispatch is a watched
    # program, and nothing else is
    assert s["device_programs"] == s["host_dispatches"]
    assert s["device_clock_dropped"] == 0
    spans = [sp for sp in tracer.spans if sp.track == "device"]
    assert len(spans) == s["device_programs"]
    assert {sp.name for sp in spans} == {f"device.{k}" for k in kinds}
    assert all(a.end <= b.start for a, b in zip(spans, spans[1:]))
    assert all(sp.attrs["shape"] for sp in spans)
    # watched seconds + idle seconds = the record's elapsed time, which is
    # the run's but for what preceded the first dispatch's return
    watched = sum(sp.end - sp.start for sp in spans)
    idle = _idle_seconds(eng.metrics)
    tiled = spans[-1].end - spans[0].start
    assert watched + idle == pytest.approx(tiled, rel=1e-6)
    assert tiled <= elapsed
    assert s["device_idle_share"] == pytest.approx(idle / tiled, abs=1e-4)
    if "tick" in kinds:
        assert s["device_tick_ms_mean"] > 0
    if "tick_chunk" in kinds:
        assert s["device_tick_chunk_ms_mean"] > 0
    if kinds & {"prefill", "extend"}:
        assert 0 < s["device_prefill_share"] < 1
        assert s["device_prefill_ms_per_ktok"] > 0


def test_watched_and_idle_seconds_are_the_windows_wall_time(gpt, block):
    """A window as the benchmark cuts it (``reset_metrics()`` with the
    engine busy, ``summary()`` at its close): what the clock accounts for
    is the window's elapsed time within 5%."""
    eng, _, _ = _serve("fused", gpt, block)
    eng.step()  # compiled and in flight
    for i in range(16):
        eng.add_request(Request(prompt=_prompt(5, seed=i), max_new_tokens=26))
    eng.step()
    opened = eng.clock()
    eng.reset_metrics()
    while eng.has_work():
        eng.step()
    closed = eng.clock()
    # everything is collected, so every leaf is ready: the thread is done
    # as soon as it has run
    _settle(lambda: not eng._device_clock._entries)
    time.sleep(0.05)
    s = eng.metrics.summary()
    assert s["finished"] >= 16 and s["device_programs"] >= 16
    accounted = sum(
        h["sum"] for h in eng.registry.snapshot()["histograms"]
        if h["name"] == "serving_device_seconds"
    ) + _idle_seconds(eng.metrics)
    assert accounted == pytest.approx(closed - opened, rel=0.05)
    assert 0.0 <= s["device_idle_share"] <= 1.0


def test_reset_metrics_starts_the_clock_from_zero(gpt, block):
    eng, _, _ = _serve("fused", gpt, block)
    _drain(eng)
    before = eng.metrics.summary()
    assert before["device_programs"] > 0
    fresh = eng.reset_metrics()
    empty = fresh.summary()
    assert empty["device_programs"] == 0
    assert empty["device_tick_ms_mean"] is None
    assert empty["device_idle_share"] is None
    assert not [
        h for h in eng.registry.snapshot()["histograms"]
        if h["name"] == "serving_device_seconds"
    ]
    out = eng.add_request(Request(prompt=_prompt(5), max_new_tokens=9))
    _drain(eng)
    assert out.status == FINISHED
    after = eng.metrics.summary()
    assert 0 < after["device_programs"] < before["device_programs"]
    # the idle stretch before the reset is the old record's, not this one's
    assert eng.metrics._device_first == fresh._device_opened


def _watched_leaves(eng):
    """Weak references to every array the engine hands its clock."""
    refs = []
    watch = eng._device_clock.watch

    def watch_and_note(kind, shape, dispatched, leaf):
        refs.append(weakref.ref(leaf))
        watch(kind, shape, dispatched, leaf)

    eng._device_clock.watch = watch_and_note
    return refs


def test_no_thread_for_an_engine_that_only_queued(gpt, block):
    before = threading.active_count()
    eng, _, _ = _serve("fused", gpt, block)
    assert eng._device_clock.thread is None
    assert threading.active_count() == before
    eng.step()
    assert eng._device_clock.thread is not None  # the first dispatch


@pytest.mark.parametrize("kind", ["fused", "block", "per_step"])
def test_a_drained_engine_leaves_nothing_of_the_clock_on_the_device(
    kind, gpt, block
):
    """What every serving driver does before its reference allocates: the
    engine serves, drains, is deleted and collected.  At the drain the
    clock's thread ends with an empty queue; after the deletion no array
    the clock was handed is among ``jax.live_arrays()``."""
    eng, outs, _ = _serve(kind, gpt, block)
    refs = _watched_leaves(eng)
    eng.step()
    thread = eng._device_clock.thread
    assert thread is not None and thread.is_alive()
    eng.run()
    assert all(out.status == FINISHED for out in outs)
    thread.join(10)
    assert not thread.is_alive() and eng._device_clock.thread is None
    assert not eng._device_clock._entries
    assert eng.metrics.summary()["device_programs"] == len(refs) > 0
    clock = eng._device_clock
    del eng, outs
    gc.collect()
    handed = [ref() for ref in refs]
    assert not any(
        leaf is live for leaf in handed if leaf is not None
        for live in jax.live_arrays()
    )
    assert handed == [None] * len(refs)  # nothing anywhere holds them
    assert clock.thread is None and not clock._entries


def test_a_deleted_engine_ends_a_thread_that_is_still_waiting(gpt, block):
    """An engine dropped with a tick in flight (a killed replica): the
    thread ends with its owner and the queue is emptied."""
    eng, _, _ = _serve("fused", gpt, block)
    pending = eng.launch()
    clock, thread = eng._device_clock, eng._device_clock.thread
    assert thread.is_alive()
    del eng, pending
    gc.collect()
    thread.join(10)
    assert not thread.is_alive()
    assert clock.thread is None and not clock._entries


def test_a_launch_reads_no_device_result_and_dispatches_through_one_point(
    gpt, block, monkeypatch
):
    """The launch half leaves every result on the device (the clock holds
    a reference, never a value), and the engine's jitted programs are
    called from ``_run`` alone."""
    import inspect
    import re

    import numpy as np

    from tpu_parallel.serving import engine as engine_mod

    source = inspect.getsource(engine_mod)
    body = inspect.getsource(engine_mod.ServingEngine._run)
    assert "fn(*args)" in body
    calls = re.findall(r"self\._\w*_fn\(", source.replace(body, ""))
    assert calls == [], calls

    reads, waits = [], []

    class NumpySpy:
        """``engine.np`` with ``asarray`` watched for device arrays."""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *args, **kwargs):
            if isinstance(x, jax.Array):
                reads.append(threading.current_thread().name)
            return np.asarray(x, *args, **kwargs)

    def waited(leaf):
        waits.append(threading.current_thread().name)
        leaf.block_until_ready()

    eng, _, _ = _serve("fused", gpt, block)
    eng._device_clock._wait = waited  # before the thread starts
    monkeypatch.setattr(engine_mod, "np", NumpySpy())
    pending = eng.launch()
    assert pending.kind == "fused" and eng._device_clock.thread is not None
    assert reads == []  # prefill, first tokens, the tick: all still handles
    assert isinstance(pending.payload[0], jax.Array)
    eng.collect(pending)
    assert reads and set(reads) == {threading.current_thread().name}
    _settle(lambda: len(waits) >= 2)  # a prefill and the tick
    assert set(waits) == {"device-clock"}


# -- room on the interpreter's stack, at the dispatch point ---------------------


def _thread_faults(fn, *args):
    resource = pytest.importorskip("resource")
    before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
    fn(*args)
    return resource.getrusage(resource.RUSAGE_THREAD).ru_minflt - before


def _under(frames, fn, *args):
    """``fn(*args)`` with ``frames`` more frames beneath it."""
    if frames == 0:
        return fn(*args)
    return _under(frames - 1, fn, *args)


def _loop_of_calls(n):
    x = 0
    for _ in range(n):
        x = abs(x) + _one()
    return x


def _one():
    # a frame wider than one step of ``_under``: the sweep cannot step
    # over the depths at which this call crosses a chunk's edge
    a = b = c = d = e = f = g = h = i = j = k = m = n = o = p = q = 1
    r = s = t = u = v = w = x = y = z = aa = bb = cc = dd = ee = ff = gg = 0
    return a + gg


def test_stack_room_keeps_a_loop_of_calls_clear_of_a_chunks_edge():
    """Where the interpreter's stack chunk ends under a loop, every call
    of the loop maps a chunk and every return unmaps it (a page fault a
    call); under :func:`stack_room` no depth shows it.  The depth at which
    the plain loop meets an edge is found by sweeping one chunk's worth."""
    from tpu_parallel.utils.stack_room import stack_room

    calls = 2000
    roomy = stack_room(_loop_of_calls)
    assert roomy(calls) == _loop_of_calls(calls) == calls
    plain = [
        _thread_faults(_under, d, _loop_of_calls, calls) for d in range(250)
    ]
    edges = [d for d, f in enumerate(plain) if f >= calls // 2]
    if not edges:
        pytest.skip("this interpreter frees no stack chunk under a loop")
    for d in edges:
        assert _thread_faults(_under, d, roomy, calls) < calls // 20, d


def test_stack_room_leaves_the_function_what_it_was():
    from tpu_parallel.utils.stack_room import WORDS, stack_room

    bound = 3

    def fn(a, b=2, *rest, c=4, **more):
        """what it says"""
        return a, b, rest, c, more, bound

    roomy = stack_room(fn)
    assert roomy(1, c=5, d=6) == fn(1, c=5, d=6) == (1, 2, (), 5, {"d": 6}, 3)
    assert roomy(1, 7, 8) == (1, 7, (8,), 4, {}, 3)
    assert (roomy.__name__, roomy.__doc__) == ("fn", "what it says")
    assert roomy.__qualname__ == fn.__qualname__
    assert roomy.__code__.co_stacksize == WORDS
    assert roomy.__code__.co_code == fn.__code__.co_code
    with pytest.raises(ZeroDivisionError):
        stack_room(lambda: 1 / 0)()


def test_the_dispatch_point_carries_the_room(gpt, block):
    """``_run`` is the frame every program is first called, and so
    traced, under: one frame more than a direct call had."""
    from tpu_parallel.serving import engine as engine_mod
    from tpu_parallel.utils.stack_room import WORDS

    assert engine_mod.ServingEngine._run.__code__.co_stacksize == WORDS
    eng, _, _ = _serve("fused", gpt, block)
    _drain(eng)
    assert eng.metrics.summary()["device_programs"] > 0


# -- the benchmark's readers ---------------------------------------------------

READERS = {
    "engine.device_tick_ms": ("device_tick_ms_mean", 181.25, 181.25),
    "engine.device_prefill_share": ("device_prefill_share", 0.2875, 28.75),
    "engine.device_prefill_ms_per_ktok": (
        "device_prefill_ms_per_ktok", 52.5, 52.5,
    ),
    "engine.device_idle_share": ("device_idle_share", 0.00125, 0.125),
    "engine.device_tick_chunk_ms.batch": (
        "device_tick_chunk_ms_mean", 223.5, 223.5,
    ),
}
# what summary() might hold under a reader's key: a traced run whose reader
# raises ends with exit code 1, so each reads None or the number
HELD = {
    "absent": None, "none": None, "nan": float("nan"),
    "infinite": float("inf"), "a_string": "181.25", "a_list": [1, 2],
    "true": True, "a_number": "the number",
}
BY_SHAPE = {"tick 8": [244, 44.3275], "prefill 512x1": [26, 0.5044]}


def _reader(name):
    bench = os.path.join(REPO_ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(bench, "metrics", f"{name}.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _Run:
    """What a reader is given: the window's ``summary()`` and a log."""

    def __init__(self, counters):
        self.counters = counters
        self.lines = []

    def log(self, line):
        self.lines.append(line)


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_the_number_or_nothing_and_never_raises(name, held):
    key, counter, want = READERS[name]
    reader = _reader(name)
    if held == "a_number":
        assert reader.read(_Run({key: counter})) == pytest.approx(want)
        assert reader.read(_Run({key: 0})) == 0.0
    elif held == "absent":
        # the parent's summary() has no such key; a run that never filled
        # its counters has none at all
        assert reader.read(_Run({"busy_ticks": 3})) is None
        assert reader.read(_Run({})) is None
        assert reader.read(_Run(None)) is None
    else:
        assert reader.read(_Run({key: HELD[held]})) is None
        assert reader.read(
            _Run({key: HELD[held], "device_by_shape": HELD[held]})
        ) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_is_the_entry_the_manifest_appends(name):
    manifest = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in manifest["per_layer"]]
    # found by name: later PRs append their own entries after these five,
    # which stay together, appended, nothing between
    first = min(names.index(n) for n in READERS)
    assert set(names[first:first + 5]) == set(READERS)
    entry = manifest["per_layer"][names.index(name)]
    assert {k: entry[k] for k in ("name", "layer", "unit", "source",
                                  "moves")} == _reader(name).META
    assert entry["better"] == "lower"
    if name.endswith(".batch"):
        assert entry["workloads"] == ["serve-gpt2_xl-batch"]
    else:
        assert len(entry["workloads"]) >= 3  # one reader file serves them


def test_the_split_by_shape_reaches_the_log_in_one_line_once_a_cell():
    """``device_by_shape`` is logged by the reader of
    ``engine.device_prefill_share`` and, in the one cell without it, by the
    reader of ``engine.device_tick_chunk_ms.batch``: every cell's traced
    run leaves the split exactly once."""
    manifest = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    loggers = ("engine.device_prefill_share",
               "engine.device_tick_chunk_ms.batch")
    cells = [
        cell for m in manifest["per_layer"] if m["name"] in loggers
        for cell in m["workloads"]
    ]
    serving = [w["name"] for w in manifest["workloads"]
               if w["name"].startswith("serve-")]
    assert sorted(cells) == sorted(serving)
    for name in sorted(READERS):
        run = _Run({"device_by_shape": BY_SHAPE})
        _reader(name).read(run)
        if name in loggers:
            (line,) = run.lines
            assert "\n" not in line and line.startswith("device_by_shape ")
            assert json.loads(line.split(" ", 1)[1]) == BY_SHAPE
        else:
            assert run.lines == []
    run = _Run({"busy_ticks": 3})  # the parent: nothing to log
    for name in loggers:
        _reader(name).read(run)
    assert run.lines == []


def _toy_cell(module, cell, tmp_path, seed):
    bench = os.path.join(REPO_ROOT, "benchmarks")
    toys = os.path.join(REPO_ROOT, "tests", "benchmarks")
    for path in (toys, bench):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run

    root = importlib.import_module(module).make_root(str(tmp_path))
    out = run.run_cell(
        cell, seed, 2.0, 1, False, check_device=False,
        bench_dir=os.path.join(root, "benchmarks"), root=root,
    )
    # the reduction names no gap by the clock's annotations
    names = [n for n, _ in out.get("breakdown", {}).get("idle_gaps", [])]
    assert not [n for n in names if n.startswith("device.run.")]
    return out["metrics"]


def _logged_split(capsys):
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("device_by_shape ")]
    (line,) = lines  # once a run
    return json.loads(line.split(" ", 1)[1])


def test_the_toy_hybrid_cell_reports_the_metrics_on_the_cpu(tmp_path, capsys):
    """The toy cells inherit every entry that lists the real one: a traced
    run's line holds the new metrics, read off ``summary()`` at the
    window's close (CPU numbers: that they are numbers is the point)."""
    metrics = _toy_cell(
        "bench_tiny_hybrid", "serve-tiny_hybrid", tmp_path,
        2 ** 31 + 4040,
    )
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["engine.device_tick_ms"] > 0
    assert 0 < value["engine.device_prefill_share"] < 100
    assert value["engine.device_prefill_ms_per_ktok"] > 0
    assert 0 <= value["engine.device_idle_share"] < 100
    assert metrics["engine.device_prefill_ms_per_ktok"]["unit"] == "ms/ktok"
    assert "engine.device_tick_chunk_ms.batch" not in metrics
    split = _logged_split(capsys)
    assert {key.split()[0] for key in split} >= {"tick", "prefill"}


def test_the_toy_batch_cell_reports_the_metrics_on_the_cpu(tmp_path, capsys):
    metrics = _toy_cell("bench_tiny", "serve-tiny", tmp_path, 2 ** 31 + 4141)
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["engine.device_tick_ms"] > 0
    assert 0 <= value["engine.device_idle_share"] < 100
    assert "engine.device_prefill_share" not in metrics
    split = _logged_split(capsys)
    chunked = [key for key in split if key.startswith("tick_chunk ")]
    if chunked:  # a prompt over the chunk's 32 tokens arrived in the window
        assert value["engine.device_tick_chunk_ms.batch"] > 0
    else:
        assert "engine.device_tick_chunk_ms.batch" not in metrics


def test_the_chunk_reader_reads_a_chunked_engines_summary(gpt, block):
    eng, _, _ = _serve("unified_with_a_chunk", gpt, block)
    _drain(eng)
    run = _Run(dict(eng.metrics.summary()))
    value = _reader("engine.device_tick_chunk_ms.batch").read(run)
    assert value > 0 and math.isfinite(value)
    (line,) = run.lines
    assert any(key.startswith("tick_chunk ") for key in json.loads(
        line.split(" ", 1)[1]
    ))
