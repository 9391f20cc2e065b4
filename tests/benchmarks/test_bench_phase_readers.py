"""The phase clock's six per-layer readers: each returns the right number
from canned ``run.counters`` and None where the program (the parent
commit's, say) publishes no such key."""

import importlib.util
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)  # the metric readers import ``lib``

COUNTERS = {
    "busy_tick_ms_mean": 216.5,
    "tick_device_wait_ms_mean": 192.25,
    "tick_deliver_ms_mean": 7.5,
    "tick_between_ms_mean": 5.125,
    "host_exposed_share": 9.75,
    "prefill_tick_ms_mean": 256.0,
    "decode_only_tick_ms_mean": 215.0,
}
# reader -> (the value it reads from COUNTERS, the keys it needs)
READERS = {
    "engine.busy_tick_ms.batch": (216.5, ["busy_tick_ms_mean"]),
    "engine.device_wait_ms.batch": (192.25, ["tick_device_wait_ms_mean"]),
    "engine.deliver_ms.batch": (7.5, ["tick_deliver_ms_mean"]),
    "engine.between_ticks_ms.batch": (5.125, ["tick_between_ms_mean"]),
    "engine.host_exposed_share.batch": (9.75, ["host_exposed_share"]),
    "engine.prefill_tick_extra_ms.batch": (
        41.0, ["prefill_tick_ms_mean", "decode_only_tick_ms_mean"]),
}


def reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_its_counter(name):
    want, _ = READERS[name]
    run = types.SimpleNamespace(counters=dict(COUNTERS))
    assert reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_none_without_its_keys(name):
    _, keys = READERS[name]
    module = reader(name)
    # the parent's summary(): none of the keys; a window with no busy
    # tick: the keys are there and hold None
    assert module.read(types.SimpleNamespace(counters={})) is None
    for key in keys:
        for hole in ({k: v for k, v in COUNTERS.items() if k != key},
                     dict(COUNTERS, **{key: None})):
            assert module.read(types.SimpleNamespace(counters=hole)) is None
