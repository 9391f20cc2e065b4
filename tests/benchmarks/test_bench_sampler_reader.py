"""The sampler's per-layer reader: 100.0 from a run whose ``counters`` hold
the engine's ``sampler_skip_share``, nothing where the program (the parent
commit's, say) publishes no such key or counted no busy tick."""

import importlib.util
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)  # the metric readers import ``lib``

NAME = "engine.sampler_skip_share.shortchat"


def reader():
    path = os.path.join(BENCH, "metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("share, want", [(1.0, 100.0), (0.75, 75.0), (0.0, 0.0)])
def test_reader_returns_the_share_as_percent(share, want):
    run = types.SimpleNamespace(counters={"sampler_skip_share": share, "busy_ticks": 120})
    assert reader().read(run) == pytest.approx(want)
    assert reader().META["name"] == NAME


@pytest.mark.parametrize("counters", [
    {}, {"launch_ahead_share": 1.0, "busy_ticks": 120}, {"sampler_skip_share": None},
], ids=["no_counters", "the_parents_summary", "no_busy_tick"])
def test_reader_returns_nothing_without_its_key(counters):
    assert reader().read(types.SimpleNamespace(counters=counters)) is None


def test_reader_reads_the_engines_own_summary():
    """The key the reader asks for is one ``ServingMetrics.summary()`` has."""
    from tpu_parallel.serving import ServingMetrics

    metrics = ServingMetrics()
    assert metrics.summary()["sampler_skip_share"] is None
    metrics.record_busy_tick(0.1, {"dispatch": 0.01}, prefill=False)
    metrics.record_busy_tick(0.1, {"dispatch": 0.01}, prefill=True, sampled=True)
    run = types.SimpleNamespace(counters=dict(metrics.summary()))
    assert reader().read(run) == pytest.approx(50.0)
