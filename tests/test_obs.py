"""Unified-telemetry tests: metric registry (log-bucketed histograms vs
numpy ground truth), span tracer (null-tracer cost contract), exporters
(Chrome trace round-trip + Perfetto field contract, Prometheus text
parse), the rebased JSONL sink, and the scheduler's queue-age gauge.
(The collective-scope static check moved to ``tests/test_checkers.py``,
the single entry point over the ``scripts/check_all.py`` registry.)"""

import json
import math
import os
import re
import time

import numpy as np
import pytest

from tpu_parallel.obs import (
    NULL_SPAN,
    NULL_TRACER,
    HistogramWindow,
    MetricRegistry,
    PercentileWindow,
    SpanSpool,
    Tracer,
    chrome_trace_events,
    parse_prometheus_text,
    prometheus_lines,
    prometheus_text,
    read_span_log,
    validate_snapshot,
    write_chrome_trace,
)
from tpu_parallel.obs.exporters import _prom_labels
from tpu_parallel.obs.registry import Histogram

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry --------------------------------------------------------------


def test_registry_instruments_are_singletons_per_label():
    r = MetricRegistry()
    a = r.counter("reqs_total", status="ok")
    b = r.counter("reqs_total", status="ok")
    c = r.counter("reqs_total", status="err")
    assert a is b and a is not c
    a.inc(), a.inc(2.0), c.inc()
    snap = r.snapshot()
    rows = {
        tuple(sorted(row["labels"].items())): row["value"]
        for row in snap["counters"]
    }
    assert rows[(("status", "ok"),)] == 3.0
    assert rows[(("status", "err"),)] == 1.0


def test_registry_kind_collision_raises():
    r = MetricRegistry()
    r.counter("x")
    with pytest.raises(ValueError):
        r.gauge("x")
    with pytest.raises(ValueError):
        r.histogram("x")


def test_counter_refuses_negative():
    r = MetricRegistry()
    with pytest.raises(ValueError):
        r.counter("c").inc(-1)


def test_histogram_exact_aggregates():
    h = Histogram()
    vals = [0.0, 0.5, 1.0, 2.0, 100.0]
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.min == 0.0 and h.max == 100.0
    assert h.mean() == pytest.approx(sum(vals) / len(vals))
    assert h.zero_count == 1
    cum = h.cumulative()
    assert [c for _, c in cum] == sorted(c for _, c in cum)
    assert cum[-1][1] == len(vals)


def test_histogram_empty_is_none():
    h = Histogram()
    assert h.percentile(50) is None and h.mean() is None
    assert h.min is None and h.max is None


def test_histogram_window_base_and_delta():
    """HistogramWindow splits a monotone histogram at its capture point:
    base_* reads the before side, delta_* the since side — the swap
    controller's baseline-vs-canary mechanism."""
    from tpu_parallel.obs import HistogramWindow

    h = Histogram()
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    w = HistogramWindow(h)
    assert w.base_count() == 3
    assert w.base_mean() == pytest.approx(2.0)
    assert w.delta_count() == 0 and w.delta_mean() is None
    for v in (10.0, 20.0):
        h.observe(v)
    assert w.base_mean() == pytest.approx(2.0)  # capture is immutable
    assert w.delta_count() == 2
    assert w.delta_mean() == pytest.approx(15.0)
    # a fresh window re-captures the same instrument
    w2 = HistogramWindow(h)
    assert w2.base_count() == 5 and w2.delta_count() == 0
    empty = HistogramWindow(Histogram())
    assert empty.base_mean() is None and empty.delta_mean() is None


def test_percentile_window_delta_percentile():
    """PercentileWindow adds WINDOWED percentiles (the autopilot's p95
    sense): the delta percentile tracks only post-capture observations,
    agreeing with numpy on the delta set within bucket tolerance, and
    the base-side stats stay those of the cheap two-float window."""
    h = Histogram()
    for _ in range(50):
        h.observe(0.001)  # pre-capture noise the window must ignore
    w = PercentileWindow(h)
    assert w.delta_percentile(95) is None  # empty window
    # plateaus sized so the probed percentiles sit INSIDE them (numpy's
    # linear interpolation between plateaus is not the bucket estimate)
    delta_vals = [0.1] * 80 + [1.0] * 10 + [10.0] * 10
    for v in delta_vals:
        h.observe(v)
    for p in (50, 85, 95, 99):
        est = w.delta_percentile(p)
        true = float(np.percentile(delta_vals, p))
        assert est == pytest.approx(true, rel=0.11), (p, est, true)
    # cumulative reads are poisoned by the pre-capture mass (its p25 is
    # the old noise; the window's p25 is squarely in the new traffic) ...
    assert h.percentile(25) == pytest.approx(0.001, rel=0.11)
    assert w.delta_percentile(25) == pytest.approx(0.1, rel=0.11)
    # ... and the base side is exactly the capture point
    assert w.base_count() == 50
    assert w.delta_count() == len(delta_vals)
    # zero-bucket observations land in the delta's rank walk too
    w2 = PercentileWindow(h)
    h.observe(0.0)
    h.observe(5.0)
    assert w2.delta_percentile(25) == 0.0
    assert w2.delta_percentile(99) == pytest.approx(5.0, rel=0.11)


def test_histogram_window_freezes_across_reset_metrics():
    """Counter-reset hygiene (autopilot + swap both depend on it): an
    ``engine.reset_metrics()`` mid-window installs a FRESH registry and
    fresh instruments, but a window holds the old histogram OBJECT — so
    its deltas freeze at their pre-reset value and can never go
    negative, while a window captured on the new registry sees only the
    new traffic."""
    import jax
    import jax.numpy as jnp

    from tpu_parallel.models import GPTLM, tiny_test
    from tpu_parallel.serving import Request, ServingEngine

    cfg = tiny_test(dtype=jnp.float32, remat=False)
    model = GPTLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]
    eng = ServingEngine(model, params, n_slots=2)
    eng.add_request(Request(prompt=[1, 2, 3], max_new_tokens=2))
    eng.run()
    old_hist = eng.registry.histogram("serving_queue_wait_seconds")
    assert old_hist.count >= 1
    mid = HistogramWindow(old_hist)
    mid_p = PercentileWindow(old_hist)
    eng.reset_metrics()
    fresh_hist = eng.registry.histogram("serving_queue_wait_seconds")
    assert fresh_hist is not old_hist  # reset = new instruments
    fresh = HistogramWindow(fresh_hist)
    eng.add_request(Request(prompt=[1, 2, 3], max_new_tokens=2))
    eng.run()
    # the mid-reset window froze: nothing negative, nothing phantom
    assert mid.delta_count() == 0
    assert mid.delta_mean() is None
    assert mid_p.delta_percentile(95) is None
    assert mid.base_count() == mid.count0 >= 1
    # the post-reset window saw exactly the new traffic
    assert fresh.delta_count() >= 1
    assert fresh.base_count() == 0


def test_histogram_percentile_within_one_bucket_width():
    """Satellite acceptance: registry histograms agree with
    numpy.percentile within one bucket width — across a log-uniform
    spread (latencies), several growth factors, and the tail/head
    percentiles the summary actually reports."""
    rng = np.random.RandomState(0)
    vals = np.exp(rng.uniform(np.log(1e-4), np.log(10.0), size=5000))
    for growth in (1.05, 1.1, 1.5):
        h = Histogram(growth=growth)
        for v in vals:
            h.observe(float(v))
        for p in (5, 25, 50, 90, 95, 99):
            est = h.percentile(p)
            true = float(np.percentile(vals, p))
            # one bucket width around the TRUE value's bucket
            idx = math.floor(math.log(true) / math.log(growth))
            width = growth ** (idx + 1) - growth ** idx
            assert abs(est - true) <= width + 1e-12, (
                f"growth={growth} p={p}: est {est} vs true {true} "
                f"(width {width})"
            )


def test_histogram_memory_is_bounded():
    """The whole point of log-bucketing: a million observations spanning
    9 decades land in a bounded bucket dict (the deques this replaced
    held every sample)."""
    h = Histogram()
    rng = np.random.RandomState(1)
    for v in np.exp(rng.uniform(np.log(1e-6), np.log(1e3), size=100_000)):
        h.observe(float(v))
    assert h.count == 100_000
    assert len(h.buckets) < 250  # log1.1(1e9) ≈ 218


def test_validate_snapshot_accepts_real_and_rejects_malformed():
    r = MetricRegistry()
    r.counter("a").inc()
    r.gauge("b").set(2.5)
    hist = r.histogram("c")
    for v in (0.1, 1.0, 10.0):
        hist.observe(v)
    snap = r.snapshot()
    assert validate_snapshot(snap) == []
    json.dumps(snap)  # exporter contract: serializable as-is
    bad = json.loads(json.dumps(snap))
    bad["histograms"][0]["buckets"][0][1] = 10**9  # breaks monotonicity
    assert validate_snapshot(bad)
    assert validate_snapshot({"counters": []})  # missing sections
    assert validate_snapshot([1, 2])  # not even a dict


# -- tracer ----------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_tracer_spans_and_async_and_instants():
    tr = Tracer(clock=FakeClock())
    q = tr.start_async("queue", track="scheduler", async_id="req-1",
                       request_id="req-1")
    with tr.span("tick", track="scheduler", tick=0):
        tr.record("prefill", "slot 0", 2.5, 3.5, bucket=32)
        tr.instant("finish", track="slot 0", request_id="req-1")
    q.finish()
    assert tr.tracks() == ["scheduler", "slot 0"]
    by_name = {s.name: s for s in tr.spans}
    assert by_name["queue"].async_id == "req-1"
    assert by_name["queue"].end > by_name["queue"].start
    assert by_name["tick"].end > by_name["tick"].start
    assert by_name["prefill"].start == 2.5 and by_name["prefill"].end == 3.5
    assert tr.instants[0]["name"] == "finish"


def test_null_tracer_allocates_nothing():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.now() == 0.0  # no timestamp read
    s1 = NULL_TRACER.span("a", track="t", big_attr=list(range(3)))
    s2 = NULL_TRACER.start_async("b", track="t", async_id="x")
    s3 = NULL_TRACER.record("c", "t", 0.0, 1.0)
    assert s1 is s2 is s3 is NULL_SPAN  # one shared object, ever
    with s1 as s:
        s.set(k=1).finish()
    assert NULL_TRACER.spans == [] and NULL_TRACER.tracks() == []


# -- exporters -------------------------------------------------------------


def _demo_tracer():
    tr = Tracer(clock=FakeClock())
    q = tr.start_async("queue", track="scheduler", async_id="req-0",
                       request_id="req-0")
    with tr.span("tick", track="scheduler", tick=0):
        tr.record("prefill", "slot 0", tr.now(), tr.now(),
                  request_id="req-0", bucket=32, slot=0, cache_hit=False)
        tr.record("decode", "slot 0", tr.now(), tr.now(),
                  request_id="req-0", token_index=0)
    q.finish()
    tr.instant("finish", track="slot 0", request_id="req-0", reason="eos")
    return tr


def test_chrome_trace_roundtrips_with_valid_fields(tmp_path):
    """Satellite acceptance: the Chrome trace output round-trips through
    json.load with valid ph/ts/pid/tid fields and monotone span
    nesting."""
    path = write_chrome_trace(_demo_tracer(), str(tmp_path / "t.json"))
    data = json.load(open(path))
    events = data["traceEvents"]
    assert events, "empty trace"
    valid_ph = {"M", "X", "i", "b", "e"}
    for ev in events:
        assert ev["ph"] in valid_ph
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # b/e async pairs balance per id
    opens = [e["id"] for e in events if e["ph"] == "b"]
    closes = [e["id"] for e in events if e["ph"] == "e"]
    assert sorted(opens) == sorted(closes)
    # monotone nesting: on each tid, complete spans are sequential or
    # strictly contained — never partially overlapping
    by_tid = {}
    for ev in events:
        if ev["ph"] == "X":
            by_tid.setdefault(ev["tid"], []).append(
                (ev["ts"], ev["ts"] + ev["dur"])
            )
    for tid, spans in by_tid.items():
        spans.sort()
        stack = []
        for start, end in spans:
            while stack and stack[-1] <= start:
                stack.pop()
            assert not stack or end <= stack[-1], (
                f"tid {tid}: span [{start}, {end}] partially overlaps "
                f"enclosing end {stack[-1]}"
            )
            stack.append(end)
    # one named thread per track, scheduler first
    names = [
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert names[0] == "scheduler" and "slot 0" in names


def test_chrome_trace_closes_unfinished_spans(tmp_path):
    tr = Tracer(clock=FakeClock())
    tr.start("dangling", track="scheduler")  # never finished (crash path)
    tr.record("done", "slot 0", tr.now(), tr.now())
    events = chrome_trace_events(tr)
    dangling = [e for e in events if e.get("name") == "dangling"][0]
    assert dangling["dur"] >= 0  # closed at the last seen timestamp


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+NaInf-]+$"
)


def test_prometheus_text_parses_line_by_line():
    """Satellite acceptance: every exposition line is either a # TYPE
    header or a well-formed sample; histograms expand to monotone
    cumulative buckets with le labels, +Inf, _sum and _count."""
    r = MetricRegistry()
    r.counter("serving_ticks_total").inc(3)
    r.gauge("queue_depth", engine="a").set(2)
    h = r.histogram("ttft_seconds")
    for v in (0.01, 0.02, 0.5, 0.0):
        h.observe(v)
    lines = prometheus_lines(r.snapshot())
    assert lines, "no exposition output"
    for line in lines:
        if line.startswith("# TYPE "):
            parts = line.split()
            assert parts[3] in ("counter", "gauge", "histogram"), line
        else:
            assert _PROM_SAMPLE.match(line), f"unparseable line: {line!r}"
    text = prometheus_text(r)
    assert text.endswith("\n")
    buckets = [
        float(line.rsplit(" ", 1)[1])
        for line in lines
        if line.startswith("ttft_seconds_bucket") and "+Inf" not in line
    ]
    assert buckets == sorted(buckets)
    assert any('le="+Inf"' in line for line in lines)
    assert any(line.startswith("ttft_seconds_sum") for line in lines)
    assert any(line.startswith("ttft_seconds_count 4") for line in lines)
    # label values with quotes/backslashes must escape, not corrupt
    r2 = MetricRegistry()
    r2.counter("c", path='a"b\\c').inc()
    (sample,) = (
        ln for ln in prometheus_lines(r2.snapshot())
        if not ln.startswith("#")
    )
    assert _PROM_SAMPLE.match(sample), sample


def test_prometheus_label_escaping_roundtrips_through_parser():
    """The escaping regression test the fleet aggregator depends on:
    a peer label value containing every escape-worthy character
    (backslash, double-quote, newline — and the adversarial ``\\n``
    TEXT sequence that naive chained str.replace corrupts) must render
    through ``prometheus_text`` and come back BYTE-IDENTICAL through
    ``parse_prometheus_text``.  The router re-exports peer series via
    exactly this parse -> relabel -> render loop, so a one-way escape
    bug would corrupt every aggregated fleet metric."""
    nasty = {
        'a"b': 'quote"inside',
        "back\\slash": "trailing\\",
        "newline": "two\nlines",
        "combo": 'mix\\"of\n all',
        "literal_backslash_n": "not\\na newline",  # \\ then n, NOT \n
    }
    r = MetricRegistry()
    for key, value in nasty.items():
        r.counter("fleet_echo_total", peer=value, which=key).inc()
    text = prometheus_text(r)
    samples = [
        s for s in parse_prometheus_text(text)
        if s["name"] == "fleet_echo_total"
    ]
    assert len(samples) == len(nasty)
    recovered = {s["labels"]["which"]: s["labels"]["peer"]
                 for s in samples}
    assert recovered == nasty  # every label value back verbatim
    # and a second render of the parsed samples is stable: render ->
    # parse -> render must be a fixed point for the label bodies
    for s in samples:
        line = "fleet_echo_total" + _prom_labels(s["labels"]) + " 1"
        (reparsed,) = parse_prometheus_text(line)
        assert reparsed["labels"] == s["labels"]


# -- MetricLogger scalar coercion (satellite regression) -------------------


def test_metric_logger_coerces_0d_arrays(tmp_path):
    """Satellite: MetricLogger.log used to crash json.dumps on 0-d
    jax/numpy array values; scalars now coerce to float/int."""
    import jax.numpy as jnp

    from tpu_parallel.utils.logging_utils import MetricLogger

    logger = MetricLogger(logdir=str(tmp_path), name="coerce")
    logger.log(
        1,
        {
            "np0d": np.asarray(1.5),
            "np_f32": np.float32(2.5),
            "jax0d": jnp.asarray(3.5),
            "plain": 4.5,
            "integer": np.asarray(7),
        },
    )
    logger.close()
    (line,) = open(tmp_path / "coerce.jsonl").read().splitlines()
    record = json.loads(line)
    assert record["np0d"] == 1.5 and record["jax0d"] == 3.5
    assert record["np_f32"] == 2.5 and record["plain"] == 4.5
    assert record["integer"] == 7


# -- serving metrics on the registry --------------------------------------


def test_serving_metrics_share_registry_and_count_stalls():
    from tpu_parallel.serving.metrics import ServingMetrics

    r = MetricRegistry()
    m = ServingMetrics(registry=r)
    assert m.registry is r
    m.record_tick(now=1.0, queue_depth=3, occupancy=0.5, new_tokens=2,
                  prefills=1, decoded=True, stall="prefill")
    m.record_tick(now=2.0, queue_depth=0, occupancy=0.0, new_tokens=0,
                  prefills=0, decoded=False, stall="queue_empty")
    m.record_spec(drafted=4, accepted=3, wasted=1)
    stalls = {
        row["labels"]["cause"]: row["value"]
        for row in r.snapshot()["counters"]
        if row["name"] == "serving_tick_stall_total"
    }
    assert stalls["prefill"] == 1 and stalls["queue_empty"] == 1
    assert stalls["none"] == 0 and stalls["spec_verify"] == 0
    s = m.summary()
    assert s["ticks"] == 2 and s["tokens_out"] == 2
    assert s["queue_depth_max"] == 3
    assert s["spec_acceptance_rate"] == 0.75
    json.dumps(s)
    assert validate_snapshot(r.snapshot()) == []


def test_scheduler_queue_age_gauge_and_wait_histogram():
    from tpu_parallel.serving import FIFOScheduler, Request, RequestOutput

    clock_now = [100.0]
    r = MetricRegistry()
    sched = FIFOScheduler(clock=lambda: clock_now[0], registry=r)
    outs = [
        RequestOutput(Request(prompt=[1, 2]), arrival_time=t)
        for t in (90.0, 95.0, 99.0)
    ]
    for out in outs:
        sched.submit(out)
    assert sched.oldest_age() == 10.0
    admitted = sched.schedule(n_free=1)  # default 1 prefill per tick
    assert admitted == [outs[0]]
    age = next(
        row["value"] for row in r.snapshot()["gauges"]
        if row["name"] == "serving_queue_age_seconds"
    )
    assert age == 5.0  # oldest REMAINING after the head admitted
    (wait_hist,) = (
        row for row in r.snapshot()["histograms"]
        if row["name"] == "serving_queue_wait_seconds"
    )
    assert wait_hist["count"] == 1 and wait_hist["sum"] == pytest.approx(10.0)


def test_generate_speculative_registry_acceptance_histogram():
    """spec_decode's standalone loop feeds the same acceptance histogram
    the engine does — checked structurally on the registry (no model:
    the histogram name + observation contract is what's pinned here)."""
    r = MetricRegistry()
    h = r.histogram("serving_spec_acceptance_ratio")
    from tpu_parallel.serving.metrics import ServingMetrics

    m = ServingMetrics(registry=r)
    m.record_spec(drafted=2, accepted=2, wasted=0)
    m.record_spec(drafted=4, accepted=1, wasted=3)
    m.record_spec(drafted=0, accepted=0, wasted=1)  # no-draft tick: no obs
    assert h.count == 2
    assert h.max == 1.0 and h.min == 0.25


# (The collective-scope gate — and every other AST contract gate — is
# wired tier-1 through the single scripts/check_all.py registry entry
# point in tests/test_checkers.py.)


# -- disabled-tracer overhead (acceptance, slow) ---------------------------


@pytest.mark.slow
def test_disabled_tracer_overhead_under_two_percent():
    """Acceptance: engine tick overhead with tracing DISABLED is within
    noise (<2%) of pre-PR.  Measured directly: the per-tick cost of the
    null-tracer call pattern the instrumented tick executes (enabled
    checks + no-op now()/span calls) against a real engine's measured
    mean tick time."""
    import jax
    import jax.numpy as jnp

    from tpu_parallel.models import GPTLM, tiny_test
    from tpu_parallel.serving import Request, ServingEngine

    cfg = tiny_test(dtype=jnp.float32, remat=False)
    model = GPTLM(cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(0), (2, 5), 1, cfg.vocab_size
    )
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, prompt, train=False
    )["params"]

    def run_once():
        eng = ServingEngine(model, params, n_slots=2)  # default NULL_TRACER
        for i in range(2):
            eng.add_request(
                Request(
                    prompt=[int(t) for t in np.asarray(prompt[i])],
                    max_new_tokens=16,
                )
            )
        t0 = time.perf_counter()
        ticks = 0
        while eng.has_work():
            eng.step()
            ticks += 1
        return (time.perf_counter() - t0) / ticks

    run_once()  # warm the compile cache
    tick_s = min(run_once() for _ in range(3))

    # the null-tracer call pattern one tick executes, upper-bounded:
    # ~4 enabled checks, ~4 now() calls, a span()+finish() pair, and a
    # per-slot guard for each of the 2 slots
    tr = NULL_TRACER
    reps = 100_000
    t0 = time.perf_counter()
    for _ in range(reps):
        if tr.enabled:
            pass
        if tr.enabled:
            pass
        if tr.enabled:
            pass
        if tr.enabled:
            pass
        tr.now(), tr.now(), tr.now(), tr.now()
        span = tr.span("tick", track="scheduler", tick=0)
        span.finish(stall="none", queue_depth=0, admitted=0, decoded=True)
    per_tick_overhead = (time.perf_counter() - t0) / reps
    ratio = per_tick_overhead / tick_s
    assert ratio < 0.02, (
        f"null-tracer overhead {per_tick_overhead * 1e6:.2f}us is "
        f"{ratio:.2%} of a {tick_s * 1e3:.2f}ms tick"
    )


# -- the phase clock and the tracer's bounded window -------------------------


def test_phase_writes_three_ways_from_one_pair_of_reads(monkeypatch):
    """One `phase` block: two clock reads; the sink, the tracer span and
    the profiler annotation all carry that one interval."""
    from tpu_parallel.obs import phase, phases

    entered = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(phases, "TraceAnnotation", Recorder)
    clock = FakeClock()
    tr = Tracer(clock=clock)
    sunk = []
    with phase(lambda n, s: sunk.append((n, s)), tr, "scheduler", "deliver",
               clock, annotation="engine.tick.deliver") as ph:
        assert entered == [("enter", "engine.tick.deliver")]
    assert clock.t == 2.0  # two reads, no more
    assert (ph.start, ph.end) == (1.0, 2.0)
    assert sunk == [("deliver", 1.0)]
    assert entered[-1] == ("exit", "engine.tick.deliver")
    (span,) = tr.spans
    assert (span.name, span.track, span.start, span.end) == (
        "tick.deliver", "scheduler", 1.0, 2.0
    )
    # tracing off and no annotation: still the sink, nothing else
    with phase(lambda n, s: sunk.append((n, s)), NULL_TRACER, "daemon",
               "step", clock):
        pass
    assert sunk[-1] == ("step", 1.0) and clock.t == 4.0
    assert len(entered) == 2 and len(tr.spans) == 1


def test_device_clock_writes_three_ways_from_one_read(monkeypatch):
    """The completion clock, as `phase` beside it: ONE read of the owner's
    clock a program; the histogram, the span and the annotation carry the
    interval that read closes, and tracing off leaves the histogram."""
    import threading
    import time

    from tpu_parallel import obs
    from tpu_parallel.obs import DeviceClock, device_clock
    from tpu_parallel.serving import ServingMetrics

    assert "DeviceClock" in obs.__all__
    entered = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(device_clock, "TraceAnnotation", Recorder)
    clock = FakeClock()  # a second more at every read
    tr = Tracer(clock=clock)

    class Owner:  # the two methods the clock holds of an engine, weakly
        metrics = ServingMetrics()

        def ran(self, *interval):
            self.metrics.record_device(*interval)

        def lost(self, dropped):
            raise AssertionError("nothing is dropped or fails here")

    owner = Owner()
    ready = threading.Event()
    dc = DeviceClock(
        clock, tr, owner.ran, owner.lost, wait=lambda leaf: leaf.wait(10)
    )

    def programs():
        return owner.metrics.summary()["device_programs"]

    def settle(n):
        deadline = time.monotonic() + 10
        while programs() < n:
            assert time.monotonic() < deadline
            time.sleep(0.001)

    dc.watch("prefill", "16x2", 0.25, ready)
    ready.set()
    settle(1)
    assert clock.t == 1.0  # one read, no more
    (span,) = tr.spans
    assert (span.name, span.track, span.start, span.end) == (
        "device.prefill", "device", 0.25, 1.0
    )
    assert span.attrs == {"shape": "16x2"}
    assert entered == [("enter", "device.run.prefill"),
                       ("exit", "device.run.prefill")]
    (hist,) = [
        h for h in owner.metrics.registry.snapshot()["histograms"]
        if h["name"] == "serving_device_seconds"
    ]
    assert hist["labels"] == {"program": "prefill", "shape": "16x2"}
    assert hist["sum"] == 0.75 and hist["count"] == 1
    tr.enabled = False
    dc.watch("prefill", "16x2", 0.5, ready)
    settle(2)
    assert clock.t == 2.0 and len(tr.spans) == 1 and len(entered) == 4
    assert owner.metrics.summary()["device_prefill_ms_mean"] == 875.0


def test_registry_hands_racing_threads_one_instrument():
    """The completion clock's thread makes its histograms at a shape's
    first completion, beside the pump thread's own registrations: two
    threads asking for a new (name, labels) pair hold ONE object."""
    import threading

    r = MetricRegistry()
    barrier = threading.Barrier(8)
    got = []

    def ask(i):
        barrier.wait()
        for shape in range(200):
            got.append(r.histogram("serving_device_seconds",
                                   program="tick", shape=shape))

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(got) == 1600 and len({id(h) for h in got}) == 200
    rows = [h for h in r.snapshot()["histograms"]]
    assert len(rows) == 200


def test_spool_drain_keeps_the_tracer_small_over_10k_ticks(tmp_path):
    """A tracer drained by a spool holds a tick's worth, not the 10 k
    ticks' spans, and the log has every span exactly once."""
    clock = FakeClock()
    tr = Tracer(clock=clock)
    spool = SpanSpool(str(tmp_path / "spans.jsonl"), "daemon:test",
                      max_bytes=1 << 30)
    resident = 0
    queue = None
    for tick in range(10_000):
        if tick % 100 == 0:
            queue = tr.start_async(
                "queue", "scheduler", async_id=f"q{tick}", n=tick
            )
        with tr.span("tick", track="scheduler", tick=tick):
            tr.record("tick.deliver", "scheduler", clock.t, clock.t, n=tick)
        if tick % 100 == 50:
            queue.finish()  # open across 50 drains, then written once
        tr.instant("finish", track="slot 0", n=tick)
        spool.drain(tr)
        resident = max(resident, len(tr.spans) + len(tr.instants))
    assert resident == 0 and tr.dropped == 0  # all handed to the spool
    spool.close()
    records, skipped = read_span_log(spool.path)
    assert skipped == {"garbage": 0, "crc": 0}
    for name, count in (("tick", 10_000), ("tick.deliver", 10_000),
                        ("queue", 100)):
        got = [r["attrs"].get("tick", r["attrs"].get("n"))
               for r in records if r.get("name") == name
               and r["kind"] == "span"]
        assert len(got) == len(set(got)) == count, name
    assert sum(r["kind"] == "instant" for r in records) == 10_000


def test_tracer_without_a_spool_is_capped_and_counts_what_it_drops():
    tr = Tracer(clock=FakeClock())
    tr.max_resident = 100
    held = tr.span("tick", track="scheduler", tick=-1)  # open, then dropped
    for i in range(1000):
        tr.record("decode", "slot 0", i, i + 1, i=i)
        tr.instant("finish", track="slot 0", i=i)
        assert len(tr.spans) <= 100 and len(tr.instants) <= 100
    assert tr.dropped == (1001 - len(tr.spans)) + (1000 - len(tr.instants))
    assert tr.spans[-1].attrs["i"] == 999  # the newest stay
    held.finish()  # a dropped span is still its holder's to close
    assert held.end is not None and held not in tr.spans
    assert NULL_TRACER.release(1, 1) is None
