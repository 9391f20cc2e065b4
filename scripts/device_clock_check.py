"""How far to trust the completion clock, measured on the chip.

    python3 scripts/device_clock_check.py --workload <serving cell> --seed <n>

Runs ONE traced run of a serving cell of the benchmark through
``benchmarks/run.py::run_cell`` (nothing under ``benchmarks/`` is edited: the
trace reduction is wrapped from outside so that the raw trace is read once
more before the driver deletes it) and prints, after the run's own result
line, one JSON line ``device_clock_check``:

- ``lateness_ms`` / ``lateness_ms_by_kind``: the end of each
  ``device.run.<kind>`` annotation (the clock's stamp, on the profiler's
  clock) against the end of the program it timed on the device's ``XLA
  Modules`` line (the executions of at least ``--program-ms``; the row
  scatters, the sampler and the seat programs between them are shorter),
  matched in order: count, median, p95, max; ``stamps_unmatched`` counts the
  stamps whose program is not in the trace;
- ``clock_against_device``: the clock's own (start, done) of the traced
  programs against the device's: by kind, the count and the MEDIAN of the
  clock's milliseconds beside the median of the device's, over the matched
  stamps only, and each matched program's pair in the clock's order (a
  prefill's bucket shows in its milliseconds);
- ``split``: ``summary()["device_by_shape"]`` over the whole window with the
  mean milliseconds a call, the seconds by kind, the idle seconds, and tick +
  prefill + idle seconds a busy tick beside ``busy_tick_ms_mean``;
- ``idle_share``: the clock's, over the window, beside the trace's, over the
  traced seconds; ``clock_idle_gaps``: the clock's idle stretches of the
  window by size; ``idle_gap_names``: the annotations the reduction named
  gaps by (no ``device.run.*`` may be among them).

Refuses the CPU as the benchmark does (``--cpu 1`` for a rehearsal).
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

PREFIX = "device.run."


def stats(values):
    from tpu_parallel.serving.metrics import percentile

    if not values:
        return {"n": 0}
    return {
        "n": len(values),
        "median": round(statistics.median(values), 4),
        "p95": round(percentile(values, 95), 4),
        "max": round(max(values), 4),
    }


def read_trace(path, program_ms):
    """Annotation ends, program ends and op intervals of one trace, in
    seconds on the profiler's clock."""
    import jax
    from lib import xplane

    data = jax.profiler.ProfileData.from_file(path)
    stamps, programs, ops = [], [], []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:0")
        for line in plane.lines:
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if device and line.name == "XLA Modules":
                    if ev.duration_ns * 1e-6 >= program_ms:
                        programs.append((start, end, ev.name))
                elif device and line.name == "XLA Ops":
                    # as lib/xplane.py: a container's body has its own events
                    if not xplane.CONTAINER.match(xplane.op_name(ev.name)):
                        ops.append((start, end))
                elif not device and ev.name.startswith(PREFIX):
                    stamps.append((start, end, ev.name[len(PREFIX):]))
    return sorted(stamps, key=lambda e: e[1]), sorted(
        programs, key=lambda e: e[1]), sorted(ops)


def lateness(stamps, programs):
    """Milliseconds from a program's end on the device to the clock's stamp,
    each stamp matched to the newest program that ended before it (plus
    50 us for the two planes' alignment); ``matched[i]`` is the program of
    stamp ``i`` or None."""
    by_kind, j, matched = {}, 0, []
    ends = [p[1] for p in programs]
    for start, end, kind in stamps:
        while j < len(ends) and ends[j] <= end + 50e-6:
            j += 1
        if j == 0 or ends[j - 1] < start:
            matched.append(None)  # its program is not in the trace
            continue
        by_kind.setdefault(kind, []).append(1e3 * (end - ends[j - 1]))
        matched.append(programs[j - 1])
    return by_kind, matched


def clock_against_device(stamps, matched, seen, ops):
    """The clock's own (start, done) of the traced programs, brought onto
    the profiler's clock, against the device's: ``seen`` is every program
    the clock recorded, in order, as (kind, start, done) on the engine's
    clock.  An annotation ends a few microseconds after its ``done`` was
    read, so the run of ``seen`` whose kinds are the stamps' and whose
    ``annotation end - done`` is one constant is the traced span."""
    kinds = [k for _, _, k in stamps]
    ends = [e for _, e, _ in stamps]
    n, best = len(stamps), None
    for i0 in range(len(seen) - n + 1):
        if [k for k, _, _ in seen[i0:i0 + n]] != kinds:
            continue
        diffs = [ends[j] - seen[i0 + j][2] for j in range(n)]
        spread = max(diffs) - min(diffs)
        if best is None or spread < best[0]:
            best = (spread, i0, statistics.median(diffs))
    if best is None or n < 3:
        return {"aligned": False}
    spread, i0, offset = best
    clock_idle, prev_clock_done = 0.0, None
    by_kind = {}  # kind: ([the clock's ms], [the device's ms])
    each = []  # [kind, the clock's ms, the device's ms] in the clock's order
    for j, program in enumerate(matched):
        kind, start, done = seen[i0 + j]
        if prev_clock_done is not None:
            clock_idle += max(0.0, start - prev_clock_done)
        prev_clock_done = done
        if program is None:
            continue
        mine, theirs = by_kind.setdefault(kind, ([], []))
        mine.append(1e3 * (done - start))
        theirs.append(1e3 * (program[1] - program[0]))
        each.append([kind, round(mine[-1], 3), round(theirs[-1], 3)])
    # the device's own idle time over the same span, by its ops
    from lib import xplane

    lo, hi = seen[i0][1] + offset, seen[i0 + n - 1][2] + offset
    busy = xplane.total(xplane.clip(xplane.union(ops), lo, hi)) if ops else None
    return {
        "aligned": True, "alignment_spread_ms": round(1e3 * spread, 4),
        "programs": n, "span_s": round(hi - lo, 4),
        "clock_idle_in_span_ms": round(1e3 * clock_idle, 3),
        "trace_idle_in_span_ms": (
            None if busy is None else round(1e3 * (hi - lo - busy), 3)
        ),
        # matched stamps only: [count, the clock's median ms, the device's]
        "ms_median_by_kind_clock_vs_device": {
            kind: [len(mine), round(statistics.median(mine), 3),
                   round(statistics.median(theirs), 3)]
            for kind, (mine, theirs) in by_kind.items()
        },
        # a prefill call reads by its bucket: one line a matched program
        "ms_each_clock_vs_device": each,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--program-ms", type=float, default=2.0)
    parser.add_argument("--cpu", type=int, choices=(0, 1), default=0)
    parser.add_argument("--root", default=ROOT,
                        help="a tree with BENCHMARK.json and benchmarks/")
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                        help="where the JSON is written")
    args = parser.parse_args(argv)

    import run as bench_run
    from lib import xplane

    from tpu_parallel.serving.metrics import ServingMetrics

    # the raw trace, read once more before the driver deletes it
    trace = {}
    reduce_trace = xplane.reduce_trace

    def reduce_and_keep(path, *a, **kw):
        trace["stamps"], trace["programs"], trace["ops"] = read_trace(
            path, args.program_ms
        )
        return reduce_trace(path, *a, **kw)

    xplane.reduce_trace = reduce_and_keep
    # every program the clock stamped, and the idle stretches, by record
    record_device = ServingMetrics.record_device
    programs_seen = []  # (kind, start, done) in the clock's order
    gaps_seen = []  # (record, seconds)

    def record_and_keep(self, kind, shape, idle_from, start, done):
        if idle_from is not None:
            opened = self._device_opened
            lo = idle_from if opened is None else max(idle_from, opened)
            if start > lo:
                gaps_seen.append((id(self), start - lo))
        programs_seen.append((kind, start, done))
        return record_device(self, kind, shape, idle_from, start, done)

    ServingMetrics.record_device = record_and_keep
    # every summary() as it was read: the window's is the one the driver
    # made the run's counters from, and the record that gave it the window's
    snapshots = []
    summary = ServingMetrics.summary

    def summary_and_keep(self):
        out = summary(self)
        snapshots.append({
            "summary": dict(out), "record": id(self),
            "gaps": len(gaps_seen), "faults": self._device_faults.value,
            "idle_seconds": self._device_idle.value,
        })
        return out

    ServingMetrics.summary = summary_and_keep
    kept = {}
    per_layer_metrics = bench_run.per_layer_metrics

    def read_and_keep(run):
        kept["counters"] = run.counters
        return per_layer_metrics(run)

    bench_run.per_layer_metrics = read_and_keep
    result = bench_run.run_cell(
        args.workload, args.seed, args.seconds, 1, check_device=not args.cpu,
        bench_dir=os.path.join(args.root, "benchmarks"), root=args.root,
    )
    print(json.dumps(result), flush=True)

    window = next(
        (snap for snap in snapshots if snap["summary"] == kept["counters"]),
        {},
    )
    s = window.get("summary", {})
    by_shape, seconds_by_kind = {}, {}
    for key, (calls, seconds) in sorted(s.get("device_by_shape", {}).items()):
        by_shape[key] = [calls, seconds, round(1e3 * seconds / calls, 3)]
        kind = key.split(" ", 1)[0]
        seconds_by_kind[kind] = round(
            seconds_by_kind.get(kind, 0.0) + seconds, 4
        )
    busy = s.get("busy_ticks") or 0
    accounted = sum(seconds_by_kind.values()) + window.get("idle_seconds", 0.0)
    sizes = {}
    for record, seconds in gaps_seen[:window.get("gaps", 0)]:
        if record == window["record"]:
            label = next((f"<{1e3 * e:g}ms" for e in (0.001, 0.005, 0.02, 0.1)
                          if seconds < e), ">=100ms")
            n, total = sizes.get(label, (0, 0.0))
            sizes[label] = (n + 1, round(total + seconds, 4))
    stamps = trace.get("stamps", [])
    late, matched = lateness(stamps, trace.get("programs", []))
    metrics = result.get("metrics", {})
    cell = args.workload.rsplit("-", 1)[-1]
    gaps = [n for n, _ in result.get("breakdown", {}).get("idle_gaps", [])]
    check = {
        "workload": args.workload, "seed": args.seed,
        "correct": result.get("correct"),
        "lateness_ms": stats([x for v in late.values() for x in v]),
        "lateness_ms_by_kind": {k: stats(v) for k, v in late.items()},
        "stamps": len(stamps),
        "stamps_unmatched": sum(m is None for m in matched),
        "clock_against_device": (
            clock_against_device(stamps, matched, programs_seen, trace["ops"])
            if stamps else None
        ),
        "split": {
            # "<program> <shape>": [calls, seconds, ms a call]
            "by_shape": by_shape,
            "seconds_by_kind": seconds_by_kind,
            "idle_seconds": round(window.get("idle_seconds", 0.0), 4),
            "busy_ticks": busy,
            "accounted_ms_per_busy_tick": (
                round(1e3 * accounted / busy, 3) if busy else None
            ),
            "busy_tick_ms_mean": s.get("busy_tick_ms_mean"),
            "prefill_calls": s.get("prefill_calls"),
            "prefill_tokens_real": s.get("prefill_tokens_real"),
        },
        "summary_device_keys": {
            k: v for k, v in s.items()
            if k.startswith("device_") and k != "device_by_shape"
        },
        "idle_share": {
            "clock_whole_window_pct": (
                None if s.get("device_idle_share") is None
                else round(100.0 * s["device_idle_share"], 4)
            ),
            "trace_pct": metrics.get(
                f"device.idle_share.{cell}", {}
            ).get("value"),
        },
        "clock_idle_gaps": sizes,  # size: [stretches, seconds]
        "idle_gap_names": gaps,
        "device_run_among_gap_names": any(PREFIX in n for n in gaps),
        "faults": window.get("faults"),
        "new_metrics": {
            k: v["value"] for k, v in metrics.items()
            if k.startswith("engine.device_") and "device_wait" not in k
        },
    }
    print("device_clock_check " + json.dumps(check), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(
        args.out, f"device_clock_check.{cell}.{args.seed}.json"
    ), "w") as f:
        json.dump({"result": result, "check": check}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
