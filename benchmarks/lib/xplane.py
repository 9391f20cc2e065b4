"""The reduction from a profiler trace (``.xplane.pb``) to device figures.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  On a TPU
each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed HLO op (``Async XLA Ops`` holds the asynchronous halves of
collectives and copies); the host is the plane ``/host:CPU`` whose lines
carry ``jax.profiler.TraceAnnotation`` spans on the same clock.

Figures, all in seconds:

- busy: the union of the op intervals on a chip (nested ops - a ``while``
  and its body - count once);
- idle gaps: the complement of that union inside the window, each named by
  the host annotation that covers most of it;
- per-op time: summed durations by op name, control-flow containers left out
  (their bodies are counted);
- collective time, and the part of it during which no other op runs on that
  chip ("exposed").
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast"
)
# ops that only enclose other ops: their bodies appear as events of their own
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]*)$")


def find_trace(logdir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def union(intervals):
    """Merged, sorted copy of ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_name(raw: str) -> str:
    """``fusion.22`` from ``%fusion.22 = bf16[...] fusion(...)``: the TPU
    plane names an event by its whole HLO instruction."""
    return raw.split(" = ", 1)[0].lstrip("%")


def load(path: str, annotations=()) -> dict:
    """``{"devices": {n: {"ops": [...], "async": [...]}}, "host": [...]}``
    with events as ``(name, start_s, end_s)``; ``host`` keeps only the
    annotations whose name starts with one of ``annotations``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "async": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", ASYNC_LINE: "async"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    dev[key].append(
                        (op_name(ev.name), start, start + ev.duration_ns * 1e-9)
                    )
        elif plane.name == HOST_PLANE and annotations:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(annotations)):
                        start = ev.start_ns * 1e-9
                        host.append(
                            (ev.name, start, start + ev.duration_ns * 1e-9)
                        )
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def name_gap(gap, host) -> str:
    """The host annotation covering most of ``gap``; ``"(no host span)"``
    when none overlaps it."""
    best, best_cover = "(no host span)", 0.0
    for name, s, e in host:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_device(dev: dict, host=(), window=None, top: int = 10) -> dict:
    """One chip's figures.  ``window`` is ``(start_s, end_s)`` on the trace's
    clock; by default it runs from the first op's start to the last op's
    end."""
    ops = [e for e in dev["ops"] if not CONTAINER.match(e[0])]
    if not ops:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": 0}
    everything = ops + dev["async"]
    if window is None:
        window = (min(e[1] for e in ops), max(e[2] for e in ops))
    lo, hi = window
    busy = clip(union((s, e) for _, s, e in ops), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    coll = clip(union(
        (s, e) for n, s, e in everything if COLLECTIVE.search(n)
    ), lo, hi)
    compute = clip(union(
        (s, e) for n, s, e in ops if not COLLECTIVE.search(n)
    ), lo, hi)
    by_name = {}
    for n, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            by_name[n] = by_name.get(n, 0.0) + d
    gap_by_name = {}
    for g in gaps:
        n = name_gap(g, host)
        gap_by_name[n] = gap_by_name.get(n, 0.0) + (g[1] - g[0])
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": hi - lo,
        "busy_s": total(busy),
        "ops": len(ops),
        "collective_s": total(coll),
        "collective_exposed_s": total(subtract(coll, compute)),
        "op_seconds": by_name,
        "device_ops": [[n, t] for n, t in rank(by_name)],
        "idle_gaps": [[n, t] for n, t in rank(gap_by_name)],
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0),
    }


def reduce_trace(path: str, annotations=(), window_annotation=None) -> dict:
    """All chips' figures: ``{"chips": {n: figures}}``.  With
    ``window_annotation``, the window on every chip is that host span (the
    first of that name) where the trace holds it."""
    wanted = tuple(annotations) + (
        (window_annotation,) if window_annotation else ()
    )
    trace = load(path, wanted)
    window = None
    if window_annotation:
        for name, s, e in trace["host"]:
            if name == window_annotation:
                window = (s, e)
                break
    host = [h for h in trace["host"] if h[0] != window_annotation]
    if window:
        # host and device planes share a clock only as well as the profiler
        # aligned them: where the ops do not fall inside the host's span,
        # trust the device's own first and last op instead
        spans = [
            (s, e) for dev in trace["devices"].values()
            for _, s, e in dev["ops"]
        ]
        inside = total(clip(union(spans), *window))
        if not spans or inside < 0.9 * total(union(spans)):
            window = None
    chips = {
        n: reduce_device(dev, host, window)
        for n, dev in sorted(trace["devices"].items())
    }
    return {"chips": chips, "window_from": "host" if window else "device"}
