"""Pluggable telemetry exporters: Chrome trace-event JSON (Perfetto) and
Prometheus text exposition.

Both read the SAME two sources — a :class:`~tpu_parallel.obs.tracer.
Tracer`'s span list and a :class:`~tpu_parallel.obs.registry.
MetricRegistry` snapshot — so adding an exporter never means adding
instrumentation.

Chrome trace mapping: one trace **process** per export, one **thread**
(tid) per tracer track — the serving engine's layout comes out as one
row per cache slot plus a ``scheduler`` row, which is exactly how
Perfetto renders a slot pool legibly.  Complete spans emit ``X`` events;
async spans (overlapping queue waits) emit ``b``/``e`` nestable pairs
keyed by request id; instants emit thread-scoped ``i`` markers.
Timestamps are microseconds (the trace-event contract).
"""

from __future__ import annotations

import json
import re
from typing import Dict, List, Union

from tpu_parallel.obs.registry import MetricRegistry
from tpu_parallel.obs.tracer import Tracer

# -- Chrome trace-event JSON (Perfetto / chrome://tracing) -----------------


def chrome_trace_events(tracer: Tracer, pid: int = 1) -> List[Dict]:
    """Flatten a tracer into trace-event dicts (metadata + spans +
    instants).  Unfinished spans close at the last timestamp seen, so a
    trace from an aborted run still loads."""
    events: List[Dict] = [
        {
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": "tpu_parallel"},
        }
    ]
    tids = {track: i for i, track in enumerate(tracer.tracks())}
    for track, tid in tids.items():
        events.append(
            {
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": track},
            }
        )
        # tid order == tracks() order (scheduler first, slots sorted)
        events.append(
            {
                "ph": "M", "name": "thread_sort_index", "pid": pid,
                "tid": tid, "args": {"sort_index": tid},
            }
        )
    ends = [s.end for s in tracer.spans if s.end is not None]
    ends += [s.start for s in tracer.spans]
    ends += [ev["ts"] for ev in tracer.instants]
    last_ts = max(ends) if ends else 0.0
    for span in tracer.spans:
        tid = tids[span.track]
        start_us = span.start * 1e6
        end = span.end if span.end is not None else last_ts
        args = dict(span.attrs)
        if span.async_id is not None:
            common = {
                "cat": "async", "id": str(span.async_id),
                "name": span.name, "pid": pid, "tid": tid,
            }
            events.append({"ph": "b", "ts": start_us, "args": args, **common})
            events.append({"ph": "e", "ts": end * 1e6, **common})
        else:
            events.append(
                {
                    "ph": "X", "cat": "span", "name": span.name,
                    "pid": pid, "tid": tid, "ts": start_us,
                    "dur": max(0.0, (end - span.start) * 1e6),
                    "args": args,
                }
            )
    for ev in tracer.instants:
        events.append(
            {
                "ph": "i", "s": "t", "cat": "instant", "name": ev["name"],
                "pid": pid, "tid": tids[ev["track"]], "ts": ev["ts"] * 1e6,
                "args": dict(ev["attrs"]),
            }
        )
    return events


def write_chrome_trace(tracer: Tracer, path: str) -> str:
    """Dump the tracer as a Perfetto-openable trace file; returns
    ``path``."""
    with open(path, "w") as fh:
        json.dump(
            {
                "traceEvents": chrome_trace_events(tracer),
                "displayTimeUnit": "ms",
            },
            fh,
        )
    return path


# -- Prometheus text exposition --------------------------------------------

_NAME_OK = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    name = _NAME_OK.sub("_", name)
    return name if not name[:1].isdigit() else "_" + name


def _prom_labels(labels: Dict[str, str], extra: Dict[str, str] = None) -> str:
    items = dict(labels)
    if extra:
        items.update(extra)
    if not items:
        return ""
    body = ",".join(
        '{}="{}"'.format(
            _prom_name(k),
            str(v).replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n"),
        )
        for k, v in sorted(items.items())
    )
    return "{" + body + "}"


def _prom_value(v) -> str:
    if v is None:
        return "NaN"
    f = float(v)
    return repr(int(f)) if f == int(f) else repr(f)


def prometheus_lines(snapshot: Dict) -> List[str]:
    """Render a registry snapshot as Prometheus text-exposition lines
    (``# TYPE`` headers + one sample per line; histograms expand to
    cumulative ``_bucket{le=...}`` series plus ``_sum``/``_count``)."""
    lines: List[str] = []
    typed: set = set()

    def header(name: str, kind: str) -> None:
        if name not in typed:
            typed.add(name)
            lines.append(f"# TYPE {name} {kind}")

    for row in snapshot.get("counters", []):
        name = _prom_name(row["name"])
        header(name, "counter")
        lines.append(
            f"{name}{_prom_labels(row['labels'])} {_prom_value(row['value'])}"
        )
    for row in snapshot.get("gauges", []):
        name = _prom_name(row["name"])
        header(name, "gauge")
        lines.append(
            f"{name}{_prom_labels(row['labels'])} {_prom_value(row['value'])}"
        )
    for row in snapshot.get("histograms", []):
        name = _prom_name(row["name"])
        header(name, "histogram")
        labels = row["labels"]
        for edge, cum in row["buckets"]:
            lines.append(
                f"{name}_bucket"
                f"{_prom_labels(labels, {'le': _prom_value(edge)})} {cum}"
            )
        lines.append(
            f"{name}_bucket{_prom_labels(labels, {'le': '+Inf'})} "
            f"{row['count']}"
        )
        lines.append(
            f"{name}_sum{_prom_labels(labels)} {_prom_value(row['sum'])}"
        )
        lines.append(f"{name}_count{_prom_labels(labels)} {row['count']}")
    return lines


def prometheus_text(source: Union[MetricRegistry, Dict]) -> str:
    snap = source.snapshot() if isinstance(source, MetricRegistry) else source
    return "\n".join(prometheus_lines(snap)) + "\n"


def _prom_unescape(value: str) -> str:
    """Invert the exposition-format label-value escaping (backslash,
    double-quote, newline) — a left-to-right scan, NOT chained
    ``str.replace`` (which would corrupt ``\\\\n`` into a newline)."""
    out: List[str] = []
    i = 0
    while i < len(value):
        ch = value[i]
        if ch == "\\" and i + 1 < len(value):
            nxt = value[i + 1]
            if nxt == "n":
                out.append("\n")
            elif nxt in ('"', "\\"):
                out.append(nxt)
            else:  # unknown escape: keep it verbatim
                out.append(ch)
                out.append(nxt)
            i += 2
        else:
            out.append(ch)
            i += 1
    return "".join(out)


_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?"
    r"\s+(?P<value>\S+)\s*$"
)
_LABEL_RE = re.compile(
    r'\s*(?P<key>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<val>(?:[^"\\]|\\.)*)"\s*(?:,|$)'
)


def parse_prometheus_text(text: str) -> List[Dict]:
    """Parse text exposition back into samples:
    ``[{"name", "labels", "value", "type"}]``.  The label values are
    UNescaped, so this round-trips :func:`prometheus_lines` exactly —
    the fleet aggregator relabels peer series through it, and the
    round-trip is the escaping regression test's oracle.  Unparseable
    lines raise ``ValueError`` (an aggregator must not silently drop a
    peer's series)."""
    samples: List[Dict] = []
    types: Dict[str, str] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"unparseable exposition line: {raw!r}")
        labels: Dict[str, str] = {}
        body = m.group("labels")
        if body:
            pos = 0
            while pos < len(body):
                lm = _LABEL_RE.match(body, pos)
                if lm is None:
                    raise ValueError(
                        f"unparseable label body in line: {raw!r}"
                    )
                labels[lm.group("key")] = _prom_unescape(lm.group("val"))
                pos = lm.end()
        name = m.group("name")
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                base = name[: -len(suffix)]
                break
        samples.append({
            "name": name,
            "labels": labels,
            "value": float(m.group("value")),
            "type": types.get(base),
        })
    return samples


def write_prometheus(source: Union[MetricRegistry, Dict], path: str) -> str:
    """Write one text-exposition snapshot (node-exporter textfile style —
    point a file scrape at it, or re-export per tick for a live series);
    returns ``path``."""
    with open(path, "w") as fh:
        fh.write(prometheus_text(source))
    return path
