"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  It finds the cell in
``BENCHMARK.json``, reads the cell's, the configuration's and the traffic
mix's data files, and hands them to the driver the cell names
(``benchmarks/drivers/<driver>.py``).  The driver builds the system under
test, makes weights and traffic from ``--seed``, warms up the cell's own
shapes (set-up), measures for ``--seconds``, and compares what the timed path
produced with the plain reference.  Earlier lines are free; the LAST line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and, traced, ``breakdown``.

No TPU, or fewer chips than the cell asks for: exit code 1, no result line.
A compile inside the window, or a failed comparison: ``correct`` is false.
``--control 1`` (never passed by the driver of the PRs) also reads the
lower-precision control's numbers after the window, for setting limits.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(msg, flush=True)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path: metric readers and drivers are found by the
    name the manifest gives, and a name may hold dots."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no such file: {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileCounter:
    """Counts programs that reach the backend compiler (or its persistent
    cache) while ``active``: inside the window there may be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.count += 1


class Run:
    """What a driver fills in and a metric reader reads."""

    def __init__(self, root, bench_dir, manifest, entry, seed, seconds, trace,
                 control=False):
        self.root = root
        self.bench_dir = bench_dir
        self.manifest = manifest
        self.entry = entry  # the cell's entry in BENCHMARK.json
        self.name = entry["name"]
        self.cell = read_json(
            os.path.join(bench_dir, "workloads", f"{self.name}.json")
        )
        cfg_entry = next(
            c for c in manifest["configs"] if c["name"] == entry["config"]
        )
        self.config = read_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = read_json(
            os.path.join(bench_dir, "traffic", f"{entry['traffic']}.json")
        )
        self.seed = seed
        self.seconds = seconds
        self.trace = bool(trace)
        self.control = bool(control)
        self.t_process = T_PROCESS
        self.compiles = None  # CompileCounter, set by run_cell
        # filled by the driver
        self.values = {}  # end-to-end values by metric name
        self.spans = []  # program spans: (name, start_s, end_s, attrs)
        self.counters = {}  # program counters by name
        self.samples = {}  # client-side samples by name
        self.device_trace = None  # lib.xplane.reduce_trace(...)
        self.facts = {}  # shapes and counts a reader needs (rows, steps...)
        self.checks = []  # {"name", "value", "limit", "ok"}
        self.attempted = 0
        self.failed = 0
        self.program_bytes = 0  # largest program by memory_analysis()
        self.memory_peak_bytes = 0

    log = staticmethod(log)

    def check(self, name: str, value: float, limit: float) -> bool:
        """Record one compared number beside its limit; ``value <= limit``
        passes (an exact comparison has the limit 0)."""
        ok = bool(value <= limit)  # NaN fails
        self.checks.append(
            {"name": name, "value": value, "limit": limit, "ok": ok}
        )
        log(f"check {name}: {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if ok else 'FAILED'}")
        return ok

    def read_memory(self) -> None:
        """Peak bytes on the fullest chip: the runtime's high-water mark,
        or the largest program's own ``memory_analysis()`` where that is
        more (this runtime's counter leaves a program's temporaries out)."""
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        ]
        self.memory_peak_bytes = int(max(peaks + [self.program_bytes]))


def device_record(chips: int, check_device: bool) -> dict:
    import jax

    devices = jax.devices()
    record = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if check_device:
        if record["platform"] != "tpu":
            raise SystemExit(
                f"no accelerator: JAX found {record['count']} x "
                f"{record['platform']!r}; this benchmark measures the chip "
                "and does not fall back"
            )
        if record["count"] < chips:
            raise SystemExit(
                f"the cell asks for {chips} chips, JAX found {record['count']}"
            )
    record["count"] = chips  # the chips this cell uses
    return record


def per_layer_metrics(run: Run) -> dict:
    """Every per-layer metric that lists this cell (or lists none and moves
    an end-to-end metric the cell reports), read by its own reader."""
    reported = set(run.cell["end_to_end"])
    out = {}
    for m in run.manifest["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and run.name not in cells:
            continue
        if cells is None and m["moves"] not in reported:
            continue
        reader = load_module(
            os.path.join(run.bench_dir, "metrics", f"{m['name']}.py"),
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"),
        )
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(name, seed, seconds, trace, control=False, check_device=True,
             bench_dir=BENCH_DIR, root=ROOT) -> dict:
    """Drive one run of one cell; returns the result object."""
    manifest = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"no cell {name!r} in BENCHMARK.json; cells: "
            f"{[w['name'] for w in manifest['workloads']]}"
        )
    device = device_record(entry["chips"], check_device)
    from tpu_parallel.runtime import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    run = Run(root, bench_dir, manifest, entry, seed, seconds, trace, control)
    run.compiles = CompileCounter()
    run.device = device
    log(f"cell {name}: config {entry['config']}, traffic {entry['traffic']}, "
        f"{entry['chips']} chip(s), seed {seed}, window {seconds}s, "
        f"trace {int(run.trace)}; device {device}; compile cache {cache_dir}")

    driver = load_module(
        os.path.join(bench_dir, "drivers", f"{run.cell['driver']}.py"),
        f"bench_driver_{run.cell['driver']}",
    )
    driver.run(run)

    run.check("compiles_in_window", run.compiles.count, 0)
    correct = bool(run.checks) and all(c["ok"] for c in run.checks)
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    if run.trace:
        metrics = per_layer_metrics(run)
    else:
        metrics = {
            k: {"value": float(run.values[k]), "unit": units[k]}
            for k in run.cell["end_to_end"]
        }
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    result = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace and run.device_trace:
        chips = run.device_trace["chips"]
        used = [c for c in chips.values() if c["ops"]]
        if used:
            device["busy_s"] = sum(c["busy_s"] for c in used) / len(used)
            device["window_s"] = sum(c["window_s"] for c in used) / len(used)
            first = used[0]
            result["breakdown"] = {
                "device_ops": first["device_ops"],
                "idle_gaps": first["idle_gaps"],
            }
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(
        args.workload, args.seed, args.seconds, args.trace, args.control
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
