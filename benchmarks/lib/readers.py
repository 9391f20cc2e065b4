"""What the per-layer metric readers share: where a run keeps things."""

import re

from lib import flops
from lib.peaks import peaks


def chip0(run):
    """The first chip's reduced trace, or None where nothing was traced."""
    trace = run.device_trace
    if not trace:
        return None
    chips = [c for _, c in sorted(trace["chips"].items()) if c.get("ops")]
    return chips[0] if chips else None


def idle_share(run):
    chip = chip0(run)
    if chip is None or chip["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - chip["busy_s"] / chip["window_s"])


def op_seconds(run, pattern: str):
    """Summed device time of the ops whose name matches ``pattern``."""
    chip = chip0(run)
    if chip is None:
        return None
    rx = re.compile(pattern)
    hits = [t for n, t in chip["op_seconds"].items() if rx.search(n)]
    return sum(hits) if hits else None


def span_seconds(run, name: str):
    hits = [end - start for n, start, end, _ in run.spans if n == name]
    return sum(hits) if hits else None


def counter(run, key: str):
    value = run.counters.get(key)
    return None if value is None else float(value)


FLASH_KERNELS = r"_attend"


def flash_roofline(run):
    """Attention's least possible time over the flash kernels' measured
    time, both over the traced steps on one chip."""
    kernel_s = op_seconds(run, FLASH_KERNELS)
    if not kernel_s or "traced_steps" not in run.facts:
        return None
    f = run.facts
    cost = flops.causal_attention_train_cost(f["rows"] // f["chips"], f["model"])
    layers_run = f["model"]["n_layers"] * f["traced_steps"]
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"flash kernels: {kernel_s * 1e3:.2f} ms over {f['traced_steps']} "
            f"steps against a least time of {least * layers_run * 1e3:.2f} ms "
            f"({bound}-bound)")
    return 100.0 * least * layers_run / kernel_s
