"""Kernels: `mla.prefill_attention_roofline.longdoc` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import flops, mla_cost
from lib.peaks import peaks

META = {"name": "mla.prefill_attention_roofline.longdoc", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """The flash kernel of the prefill calls (ops under ``mla.scores/flash``:
    scores at 192, values at 128): the least time of the causal half of
    ``2 x heads x (192 + 128) x S^2`` FLOPs a call and layer, ``S`` the REAL
    length of each call's prompt (``lib/mla_cost.py``; padding reads as lost
    share), summed over the calls that ran in the traced span
    (``mla_cost.span_work``), over the measured time of the ops under that
    scope (the kernel and the copies XLA puts in
    front of it to lay its operands out)."""
    mla, span = run.facts.get("mla"), run.facts.get("span_mla")
    flash, _ = mla_cost.scope(run, r"mla\.scores/flash")
    if not mla or not span or not span["flash_sum_sq"] or not flash:
        return None
    if not flash["events"] or not flash["seconds"]:
        return None
    cost = mla_cost.prefill_attention_cost(span["flash_sum_sq"], mla)
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"prefill attention: {flash['seconds'] * 1e3:.2f} ms in "
            f"{flash['events']} ops (the kernel and the copies that lay its "
            f"operands out); the trace holds {span['prefill_calls']:.2f} "
            f"prefill calls ({span['prefill_tokens']:.0f} real tokens) of "
            f"{mla['layers']} layers: least time "
            f"{least * 1e3:.2f} ms "
            f"({bound}-bound)")
    return 100.0 * least / flash["seconds"]
