"""Kernels: `mla.decode_attention_roofline.longdoc` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import flops, mla_cost
from lib.peaks import peaks

META = {"name": "mla.decode_attention_roofline.longdoc", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """The absorbed form's products against the stored rows in the tick (the
    XLA ops under ``mla.scores/stored``): the least time of the stored rows
    read (``lib/mla_cost.py``: 278,528 FLOPs and 1,152 bytes a row, the larger
    of the two bounds), the rows summed over the ticks that ran in the traced
    span, each with the rows it read (``mla_cost.span_work``), over their
    measured time in it.  It is the number a decode kernel over the latent
    rows starts from."""
    mla, span = run.facts.get("mla"), run.facts.get("span_mla")
    stored, _ = mla_cost.scope(run, r"mla\.scores/stored")
    if not mla or not span or not span["stored_rows"] or not stored:
        return None
    if not stored["events"] or not stored["seconds"]:
        return None
    cost = mla_cost.stored_rows_cost(span["stored_rows"], mla)
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"decode attention: {stored['seconds'] * 1e3:.2f} ms in "
            f"{stored['events']} ops; the trace holds "
            f"{span['decode_steps']:.2f} decode steps that read "
            f"{span['stored_rows']:.0f} stored rows (over the latent "
            f"layers): least time "
            f"{least * 1e3:.2f} ms ({bound}-bound)")
    return 100.0 * least / stored["seconds"]
