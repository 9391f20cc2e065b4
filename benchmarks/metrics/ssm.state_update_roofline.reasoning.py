"""Kernels: `ssm.state_update_roofline.reasoning` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import flops, ssm_cost
from lib.peaks import peaks

META = {"name": "ssm.state_update_roofline.reasoning", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """The one-token state update of the recurrent layers (ops under
    ``ssm.step``): its least time (``lib/ssm_cost.py``, 8 groups of B and C),
    for the decode steps the trace itself holds times the slots live in a
    step (``ssm_cost.span_work``), over its measured time in the trace."""
    scopes, ssm = run.facts.get("scopes"), run.facts.get("ssm")
    span = run.facts.get("span_ssm")
    if not scopes or not ssm or not span or not span["slot_steps"]:
        return None
    step = scopes[r"ssm\.step"]
    if not step["events"] or not step["seconds"]:
        return None
    cost = ssm_cost.state_update_cost(span["slot_steps"], ssm)
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"state update: {step['seconds'] * 1e3:.2f} ms in {step['events']} "
            f"ops; the trace holds {span['decode_steps']:.2f} decode steps of "
            f"{span['live_slots']:.2f} live slots = {span['slot_steps']:.0f} "
            f"slot-steps: least time {least * 1e3:.2f} ms ({bound}-bound)")
    return 100.0 * least / step["seconds"]
