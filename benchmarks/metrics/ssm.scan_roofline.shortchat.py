"""Kernels: `ssm.scan_roofline.shortchat` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import flops, ssm_cost
from lib.peaks import peaks

META = {"name": "ssm.scan_roofline.shortchat", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """The chunked scan of the recurrent layers (ops under ``ssm.scan``): its
    least time, for the REAL prompt tokens of the prefill calls the trace
    itself holds (``ssm_cost.span_work``: calls counted by shape in the
    trace, pads and dummy rows taken off by the window's own share), over
    its measured time in the trace."""
    scopes, ssm = run.facts.get("scopes"), run.facts.get("ssm")
    span = run.facts.get("span_ssm")
    if not scopes or not ssm or not span or not span["prompt_tokens"]:
        return None
    scan = scopes[r"ssm\.scan"]
    if not scan["events"] or not scan["seconds"]:
        return None
    cost = ssm_cost.scan_cost(span["prompt_tokens"], span["prompts"], ssm)
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"scan: {scan['seconds'] * 1e3:.2f} ms in {scan['events']} ops; "
            f"the trace holds prefill calls {span['prefill_calls']} = "
            f"{span['positions']} positions, {span['prompt_tokens']:.0f} real "
            f"prompt tokens in {span['prompts']:.1f} prompts: least time "
            f"{least * 1e3:.2f} ms ({bound}-bound)")
    return 100.0 * least / scan["seconds"]
