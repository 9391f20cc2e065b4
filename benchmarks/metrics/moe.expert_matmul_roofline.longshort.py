"""Kernels: `moe.expert_matmul_roofline.longshort` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import flops, moe_cost
from lib.peaks import peaks

META = {"name": "moe.expert_matmul_roofline.longshort", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """The routed experts' grouped matmuls (``ragged-dot`` ops, three a
    call): their least time, from what the program's counters gained while
    the trace ran, over their measured time in it."""
    scopes, experts = run.facts.get("scopes"), run.facts.get("experts")
    traced = run.facts.get("traced_experts")
    if not scopes or not experts or not traced or not traced["calls"]:
        return None
    kernels = scopes["ragged-dot"]
    if not kernels["events"] or not kernels["seconds"]:
        return None
    cost = moe_cost.routed_experts_cost(
        traced["held_rows"], traced["touched"], experts
    )
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"routed-expert matmuls: {kernels['seconds'] * 1e3:.2f} ms in "
            f"{kernels['events']} kernels; the counters saw "
            f"{traced['calls']} calls meanwhile ({traced['held_rows']} rows, "
            f"{traced['touched']:.0f} experts touched): least time "
            f"{least * 1e3:.2f} ms ({bound}-bound)")
    return 100.0 * least / kernels["seconds"]
