"""Kernels: `moe.expert_matmul_roofline.reasoning` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import flops, latent_moe_cost
from lib.peaks import peaks

META = {"name": "moe.expert_matmul_roofline.reasoning", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """The routed experts' grouped matmuls (``ragged-dot`` ops: the streamed
    kernel's ``ragged-dot-streamed*`` or ``lax.ragged_dot``'s
    ``ragged-dot-none*``, whichever ``moe_plan`` chose a program shape):
    their least time (``lib/latent_moe_cost.py``: the TWO matrices of every
    expert a pass touches, its rows in and out at the latent width) for the
    passes the traced span held, over their measured time in it."""
    scopes, experts = run.facts.get("scopes"), run.facts.get("experts")
    traced = run.facts.get("traced_experts")
    if not scopes or not experts or not traced or not traced["calls"]:
        return None
    if "latent" not in experts:
        return None
    kernels = scopes["ragged-dot"]
    if not kernels["events"] or not kernels["seconds"]:
        return None
    cost = latent_moe_cost.routed_experts_cost(
        traced["held_rows"], traced["touched"], experts
    )
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"routed-expert matmuls: {kernels['seconds'] * 1e3:.2f} ms in "
            f"{kernels['events']} kernels = {traced['calls']:.1f} passes "
            f"({traced['held_rows']:.0f} rows, {traced['touched']:.0f} experts "
            f"touched): least time {least * 1e3:.2f} ms ({bound}-bound)")
    return 100.0 * least / kernels["seconds"]
