"""Kernels: `moe.expert_matmul_roofline.longdoc` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import flops, mla_cost, moe_cost
from lib.peaks import peaks

META = {"name": "moe.expert_matmul_roofline.longdoc", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """The routed experts' grouped matmuls (``ragged-dot`` ops: the streamed
    kernel's ``ragged-dot-streamed*`` or ``lax.ragged_dot``'s
    ``ragged-dot-none*``, whichever ``moe_plan`` chose a program shape):
    their least time (``lib/moe_cost.py`` at ``d_model`` 7680 and ``width``
    2048: the three matrices of every expert a pass touches, its rows in and
    out) for the passes the traced span held, over their measured time in
    it."""
    experts, traced = run.facts.get("experts"), run.facts.get("traced_experts")
    kernels, _ = mla_cost.scope(run, "ragged-dot")
    if not experts or not traced or not traced["calls"] or not kernels:
        return None
    if not kernels["events"] or not kernels["seconds"]:
        return None
    cost = moe_cost.routed_experts_cost(
        traced["held_rows"], traced["touched"], experts
    )
    least, bound = flops.roofline_seconds(cost, peaks(run.device["kind"]))
    run.log(f"routed-expert matmuls: {kernels['seconds'] * 1e3:.2f} ms in "
            f"{kernels['events']} kernels = {traced['calls']:.1f} passes "
            f"({traced['held_rows']:.0f} rows, {traced['touched']:.0f} experts "
            f"touched): least time {least * 1e3:.2f} ms ({bound}-bound)")
    return 100.0 * least / kernels["seconds"]
