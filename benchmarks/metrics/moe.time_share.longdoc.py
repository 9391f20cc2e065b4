"""Model: `moe.time_share.longdoc` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import mla_cost

META = {"name": "moe.time_share.longdoc", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time spent in the expert layers: ops
    under the ``moe.*`` scopes (router, the routed experts' gathers and sums,
    the shared expert) and the grouped matmuls (``ragged-dot`` ops), over the
    traced span; the ``lax.cond`` over the experts' two buffers is an op of
    its own there whose branch's ops are events beside it, so its time is
    taken off."""
    found, busy = mla_cost.scope(run, r"moe\.|ragged-dot")
    conds, _ = mla_cost.scope(run, r"moe\.experts/cond(:|$)")
    if found is None:
        return None
    enclosing = conds["seconds"] if conds else 0.0
    return 100.0 * (found["seconds"] - enclosing) / busy
