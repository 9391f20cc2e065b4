"""Model: `moe.time_share.longshort` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "moe.time_share.longshort", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time spent in the expert layers: ops
    under the ``moe.*`` scopes (router, routed experts' gathers and sums,
    shared experts) and the grouped matmuls (``ragged-dot`` custom calls,
    which carry no scope), over the traced span."""
    scopes = run.facts.get("scopes")
    if not scopes or not scopes.get("busy_s"):
        return None
    return 100.0 * scopes[r"moe\.|ragged-dot"]["seconds"] / scopes["busy_s"]
