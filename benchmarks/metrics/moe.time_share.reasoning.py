"""Model: `moe.time_share.reasoning` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import scope_share

META = {"name": "moe.time_share.reasoning", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time spent in the expert layers: ops
    under the ``moe.*`` scopes (router, latent projections, the routed
    experts' gathers and sums, the shared expert) and the grouped matmuls
    (``ragged-dot`` ops), over the traced span."""
    return scope_share.read(run, r"moe\.|ragged-dot")
