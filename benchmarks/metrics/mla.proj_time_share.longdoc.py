"""Model: `mla.proj_time_share.longdoc` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import mla_cost

META = {"name": "mla.proj_time_share.longdoc", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time spent in latent attention's
    projections: ops under ``mla.q_proj``, ``mla.kv_down``, ``mla.kv_up``
    (prefill's expansion), ``mla.absorb`` (decode's two per-head products)
    and ``mla.out_proj``, over the traced span."""
    return mla_cost.share(run, r"mla\.(q_proj|kv_down|kv_up|absorb|out_proj)")
