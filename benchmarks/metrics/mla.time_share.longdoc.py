"""Model: `mla.time_share.longdoc` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import mla_cost

META = {"name": "mla.time_share.longdoc", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time spent under ``attn.latent``: all
    of the latent attention layers (the two low-rank paths, their norms and
    rotary parts, the expansion or the absorbed products, the attention core
    of either form, the output projection), over the traced span."""
    return mla_cost.share(run, r"attn\.latent")
