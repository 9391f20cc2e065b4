"""Serving engine: `diffusion.unmask_time_share.blockgen` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import scope_share

META = {"name": "diffusion.unmask_time_share.blockgen", "layer": "Serving engine", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time under the scope
    ``diffusion.unmask``: each position's pick and confidence over the
    vocabulary, and the choice among the block's masked positions."""
    return scope_share.read(run, r"diffusion\.unmask")
