"""Model: `attn.block_time_share.blockgen` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import scope_share

META = {"name": "attn.block_time_share.blockgen", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time under the scope ``attn.block``:
    the block step's attention over a slot's stripe and a prefill's flash
    kernels, both under the block rule."""
    return scope_share.read(run, r"attn\.block")
