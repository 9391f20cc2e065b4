"""Serving engine: `diffusion.commit_forward_share.blockgen` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "diffusion.commit_forward_share.blockgen", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Live slot-steps that were a block's commit pass (the clean block fed
    once more to write its final K/V), over live slot-steps."""
    value = readers.counter(run, "commit_forward_share")
    return None if value is None else 100.0 * value
