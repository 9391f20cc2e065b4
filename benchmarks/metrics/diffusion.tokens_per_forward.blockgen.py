"""Serving engine: `diffusion.tokens_per_forward.blockgen` (tokens/forward), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "diffusion.tokens_per_forward.blockgen", "layer": "Serving engine", "unit": "tokens/forward", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Positions filled over live slot-steps (a slot's block fed once): 1.0
    at one position a forward, L / T on a slot at T steps a block, less by
    the commit passes, which fill nothing."""
    return readers.counter(run, "tokens_per_forward")
