"""Model: `moe.experts_touched.longdoc` (experts), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "moe.experts_touched.longdoc", "layer": "Model", "unit": "experts", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Experts that got at least one row, a layer's pass (of 16 held): what
    a decode step has to read of the expert weights."""
    return readers.counter(run, "moe_experts_touched_mean")
