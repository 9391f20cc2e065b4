"""Model: `moe.rows_per_expert_max_over_mean.longdoc` (ratio), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "moe.rows_per_expert_max_over_mean.longdoc", "layer": "Model", "unit": "ratio", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """The most-loaded (layer, expert)'s rows over the mean, over the
    window: the imbalance a dropless layer has to absorb."""
    return readers.counter(run, "moe_rows_per_expert_max_over_mean")
