"""Model: `moe.rows_per_expert_max_over_mean.longshort` (ratio), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "moe.rows_per_expert_max_over_mean.longshort", "layer": "Model", "unit": "ratio", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Rows of the busiest held expert over the mean, summed over the
    window's calls: the imbalance the grouped matmuls saw."""
    return readers.counter(run, "moe_rows_per_expert_max_over_mean")
