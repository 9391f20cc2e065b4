"""Trainer: `train.data_wait_share` (%), from program_span; should move `train_tok_s_chip`."""

from lib import readers

META = {"name": "train.data_wait_share", "layer": "Trainer", "unit": "%", "source": "program_span", "moves": "train_tok_s_chip"}


def read(run):
    """Share of the traced steps the loop spent waiting for its batch."""
    wait = readers.span_seconds(run, "data_wait")
    compute = readers.span_seconds(run, "compute")
    if wait is None or not compute:
        return None
    return 100.0 * wait / (wait + compute)
