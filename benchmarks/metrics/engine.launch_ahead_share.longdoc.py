"""Serving engine: `engine.launch_ahead_share.longdoc` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.launch_ahead_share.longdoc", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Busy ticks `step()` launched with their predecessor still on the
    device, over busy ticks; nothing where the program has no such counter."""
    value = readers.counter(run, "launch_ahead_share")
    return None if value is None else 100.0 * value
