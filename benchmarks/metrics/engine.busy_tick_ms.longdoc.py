"""Serving engine: `engine.busy_tick_ms.longdoc` (ms), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.busy_tick_ms.longdoc", "layer": "Serving engine", "unit": "ms", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Mean wall time of a tick that ran work, by the pump's phase clock."""
    return readers.counter(run, "busy_tick_ms_mean")
