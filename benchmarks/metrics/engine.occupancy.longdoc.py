"""Serving engine: `engine.occupancy.longdoc` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.occupancy.longdoc", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    value = readers.counter(run, "slot_occupancy_mean")
    return None if value is None else 100.0 * value
