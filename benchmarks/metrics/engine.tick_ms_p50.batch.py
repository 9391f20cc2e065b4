"""Serving engine: `engine.tick_ms_p50.batch` (ms), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.tick_ms_p50.batch", "layer": "Serving engine", "unit": "ms", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    return readers.counter(run, "host_ms_per_tick_p50")
