"""Serving engine: `engine.between_ticks_ms.shortchat` (ms), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.between_ticks_ms.shortchat", "layer": "Serving engine", "unit": "ms", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    return readers.counter(run, "tick_between_ms_mean")
