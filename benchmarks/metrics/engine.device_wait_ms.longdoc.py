"""Serving engine: `engine.device_wait_ms.longdoc` (ms), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.device_wait_ms.longdoc", "layer": "Serving engine", "unit": "ms", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    return readers.counter(run, "tick_device_wait_ms_mean")
