"""Serving engine: `engine.host_exposed_share.batch` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.host_exposed_share.batch", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    return readers.counter(run, "host_exposed_share")
