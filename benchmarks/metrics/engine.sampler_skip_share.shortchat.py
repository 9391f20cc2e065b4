"""Serving engine: `engine.sampler_skip_share.shortchat` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.sampler_skip_share.shortchat", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Busy ticks in which no slot's owner was sampled, so that the sampler
    took the argmax alone (no sort, no draw), over busy ticks; nothing where
    the program has no such counter."""
    value = readers.counter(run, "sampler_skip_share")
    return None if value is None else 100.0 * value
