"""Serving engine: `engine.prefill_pad_share.longdoc` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.prefill_pad_share.longdoc", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Positions the prefill programs computed that were no prompt token
    (bucket padding), over all they computed; nothing where the program has
    no such counters."""
    real = readers.counter(run, "prefill_tokens_real")
    padded = readers.counter(run, "prefill_tokens_padded")
    if real is None or padded is None or real + padded <= 0:
        return None
    return 100.0 * padded / (real + padded)
