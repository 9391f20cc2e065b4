"""Serving engine: `engine.out_tok_s.batch` (tokens/s), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.out_tok_s.batch", "layer": "Serving engine", "unit": "tokens/s", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Tokens the engine produced between window open and close
    (`ServingMetrics.tokens_out` after `reset_metrics()`), a second."""
    tokens = readers.counter(run, "tokens_out")
    return None if tokens is None or run.seconds <= 0 else tokens / run.seconds
