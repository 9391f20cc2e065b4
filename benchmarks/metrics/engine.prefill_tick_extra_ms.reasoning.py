"""Serving engine: `engine.prefill_tick_extra_ms.reasoning` (ms), from program_counter; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "engine.prefill_tick_extra_ms.reasoning", "layer": "Serving engine", "unit": "ms", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """What carrying an admission (a whole-prompt prefill) adds to a busy
    tick."""
    with_prefill = readers.counter(run, "prefill_tick_ms_mean")
    decode_only = readers.counter(run, "decode_only_tick_ms_mean")
    if with_prefill is None or decode_only is None:
        return None
    return with_prefill - decode_only
