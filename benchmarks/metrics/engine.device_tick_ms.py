"""Serving engine: `engine.device_tick_ms` (ms), from program_counter; should move `serve_out_tok_s`."""

from lib import clock_counters

META = {"name": "engine.device_tick_ms", "layer": "Serving engine", "unit": "ms", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """The device time of one decode tick alone: mean of the completion
    clock's `serving_device_seconds{program="tick"}` over the window (a tick
    that carried no prompt tokens, from its predecessor's completion, or its
    own dispatch where the device was idle, to its own).  From host stamps:
    a stamp that comes late after the prefill before it shortens the tick by
    as much, so tick and prefill seconds trade and their sum holds.  Nothing
    where the program has no such clock."""
    return clock_counters.number(run, "device_tick_ms_mean")
