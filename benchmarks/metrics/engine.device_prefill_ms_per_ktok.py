"""Serving engine: `engine.device_prefill_ms_per_ktok` (ms/ktok), from program_counter; should move `serve_out_tok_s`."""

from lib import clock_counters

META = {"name": "engine.device_prefill_ms_per_ktok", "layer": "Serving engine", "unit": "ms/ktok", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Device milliseconds of prefill a 1000 REAL prompt tokens: the pads
    of a bucket and the weight stream of a one-row call are in it.  An UPPER
    bound, from host stamps: the row scatter, first-token sampler and seat
    programs behind a call are charged to it, and a late stamp moves time
    between a call and its neighbour (a call under 20 ms can read half as
    much again as the trace does; `scripts/device_clock_check.py` gives the
    clock against the trace).  Nothing where the program has no such clock."""
    return clock_counters.number(run, "device_prefill_ms_per_ktok")
