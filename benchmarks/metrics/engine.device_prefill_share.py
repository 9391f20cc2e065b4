"""Serving engine: `engine.device_prefill_share` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import clock_counters

META = {"name": "engine.device_prefill_share", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Device seconds of the whole-prompt prefill and extend programs over
    the window's elapsed time, by the completion clock.  An UPPER bound,
    from host stamps: the unwatched programs behind a call (row scatter,
    first-token sampler, seat) are in it, and a late stamp moves time from
    the tick behind to the call; only prefill + tick seconds together hold
    to the trace.  Nothing where the program has no such clock.  Writes the
    clock's split by compiled shape to the run's log."""
    clock_counters.log_by_shape(run)
    return clock_counters.percent(run, "device_prefill_share")
