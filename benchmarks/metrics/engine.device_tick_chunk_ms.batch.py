"""Serving engine: `engine.device_tick_chunk_ms.batch` (ms), from program_counter; should move `serve_out_tok_s`."""

from lib import clock_counters

META = {"name": "engine.device_tick_chunk_ms.batch", "layer": "Serving engine", "unit": "ms", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """The device time of the unified tick when it carries a prompt chunk,
    by the completion clock; less `engine.device_tick_ms` it is what a chunk
    costs.  Nothing where the program has no such clock.  This cell has no
    `engine.device_prefill_share`, so the clock's split by compiled shape
    reaches the run's log from here."""
    clock_counters.log_by_shape(run)
    return clock_counters.number(run, "device_tick_chunk_ms_mean")
