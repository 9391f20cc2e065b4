"""Serving engine: `engine.device_idle_share` (%), from program_counter; should move `serve_out_tok_s`."""

from lib import clock_counters

META = {"name": "engine.device_idle_share", "layer": "Serving engine", "unit": "%", "source": "program_counter", "moves": "serve_out_tok_s"}


def read(run):
    """Seconds in which no program of the engine ran, over the window's
    elapsed time, by the completion clock: the whole window, where
    `device.idle_share.*` reads the traced seconds; nothing where the
    program has no such clock."""
    return clock_counters.percent(run, "device_idle_share")
