"""What the readers of the engine's completion clock share: a counter of
``summary()`` read so that nothing the program left there can raise, and the
split by compiled shape written to the run's log."""

import json
import math


def number(run, key: str):
    """``run.counters[key]`` as a finite float; None where the key is absent
    (a program without the clock), None, not a number or not finite."""
    value = (run.counters or {}).get(key)
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return None
    value = float(value)
    return value if math.isfinite(value) else None


def percent(run, key: str):
    share = number(run, key)
    return None if share is None else 100.0 * share


def log_by_shape(run) -> None:
    """One line: ``"<program> <shape>" -> [calls, seconds]`` over the whole
    window, as the clock left it; nothing where it left none."""
    by_shape = (run.counters or {}).get("device_by_shape")
    if not isinstance(by_shape, dict) or not by_shape:
        return
    try:
        run.log("device_by_shape " + json.dumps(by_shape, sort_keys=True))
    except (TypeError, ValueError):
        pass  # not what the clock writes: the metrics are read without it
