"""SPMD step: `train.step_device_ms` (ms), from device_trace; should move `train_tok_s_chip`."""

from lib import readers

META = {"name": "train.step_device_ms", "layer": "SPMD step", "unit": "ms", "source": "device_trace", "moves": "train_tok_s_chip"}


def read(run):
    """Time a traced step keeps the first chip busy."""
    chip = readers.chip0(run)
    if chip is None or not run.facts.get("traced_steps"):
        return None
    return 1e3 * chip["busy_s"] / run.facts["traced_steps"]
