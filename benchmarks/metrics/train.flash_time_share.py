"""Kernels: `train.flash_time_share` (%), from device_trace; should move `train_tok_s_chip`."""

from lib import readers

META = {"name": "train.flash_time_share", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "train_tok_s_chip"}


def read(run):
    """Share of the first chip's busy time spent in the flash-attention
    kernels (forward, dq, dkv)."""
    chip = readers.chip0(run)
    kernels = readers.op_seconds(run, readers.FLASH_KERNELS)
    if chip is None or kernels is None or not chip["busy_s"]:
        return None
    return 100.0 * kernels / chip["busy_s"]
