"""Kernels: `train.flash_attention_roofline` (%), from device_trace; should move `train_tok_s_chip`."""

from lib import readers

META = {"name": "train.flash_attention_roofline", "layer": "Kernels", "unit": "%", "source": "device_trace", "moves": "train_tok_s_chip"}


def read(run):
    return readers.flash_roofline(run)
