"""Device: `device.idle_share.train` (%), from device_trace; should move `train_tok_s_chip`."""

from lib import readers

META = {"name": "device.idle_share.train", "layer": "Device", "unit": "%", "source": "device_trace", "moves": "train_tok_s_chip"}


def read(run):
    return readers.idle_share(run)
