"""Device: `device.idle_share.longdoc` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import readers

META = {"name": "device.idle_share.longdoc", "layer": "Device", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    return readers.idle_share(run)
