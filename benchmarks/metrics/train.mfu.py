"""Model: `train.mfu` (%), from host_clock; should move `train_tok_s_chip`."""

from lib import readers

META = {"name": "train.mfu", "layer": "Model", "unit": "%", "source": "host_clock", "moves": "train_tok_s_chip"}


def read(run):
    """Model FLOP/s utilization over the untraced window: tokens a second a
    chip times the FLOPs a token needs (recomputation not counted), over the
    chip's published peak."""
    from lib import flops
    from lib.peaks import peaks

    rate = run.values.get("train_tok_s_chip")
    if rate is None:
        return None
    need = flops.train_flops_per_token(run.facts["model"])
    return 100.0 * rate * need / peaks(run.device["kind"])["flops"]
