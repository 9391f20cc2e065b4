"""Model: `moe.latent_proj_time_share.reasoning` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import scope_share

META = {"name": "moe.latent_proj_time_share.reasoning", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time spent in the two latent
    projections of the expert layers (ops under ``moe.latent_down`` and
    ``moe.latent_up``: one pair a layer, around the routed sum), over the
    traced span; nothing where the program has no such scope."""
    scopes = run.facts.get("scopes")
    if not scopes or r"moe\.latent_" not in scopes:
        return None
    return scope_share.read(run, r"moe\.latent_")
