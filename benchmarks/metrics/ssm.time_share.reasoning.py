"""Model: `ssm.time_share.reasoning` (%), from device_trace; should move `serve_out_tok_s`."""

from lib import scope_share

META = {"name": "ssm.time_share.reasoning", "layer": "Model", "unit": "%", "source": "device_trace", "moves": "serve_out_tok_s"}


def read(run):
    """Share of the first chip's busy time spent in the recurrent mixers: ops
    under the ``ssm.*`` scopes (in_proj, conv, scan, step, gate_norm,
    out_proj), over the traced span; the log line has every scope."""
    scopes = run.facts.get("scopes")
    if not scopes or not scopes.get("busy_s"):
        return None
    sort_s = scopes[r"^sort"]["seconds"]
    run.log(f"device time by scope over {scopes['busy_s']:.4f}s busy: " + ", ".join(
        f"{k} {v['seconds']:.4f}s" for k, v in scopes.items() if k != "busy_s"
    ) + f"; the sampler's sorts are {100.0 * sort_s / scopes['busy_s']:.2f}%")
    return scope_share.read(run, r"ssm\.")
