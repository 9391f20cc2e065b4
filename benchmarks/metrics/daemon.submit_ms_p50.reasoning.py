"""Cluster / daemon: `daemon.submit_ms_p50.reasoning` (ms), from host_clock; should move `serve_out_tok_s`."""

META = {"name": "daemon.submit_ms_p50.reasoning", "layer": "Cluster / daemon", "unit": "ms", "source": "host_clock", "moves": "serve_out_tok_s"}


def read(run):
    """Median round trip of POST /v1/submit on the client's clock."""
    from lib.traffic import median

    rtts = run.samples.get("submit_s")
    return 1e3 * median(rtts) if rtts else None
