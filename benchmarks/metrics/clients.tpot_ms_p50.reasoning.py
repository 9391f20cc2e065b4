"""Cluster / daemon: `clients.tpot_ms_p50.reasoning` (ms), from host_clock; should move `serve_out_tok_s`."""

META = {"name": "clients.tpot_ms_p50.reasoning", "layer": "Cluster / daemon", "unit": "ms", "source": "host_clock", "moves": "serve_out_tok_s"}


def read(run):
    """Median, over the requests sent and finished inside the window, of
    (last - first token) / (tokens - 1) on the client's clock: the pace of
    one stream.  Slots x 1000 / it bounds the job's tokens a second."""
    from lib.traffic import median

    pace = run.samples.get("tpot_s")
    return 1e3 * median(pace) if pace else None
