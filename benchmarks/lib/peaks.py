"""Published peaks of one chip, keyed by the EXACT ``device_kind`` JAX reports.

Copied from ``tpu_parallel/utils/profiling.py::PEAK_FLOPS_BY_KIND`` (the FLOP
column; ``tests/benchmarks`` checks the copy still agrees) so that a later PR
may change the program and not the yardstick.  The HBM bandwidth column is
new here.  Source of every figure: Google Cloud TPU documentation, the
"TPU v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system architecture pages.  A
kind that is not in the table is an error, never a default.
"""

PEAKS_BY_KIND = {
    # kind: dense bf16 FLOP/s, HBM bytes/s
    "TPU v4": {"flops": 275e12, "hbm_bytes_per_s": 1228e9},
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},  # v5e
    "TPU v5": {"flops": 459e12, "hbm_bytes_per_s": 2765e9},  # v5p
    "TPU v6 lite": {"flops": 918e12, "hbm_bytes_per_s": 1640e9},  # v6e
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks on record for device_kind={device_kind!r}; "
            f"known: {sorted(PEAKS_BY_KIND)} - add the figures with their "
            "source, do not assume any"
        ) from None
