from tpu_parallel.utils.logging_utils import MetricLogger, print_exception
from tpu_parallel.utils.profiling import (
    mfu,
    peak_flops,
    run_identity,
    sync,
    timeit,
    trace,
    transformer_flops_per_token,
)

__all__ = [
    "MetricLogger",
    "print_exception",
    "mfu",
    "peak_flops",
    "run_identity",
    "sync",
    "timeit",
    "trace",
    "transformer_flops_per_token",
]
