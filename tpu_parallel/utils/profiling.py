"""Profiling, timing, and MFU accounting.

The reference's entire observability story is ``jax.named_scope`` labels
(SURVEY.md §5, tracing row).  This module keeps those (every collective in
the framework is scoped) and adds what the reference lacked: a
``jax.profiler`` trace context for Perfetto/XProf, a ``block_until_ready``
timing harness, and model-FLOPs-utilization math for the benchmark harness.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable

import jax

# Dense bf16 peak FLOPs/s per chip, keyed by the EXACT ``device_kind`` string
# JAX reports (the strings are what ``jax.experimental.topologies`` describes
# for v4 / v5e / v5p / v6e with the installed libtpu).  Figures: Google Cloud
# TPU documentation, the "TPU v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system
# architecture pages.  A kind that is not here is an error on a measurement
# path, never a default.
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275e12,  # one device per chip (megacore)
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v6 lite": 918e12,  # v6e
}


def peak_flops(device=None) -> float:
    """Peak bf16 FLOPs/s for ``device``; raises on an unknown ``device_kind``."""
    device = device or jax.devices()[0]
    try:
        return PEAK_FLOPS_BY_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOPs/s on record for device_kind={device.device_kind!r} "
            f"(platform {device.platform!r}); known: {sorted(PEAK_FLOPS_BY_KIND)} "
            "— add the published figure with its source, do not assume one"
        ) from None


def model_label(cfg) -> str:
    """The architecture by its numbers — how benchmark records name what they
    ran, so the label follows the config and nothing else (not the backend,
    not a default)."""
    return (
        f"d{cfg.d_model}_l{cfg.n_layers}_h{cfg.n_heads}"
        f"_v{cfg.vocab_size}_s{cfg.seq_len}"
    )


def run_identity(cfg) -> dict:
    """What every benchmark record says about what ran where: the model by
    its shape, and the device as JAX reports it (``backend`` repeats the
    platform under the key older records used)."""
    from tpu_parallel.runtime import device_record

    device = device_record()
    return {"model": model_label(cfg), "backend": device["platform"], **device}


def transformer_flops_per_token(cfg) -> float:
    """Training FLOPs per token: 6*N for the matmul params + attention term.

    Standard PaLM-appendix accounting: 6 FLOPs per parameter per token
    (fwd 2 + bwd 4) over matmul-participating params, plus
    ``12 * L * d * T`` for the T-length causal attention (QK^T, softmax*V,
    fwd+bwd).  Embedding lookups are excluded (gather, not matmul); the
    untied lm_head matmul is included.

    MoE configs use ACTIVE-param accounting (the standard MoE MFU
    convention): each token runs ``moe_top_k`` experts' FFN matmuls plus
    the router projection — FLOPs scale with k, not with the total expert
    count, so a Switch model's MFU reads against the same roofline as its
    dense-equivalent.  Under expert-choice routing every expert fills its
    capacity by construction, so the per-token average is
    ``moe_capacity_factor`` experts (1.25 by default), not 1 — the FFN
    term scales by the capacity factor or expert-choice MFU reads ~25%
    high.
    """
    mlp_term = 2 * cfg.mlp_ratio * cfg.d_model**2
    moe_experts = getattr(cfg, "moe_experts", 0)
    if moe_experts:
        k = (
            cfg.moe_top_k
            if getattr(cfg, "moe_router", "topk") == "topk"
            else getattr(cfg, "moe_capacity_factor", 1.0)
        )
        mlp_term = k * mlp_term + cfg.d_model * moe_experts  # + router
    matmul_params = (
        cfg.vocab_size * cfg.d_model  # lm_head projection
        + cfg.n_layers * (4 * cfg.d_model**2 + mlp_term)
    )
    attn = 12 * cfg.n_layers * cfg.d_model * cfg.seq_len
    return 6 * matmul_params + attn


def mfu(tokens_per_sec_per_chip: float, cfg, device=None) -> float:
    return (
        tokens_per_sec_per_chip
        * transformer_flops_per_token(cfg)
        / peak_flops(device)
    )


@contextlib.contextmanager
def trace(logdir: str):
    """``with trace("/tmp/trace"):`` — dumps an XProf/Perfetto trace."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def sync(out) -> None:
    """Force completion of every array in the pytree ``out``.

    ``block_until_ready`` (all shards, all leaves) plus a device->host fetch
    of one element, so a timed region ends on a value the host has actually
    read.  The fetch only runs on fully-addressable arrays (eager indexing
    of a multi-host global array would raise); on a pod,
    ``block_until_ready`` alone is the barrier.
    """
    out = jax.block_until_ready(out)
    leaves = [
        l
        for l in jax.tree_util.tree_leaves(out)
        if hasattr(l, "shape")
        and getattr(l, "size", 0) > 0
        and getattr(l, "is_fully_addressable", True)
    ]
    if leaves:
        leaf = leaves[0]
        jax.device_get(leaf[(0,) * leaf.ndim])


def timeit(
    fn: Callable, *args, iters: int = 10, warmup: int = 3, **kwargs
) -> float:
    """Mean seconds per call, with compile excluded and device-synced timing."""
    out = None
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kwargs)
    sync(out)
    return (time.perf_counter() - t0) / iters
