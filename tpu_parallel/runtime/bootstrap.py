"""Runtime bootstrap: device discovery, multi-host init, CPU device simulation.

Capability parity target: ``util.py:31-38`` (``sim_multiCPU_dev``) in the
reference, which fakes an N-device machine by appending
``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``.  The reference
version is broken (uses ``os`` without importing it) and fragile (mutates the
env *after* ``import jax``).  This module makes the ordering explicit and adds
the two things the reference never had: a real multi-host bootstrap
(``jax.distributed.initialize``) and introspection helpers for the process
topology.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger("tpu_parallel")

_SIMULATED = False


def simulate_cpu_devices(num_devices: int = 8) -> None:
    """Present ``num_devices`` virtual CPU devices to JAX in this process.

    Every collective, ``shard_map``, and mesh then behaves exactly as on a real
    multi-chip slice, single-process — the canonical JAX trick for testing
    parallelism without hardware.

    Must run before the first touch of the JAX CPU backend (first
    ``jax.devices()`` / compilation).  Works both before and after
    ``import jax``:

    - ``XLA_FLAGS`` is read by the CPU PJRT client at *backend* init, not at
      import, so setting it here is safe as long as no backend exists yet.
    - If ``jax`` is already imported with another platform selected
      (``JAX_PLATFORMS`` named an accelerator), we also flip ``jax_platforms``
      to ``cpu`` through the config system, which — unlike mutating
      ``os.environ`` — still takes effect post-import.
    """
    global _SIMULATED
    flag = f"--xla_force_host_platform_device_count={num_devices}"
    prev = os.environ.get("XLA_FLAGS", "")
    # Replace any stale device-count flag rather than deferring to it.
    kept = [
        f for f in prev.split() if "xla_force_host_platform_device_count" not in f
    ]
    os.environ["XLA_FLAGS"] = " ".join(kept + [flag])
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

    # Post-condition, not an assert (must survive `python -O`): if another
    # backend was already initialized, the config update above silently has
    # no effect and every later mesh/reshape error would be obscure.  Local
    # devices, so the check is also correct under multi-process fakes
    # (jax.devices() is global across processes).
    devices = jax.local_devices()
    if devices[0].platform != "cpu" or len(devices) != num_devices:
        raise RuntimeError(
            f"simulate_cpu_devices({num_devices}) failed: backend is "
            f"{len(devices)} x {devices[0].platform!r} — a JAX backend was "
            "initialized before this call (it must run before the first "
            "jax.devices()/compilation in the process)"
        )
    _SIMULATED = True


def is_simulated() -> bool:
    return _SIMULATED


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Bootstrap the distributed runtime.

    - Single-process (one host, any number of local chips): no-op.
    - TPU pod / multi-host: calls ``jax.distributed.initialize``.  On Cloud TPU
      VMs all three arguments are auto-detected from the metadata server, so
      ``initialize()`` with no arguments is the common path; the explicit
      arguments cover manual (e.g. DCN-spanning) launches.

    The reference has no equivalent — it never leaves one process
    (``util.py:31-38`` is its whole runtime layer).
    """
    env_procs = os.environ.get("TPU_PROCESS_COUNT") or os.environ.get("JAX_NUM_PROCESSES")
    multi = (
        coordinator_address is not None
        or num_processes is not None
        or process_id is not None
        or (env_procs is not None and int(env_procs) > 1)
    )
    if not multi:
        logger.debug("single-process runtime; skipping jax.distributed.initialize")
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


# The one persistent XLA cache every entry point shares when the caller names
# none: inside the checkout (git-ignored) and fixed — never a temp name, pid
# or time, because a directory that moves between runs never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def enable_compilation_cache() -> str:
    """Persist XLA compilations across processes; returns the cache path.

    The ONLY place in the repo that chooses a cache directory (train.py,
    bench.py, chip_smoke.py, the bench children and tests/conftest.py all
    call it).  ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it
    itself, so no directory is set here; otherwise :data:`COMPILE_CACHE_DIR`.
    Call before the first compilation.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = COMPILE_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program that took meaningful compile time, however small
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def device_record() -> dict:
    """What every benchmark record says about where it ran — the three
    fields as JAX reports them (initializes the backend)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_tpu() -> dict:
    """Fail unless the default backend is a TPU; returns :func:`device_record`.

    Measurement paths call this first: the CPU is chosen only explicitly
    (``JAX_PLATFORMS=cpu`` / :func:`simulate_cpu_devices`), never as a silent
    substitute for a chip that was not found.
    """
    record = device_record()
    if record["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found {record['device_count']} x "
            f"{record['platform']!r} ({record['device_kind']}) — this path "
            "measures the chip and does not fall back to another backend"
        )
    return record


def process_info() -> dict:
    """Topology snapshot for logging: process index/count, device counts."""
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
        "platform": jax.devices()[0].platform,
    }
