"""Device-mesh construction for DP x FSDP x TP x PP (x SP) parallelism.

The reference only ever builds a 1-D mesh over one ``"data"`` axis inline in
each script (``data_paral.py:150-152``, ``param_sharding.py`` equivalent).
Here the mesh is a first-class object: named axes, arbitrary shape, built with
``jax.experimental.mesh_utils.create_device_mesh`` so the logical axes map onto
the physical ICI torus well (innermost axes get the tightest rings), and
DCN-aware when a pod spans multiple slices.

Axis convention (outermost -> innermost):

- ``pipe``  — pipeline stages.  Lowest-bandwidth traffic (one activation
  handoff per microbatch) so it tolerates the slowest links (DCN).
- ``data``  — data parallelism; FSDP shards parameters over this same axis
  (ZeRO-3 style), so its traffic is one gradient reduce-scatter + param
  all-gather per step.
- ``seq``   — sequence/context parallelism (ring attention KV rotation).
- ``model`` — tensor parallelism.  Per-layer activation collectives — the most
  latency-sensitive — so it sits innermost, on the fastest ICI ring.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger("tpu_parallel")

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"

# Outer-to-inner ordering used when materializing the physical mesh.
AXIS_ORDER: Tuple[str, ...] = (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. ``-1`` on any ONE axis means "all remaining devices"."""

    data: int = -1
    model: int = 1
    pipe: int = 1
    seq: int = 1

    def resolved(self, n_devices: int) -> "MeshConfig":
        sizes = dict(data=self.data, model=self.model, pipe=self.pipe, seq=self.seq)
        wild = [name for name, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wild}")
        if wild:
            fixed = 1
            for name, v in sizes.items():
                if name != wild[0]:
                    fixed *= v
            if fixed <= 0 or n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by the fixed axes "
                    f"product {fixed} (mesh {sizes})"
                )
            sizes[wild[0]] = n_devices // fixed
        if sizes["data"] * sizes["model"] * sizes["pipe"] * sizes["seq"] != n_devices:
            raise ValueError(
                f"mesh shape data={sizes['data']} model={sizes['model']} "
                f"pipe={sizes['pipe']} seq={sizes['seq']} does not cover "
                f"{n_devices} devices"
            )
        return MeshConfig(**sizes)

    def axis_sizes(self) -> dict:
        return {
            PIPE_AXIS: self.pipe,
            DATA_AXIS: self.data,
            SEQ_AXIS: self.seq,
            MODEL_AXIS: self.model,
        }


def make_mesh(
    config: MeshConfig = MeshConfig(),
    devices: Optional[Sequence] = None,
    *,
    allow_split_physical_axes: bool = True,
):
    """Build a ``jax.sharding.Mesh`` with named axes from a logical shape.

    Uses ``mesh_utils.create_device_mesh`` so that on TPU the logical axes are
    laid out along physical ICI rings ("model" innermost), and falls back to a
    plain reshape on CPU-simulated devices.  Drops axes of size 1 is NOT done —
    keeping all four axes means the same ``PartitionSpec``s work for every
    strategy combination (an axis of size 1 is free).
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    cfg = config.resolved(len(devices))
    sizes = cfg.axis_sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)

    if devices[0].platform == "cpu":
        dev_array = np.asarray(devices).reshape(shape)
    else:
        from jax.experimental import mesh_utils

        try:
            dev_array = mesh_utils.create_device_mesh(
                shape,
                devices=devices,
                allow_split_physical_axes=allow_split_physical_axes,
            )
        except (ValueError, AssertionError, NotImplementedError) as exc:
            # loud, not silent: a plain reshape ignores the ICI topology
            logger.warning(
                "create_device_mesh%s failed (%r); falling back to a plain "
                "reshape of the device list", shape, exc,
            )
            dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def mesh_from_sizes(data: int = -1, model: int = 1, pipe: int = 1, seq: int = 1, devices=None):
    return make_mesh(MeshConfig(data=data, model=model, pipe=pipe, seq=seq), devices=devices)


def factor_mesh(
    n_devices: int, *, want_model: int = 1, want_pipe: int = 1, want_seq: int = 1
) -> MeshConfig:
    """Best-effort factorization of ``n_devices`` into (pipe, data, seq, model).

    Shrinks the requested model/pipe/seq degrees to the largest divisors that
    fit (in that priority order).  Useful for dry-runs where the device count
    is dictated from outside.
    """

    def largest_divisor(n: int, want: int) -> int:
        for d in range(min(want, n), 0, -1):
            if n % d == 0:
                return d
        return 1

    model = largest_divisor(n_devices, want_model)
    rem = n_devices // model
    pipe = largest_divisor(rem, want_pipe)
    rem //= pipe
    seq = largest_divisor(rem, want_seq)
    return MeshConfig(data=rem // seq, model=model, pipe=pipe, seq=seq)
