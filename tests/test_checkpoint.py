"""Checkpoint / resume tests: sharded state round-trips through orbax."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_parallel.runtime import MeshConfig
from tpu_parallel.train_lib import Trainer, TrainerConfig


def _tree_equal(a, b):
    flat_a = jax.tree_util.tree_leaves(jax.device_get(a))
    flat_b = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_roundtrip_sharded(tmp_path, devices):
    """FSDP+TP+PP-sharded TrainState saves and restores bit-identically."""
    config = TrainerConfig(
        model="tiny",
        model_overrides=dict(num_microbatches=2, fsdp=True, fsdp_min_size=0),
        mesh=MeshConfig(data=2, model=2, pipe=2),
        global_batch_size=8,
        steps=3,
        log_every=10,
        donate=False,
    )
    trainer = Trainer(config)
    trainer.init()
    trainer.train(steps=3)
    step_count = int(jax.device_get(trainer.state.step))
    trainer.save_checkpoint(str(tmp_path / "ckpt"), step=step_count)

    # fresh trainer, same config: restore must reproduce the state exactly
    trainer2 = Trainer(config)
    restored = trainer2.restore_checkpoint(str(tmp_path / "ckpt"))
    _tree_equal(trainer.state.params, restored.params)
    _tree_equal(trainer.state.opt_state, restored.opt_state)
    assert int(jax.device_get(restored.step)) == step_count

    # and training continues from the restored state
    result = trainer2.train(steps=2)
    assert result["loss"] > 0


def test_checkpoint_restore_missing_raises(tmp_path, devices):
    config = TrainerConfig(
        model="tiny", mesh=MeshConfig(data=8), global_batch_size=8, donate=False
    )
    trainer = Trainer(config)
    with pytest.raises(FileNotFoundError):
        trainer.restore_checkpoint(str(tmp_path / "nope"))


def test_profiling_helpers(devices):
    from tpu_parallel.models import tiny_test
    from tpu_parallel.utils.profiling import (
        timeit,
        transformer_flops_per_token,
    )

    cfg = tiny_test()
    flops = transformer_flops_per_token(cfg)
    assert flops > 0
    # expert_choice active-param accounting: every expert fills its
    # capacity, so per-token FLOPs scale with moe_capacity_factor — a
    # capacity factor of 1.25 must read ~25% more FFN work than 1.0
    # (the old k=1 accounting overstated MFU)
    ec1 = transformer_flops_per_token(
        tiny_test(
            moe_experts=4, moe_router="expert_choice", moe_capacity_factor=1.0
        )
    )
    ec125 = transformer_flops_per_token(
        tiny_test(
            moe_experts=4, moe_router="expert_choice", moe_capacity_factor=1.25
        )
    )
    assert ec125 > ec1
    topk1 = transformer_flops_per_token(
        tiny_test(moe_experts=4, moe_router="topk", moe_top_k=1)
    )
    assert ec1 == topk1  # capacity 1.0 == one expert per token
    f = jax.jit(lambda x: x * 2)
    dt = timeit(f, jnp.ones(16), iters=3, warmup=1)
    assert dt > 0


def test_metric_logger(tmp_path, devices):
    import json

    from tpu_parallel.utils import MetricLogger

    logger = MetricLogger(str(tmp_path), name="t")
    logger.log(1, {"loss": 1.5})
    logger.log(2, {"loss": 1.2})
    logger.close()
    lines = [json.loads(l) for l in open(tmp_path / "t.jsonl")]
    assert [l["step"] for l in lines] == [1, 2]
    assert lines[1]["loss"] == 1.2


def test_restore_across_structure_drift(tmp_path):
    """Checkpoints written before an optional state field existed must still
    restore: the new field keeps its template default (regression: adding
    TrainState.ema_params broke restoring every pre-existing checkpoint)."""
    import numpy as np
    from typing import Any, Optional

    from flax import struct

    from tpu_parallel.checkpoint.io import Checkpointer

    @struct.dataclass
    class StateV1:
        step: jax.Array
        params: Any

    @struct.dataclass
    class StateV2:
        step: jax.Array
        params: Any
        ema_params: Optional[Any] = None

    params = {"w": jnp.arange(8, dtype=jnp.float32)}
    ck = Checkpointer(str(tmp_path / "ckpt"))
    ck.save(3, StateV1(step=jnp.int32(3), params=params), wait=True)

    abstract_v2 = jax.eval_shape(
        lambda: StateV2(step=jnp.int32(0), params={"w": jnp.zeros(8)})
    )
    restored = ck.restore(abstract_v2)
    assert int(restored.step) == 3
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), np.arange(8))
    assert restored.ema_params is None
    ck.close()


def test_checkpoint_roundtrip_with_ema(tmp_path, devices):
    """ema_params survives the save/restore roundtrip bit-for-bit."""
    import numpy as np

    from tpu_parallel.runtime import MeshConfig
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    config = TrainerConfig(
        model="tiny",
        mesh=MeshConfig(data=-1),
        global_batch_size=16,
        steps=3,
        ema_decay=0.9,
        log_every=10,
        donate=False,
    )
    trainer = Trainer(config)
    final = trainer.fit(str(tmp_path / "run"), checkpoint_every=3)
    assert "loss" in final
    state = trainer.state

    trainer2 = Trainer(config)
    trainer2.fit(str(tmp_path / "run"), checkpoint_every=10**9)
    for (p1, a), (p2, b) in zip(
        jax.tree_util.tree_leaves_with_path(state.ema_params),
        jax.tree_util.tree_leaves_with_path(trainer2.state.ema_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(p1))


def test_resume_toggling_ema_both_directions(tmp_path, devices):
    """EMA can be turned on or off across resumes of the same run directory.

    off -> on: the pre-EMA checkpoint restores and the shadow seeds from
    the restored params; on -> off: the EMA-bearing checkpoint restores
    into a no-EMA config with the shadow dropped."""
    import numpy as np

    from tpu_parallel.runtime import MeshConfig
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    base = dict(
        model="tiny",
        mesh=MeshConfig(data=-1),
        global_batch_size=16,
        log_every=10,
        donate=False,
    )
    run = str(tmp_path / "run")

    t1 = Trainer(TrainerConfig(steps=2, ema_decay=0.0, **base))
    t1.fit(run, checkpoint_every=2)

    # off -> on
    t2 = Trainer(TrainerConfig(steps=4, ema_decay=0.9, **base))
    t2.fit(run, checkpoint_every=2)
    assert int(t2.state.step) == 4
    assert t2.state.ema_params is not None

    # on -> off
    t3 = Trainer(TrainerConfig(steps=6, ema_decay=0.0, **base))
    t3.fit(run, checkpoint_every=10**9)
    assert int(t3.state.step) == 6
    assert t3.state.ema_params is None
