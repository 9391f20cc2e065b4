"""The head-and-loss unit of PR 46 (``core.losses.token_ce_and_argmax``):
value, correct count and every gradient against the form it replaced
(autodiff through optax's softmax cross-entropy of the upcast logits and a
separate ``argmax``), kept HERE as the plain reference; what the unit saves
for its backward; and the plan the ``Trainer`` states.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import PartitionSpec as P

from tpu_parallel.core.losses import token_ce_and_argmax, token_cross_entropy


def reference_ce_and_argmax(logits, targets):
    """What ``token_cross_entropy`` was, beside the accuracy's ``argmax``."""
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    )
    return ce, logits.argmax(-1)


def head_and_loss(loss):
    """``(kernel, h, targets, mask) -> (loss_sum, correct)`` over a head as
    ``nn.Dense(dtype=h.dtype)`` applies it."""

    def fn(kernel, h, targets, mask):
        logits = jnp.dot(h, kernel.astype(h.dtype))
        ce, pred = loss(logits, targets)
        return (ce * mask).sum(), ((pred == targets) * mask).sum()

    return fn


def operands(dtype, vocab, rows=6, seq=10, width=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = jax.random.normal(keys[0], (rows, seq, width), dtype)
    kernel = jax.random.normal(keys[1], (width, vocab), jnp.float32) * 0.5
    targets = jax.random.randint(keys[2], (rows, seq), 0, vocab)
    mask = (jax.random.uniform(keys[3], (rows, seq)) > 0.3).astype(jnp.float32)
    return kernel, h, targets, mask


def both(args):
    out = []
    for loss in (token_ce_and_argmax, reference_ce_and_argmax):
        fn = jax.jit(jax.value_and_grad(
            head_and_loss(loss), argnums=(0, 1), has_aux=True
        ))
        (value, correct), grads = fn(*args)
        out.append((float(value), float(correct), *(
            np.asarray(g.astype(jnp.float32)) for g in grads
        )))
    return out


@pytest.mark.parametrize("vocab", [256, 300])  # 300: not a multiple of 128
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_value_count_and_gradients_match_the_reference(dtype, vocab):
    new, ref = both(operands(dtype, vocab))
    assert new[1] == ref[1]
    if dtype == jnp.float32:
        assert abs(new[0] - ref[0]) <= 1e-6 * abs(ref[0])
        for got, want in zip(new[2:], ref[2:]):  # sums of 60 float32 products
            np.testing.assert_allclose(
                got, want, rtol=0, atol=2e-6 * np.abs(want).max()
            )
    else:
        # both forms round d logits to bfloat16 once, from float32 values that
        # differ in their last bits: a bfloat16 step (2**-8) of the largest
        # entry is what a flipped rounding costs a gradient's element
        assert abs(new[0] - ref[0]) <= 1e-5 * abs(ref[0])
        for got, want in zip(new[2:], ref[2:]):
            np.testing.assert_allclose(
                got, want, rtol=0, atol=2.0 ** -8 * np.abs(want).max()
            )


def test_rows_masked_out_get_no_gradient():
    kernel, h, targets, mask = operands(jnp.float32, 300)
    mask = mask.at[1].set(0.0).at[4].set(0.0)  # two rows with no token counted
    new, ref = both((kernel, h, targets, mask))
    assert new[1] == ref[1]
    np.testing.assert_allclose(new[2], ref[2], rtol=0, atol=2e-6 * np.abs(ref[2]).max())
    np.testing.assert_allclose(new[3], ref[3], rtol=0, atol=2e-6 * np.abs(ref[3]).max())
    assert not new[3][1].any() and not new[3][4].any()
    assert new[3][0].any()
    # and a mask of zeros everywhere is a loss of zero with zero gradients
    zero = both((kernel, h, targets, jnp.zeros_like(mask)))[0]
    assert zero[0] == 0.0 and zero[1] == 0.0
    assert not zero[2].any() and not zero[3].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_tied_maximum_goes_to_the_first_index(dtype):
    logits = jnp.zeros((3, 300), dtype)
    logits = logits.at[0, jnp.array([7, 200, 299])].set(2.0)  # three-way tie
    logits = logits.at[1, jnp.array([150, 149])].set(1.5)  # neighbours
    # row 2: every logit equal
    targets = jnp.array([200, 149, 0])
    ce, pred = jax.jit(token_ce_and_argmax)(logits, targets)
    assert pred.tolist() == [7, 149, 0] == logits.argmax(-1).tolist()
    want = reference_ce_and_argmax(logits, targets)[0]
    np.testing.assert_allclose(np.asarray(ce), np.asarray(want), rtol=1e-6)
    assert token_cross_entropy(logits, targets).dtype == jnp.float32


def test_under_checkpoint_inside_a_scan():
    """The ``loss_chunk`` path: ``make_ce_fn``'s scan of rematerialized
    chunks gives the unchunked head's value, count and gradients, and those
    are the reference's."""
    from tpu_parallel.models import tiny_test
    from tpu_parallel.models.gpt import make_ce_fn

    kernel, h, targets, mask = operands(jnp.float32, 256, rows=2, seq=32, width=32)
    params = {"shard": {"kernel": kernel}}  # the head outside a mesh
    out = {}
    for chunk in (0, 8):
        cfg = tiny_test(dtype=jnp.float32, loss_chunk=chunk)
        ce_fn = make_ce_fn(cfg)
        out[chunk] = jax.jit(jax.value_and_grad(
            lambda p, h: ce_fn(p, h, targets, mask), argnums=(0, 1), has_aux=True
        ))(params, h)
    ref = both((kernel, h, targets, mask))[1]
    for chunk, ((value, correct), (d_params, d_h)) in out.items():
        assert abs(float(value) - ref[0]) <= 1e-5 * abs(ref[0]), chunk
        assert float(correct) == ref[1]
        np.testing.assert_allclose(
            np.asarray(d_params["shard"]["kernel"]), ref[2], atol=2e-6
        )
        np.testing.assert_allclose(np.asarray(d_h), ref[3], atol=2e-6)


def test_under_shard_map_with_data_parallelism(mesh_data8):
    """Eight shards of the rows, the replication checker on: the summed
    loss and the gradients (the replicated kernel's arrives summed over the
    shards: the checker's own transpose) are the one-device reference's."""
    kernel, h, targets, mask = operands(jnp.float32, 300, rows=8)
    fn = head_and_loss(token_ce_and_argmax)

    def shard(kernel, h, targets, mask):
        (value, correct), (d_kernel, d_h) = jax.value_and_grad(
            fn, argnums=(0, 1), has_aux=True
        )(kernel, h, targets, mask)
        return (jax.lax.psum(value, "data"), jax.lax.psum(correct, "data"),
                d_kernel, d_h)

    value, correct, d_kernel, d_h = jax.jit(jax.shard_map(
        shard, mesh=mesh_data8,
        in_specs=(P(), P("data"), P("data"), P("data")),
        out_specs=(P(), P(), P(), P("data")),
    ))(kernel, h, targets, mask)
    ref = both((kernel, h, targets, mask))[1]
    assert abs(float(value) - ref[0]) <= 1e-5 * abs(ref[0])
    assert float(correct) == ref[1]
    np.testing.assert_allclose(np.asarray(d_kernel), ref[2], atol=2e-6)
    np.testing.assert_allclose(np.asarray(d_h), ref[3], atol=2e-6)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_through_the_pipeline_steps(schedule, monkeypatch):
    """Three ``Trainer`` steps on a pipe x data mesh (the model axis bound
    at ONE chip, so the unit runs and its type over that axis is closed):
    the loss with the unit is the loss with the reference in its place."""
    from tpu_parallel.models import gpt
    from tpu_parallel.runtime import MeshConfig
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    def three_steps():
        config = TrainerConfig(
            model="tiny",
            model_overrides=dict(
                pipe_size=2, num_microbatches=2, dtype=jnp.float32,
                remat=False, dropout_rate=0.0, pipe_schedule=schedule,
            ),
            mesh=MeshConfig(pipe=2, data=4),
            global_batch_size=16, steps=3, log_every=1000, donate=False, seed=0,
        )
        trainer = Trainer(config)
        assert trainer.loss_plan["form"] == "fused"
        trainer.init()
        return trainer.train(steps=3)["loss"]

    with_unit = three_steps()
    monkeypatch.setattr(gpt, "token_ce_and_argmax", reference_ce_and_argmax)
    with_reference = three_steps()
    assert abs(with_unit - with_reference) < 1e-5, (with_unit, with_reference)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_unit_saves_one_copy_of_the_logits_in_their_own_type(dtype):
    """The mechanism itself: of ``[rows, vocab]`` the backward keeps the
    logits as the head wrote them and nothing else; the reference keeps a
    float32 array of that size (its exponentials) in their place."""
    kernel, h, targets, mask = operands(dtype, 300)
    rows_by_vocab = targets.size * 300

    def big(loss):
        saved = saved_residuals(
            head_and_loss(loss), kernel, h, targets, mask
        )
        return [aval for aval, _ in saved if aval.size == rows_by_vocab]

    assert [(a.shape, a.dtype) for a in big(token_ce_and_argmax)] == [
        (targets.shape + (300,), dtype)
    ]
    assert any(a.dtype == jnp.float32 for a in big(reference_ce_and_argmax))
    # beside them: the float32 log-sum-exp a row
    small = [
        aval for aval, _ in saved_residuals(
            lambda x: token_ce_and_argmax(x, targets)[0].sum(),
            jnp.zeros(targets.shape + (300,), dtype),
        )
    ]
    assert sorted((a.shape, str(a.dtype)) for a in small) == sorted([
        (targets.shape + (300,), jnp.dtype(dtype).name),
        (targets.shape, "float32"), (targets.shape, "int32"),
    ])


@pytest.mark.parametrize("model_axis,form", [(1, "fused"), (2, "vocab_parallel")])
def test_the_trainer_states_its_loss_plan(model_axis, form, caplog):
    import json
    import logging

    from tpu_parallel.obs import Tracer
    from tpu_parallel.runtime import MeshConfig
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    config = TrainerConfig(
        model="tiny", mesh=MeshConfig(data=8 // model_axis, model=model_axis),
        global_batch_size=16, num_minibatches=2, steps=1, log_every=1000,
        donate=False,
    )
    with caplog.at_level(logging.INFO, logger="tpu_parallel.train_lib"):
        trainer = Trainer(config, tracer=Tracer())
    cfg = trainer.model_config
    rows = 16 // (8 // model_axis) // 2
    vocab = cfg.vocab_size // model_axis
    itemsize = jnp.dtype(cfg.dtype).itemsize if form == "fused" else 4
    assert trainer.loss_plan == {
        "form": form, "rows_per_pass": rows, "vocab": vocab,
        "logits_dtype": jnp.dtype(cfg.dtype).name,
        "residual_bytes_per_pass": rows * cfg.seq_len * (vocab * itemsize + 4),
        "chunk": 0,
    }
    logged = [r.getMessage() for r in caplog.records
              if r.getMessage().startswith("loss_plan ")]
    assert len(logged) == 1
    assert json.loads(logged[0][len("loss_plan "):]) == trainer.loss_plan
    trainer.train(steps=1)
    instants = [ev for ev in trainer.tracer.instants if ev["name"] == "loss_plan"]
    assert len(instants) == 1 and instants[0]["track"] == "trainer"
    assert instants[0]["attrs"] == trainer.loss_plan
