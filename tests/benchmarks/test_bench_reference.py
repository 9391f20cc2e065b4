"""``gpt2_ref.py`` against the program's model at a tiny size, and its
optimizer against optax: agreement in float32, a stated disagreement once
either side computes in a lower precision."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import weights  # noqa: E402
from reference import gpt2_ref  # noqa: E402

# float32 against float32 differs by summation order only (2e-6 measured);
# bf16 activations move a unit-variance logit by about 3e-2
FP32_TOL = 2e-5
SEED = 2 ** 31 + 77


def build(scan, dtype):
    from tpu_parallel.models import GPTLM
    from tpu_parallel.models.gpt import tiny_test

    cfg = tiny_test(scan_layers=scan, n_layers=3, dtype=dtype, seq_len=32)
    model = GPTLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False)
    )["params"]
    return cfg, model, weights.make_params(SEED, abstract)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
def test_reference_agrees_in_float32_and_not_in_bf16(scan):
    cfg, model, params = build(scan, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 250)
    ref = gpt2_ref.forward(weights.to_reference(params, cfg.n_heads), toks)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, toks, train=False)
    assert float(jnp.max(jnp.abs(got - ref))) < FP32_TOL
    layerwise = gpt2_ref.forward_layerwise(
        weights.to_reference(params, cfg.n_heads), toks
    )
    assert float(jnp.max(jnp.abs(layerwise - ref))) < FP32_TOL
    # the program's side in bf16: beyond the tolerance, by a wide margin
    _, low_model, _ = build(scan, jnp.bfloat16)
    low = low_model.apply({"params": params}, toks, train=False)
    assert float(jnp.max(jnp.abs(low.astype(jnp.float32) - ref))) > 100 * FP32_TOL


def test_lower_precisions_order():
    cfg, _, params = build(False, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 250)
    ref_w = weights.to_reference(params, cfg.n_heads)
    ref = gpt2_ref.forward(ref_w, toks)
    err = {
        p: float(jnp.max(jnp.abs(gpt2_ref.forward(ref_w, toks, p) - ref)))
        for p in ("bfloat16", "float8")
    }
    assert 100 * FP32_TOL < err["bfloat16"] < err["float8"] / 3


def test_weights_are_a_function_of_seed_and_path():
    cfg, _, a = build(False, jnp.float32)
    _, _, b = build(False, jnp.float32)
    flat = lambda t: jnp.concatenate([x.ravel() for x in jax.tree.leaves(t)])
    assert bool(jnp.all(flat(a) == flat(b)))
    # unrolled and scanned stacks re-lay out to the same reference shapes
    _, _, scanned = build(True, jnp.float32)
    ra = weights.to_reference(a, cfg.n_heads)
    rs = weights.to_reference(scanned, cfg.n_heads)
    assert jax.tree.map(jnp.shape, ra) == jax.tree.map(jnp.shape, rs)
    assert set(weights.leaf_norms(ra)) == set(weights.leaf_norms(rs))
    assert len(weights.leaf_norms(ra)) == 5 + 16 * 3


def test_reference_optimizer_follows_optax():
    import optax

    cfg, model, params = build(False, jnp.float32)
    ref_params = weights.to_reference(params, cfg.n_heads)
    toks = jax.random.randint(jax.random.PRNGKey(3), (4, 33), 0, 250)
    opt = {"grad_clip": 1.0, "learning_rate": 6e-4, "warmup_steps": 2,
           "steps": 1000, "weight_decay": 0.1}
    tx = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(optax.warmup_cosine_decay_schedule(
            0.0, 6e-4, 2, 1000, 6e-5), weight_decay=0.1),
    )
    state, theirs = tx.init(ref_params), ref_params
    adam, mine = gpt2_ref.AdamW(ref_params, opt), ref_params
    for _ in range(3):
        loss, grads = gpt2_ref.loss_and_grads(
            mine, toks[:, :-1], toks[:, 1:], block_rows=2
        )
        mine, _ = adam.step(mine, grads)
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
    assert 5.0 < float(loss) < 6.5
    moved = jnp.sqrt(sum(
        jnp.sum((a - b) ** 2) for a, b in
        zip(jax.tree.leaves(mine), jax.tree.leaves(ref_params))
    ))
    apart = jnp.sqrt(sum(
        jnp.sum((a - b) ** 2) for a, b in
        zip(jax.tree.leaves(mine), jax.tree.leaves(theirs))
    ))
    assert float(moved) > 0 and float(apart) < 1e-4 * float(moved)
