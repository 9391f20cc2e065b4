"""Seeded weights of a one-sublayer-a-layer hybrid decoder (``nemotron_h``:
Mamba-2 layers, attention layers and LatentMoE layers, one kind a layer), and
the program's parameter tree re-laid out as
``benchmarks/reference/nemotron_h_ref.py`` names things.

``make_params`` is ``lib/weights.make_params`` (every leaf a function of the
seed and of the leaf's own path; matrices, the experts' stacked ones
included, normal with variance ``1 / fan_in``, the conv bias normal 0.02,
norm scales 1 + normal 0.02, the token embedding normal 0.02) with

- the Mamba-2 ranges of ``lib/granite_weights.py`` for the leaves a
  recurrence is sensitive to: ``A_log = log(uniform[1, 16])``, ``dt_bias``
  the inverse softplus of a step drawn log-uniformly in ``[1e-3, 1e-1]``,
  ``D = 1``, conv weights uniform in ``+-1/2``;
- the router's selection bias normal ``SELECT_BIAS_STD`` = 0.02 and NOT
  zero.  At rank 22 of 512 sigmoid scores of unit-variance logits lie 0.003
  apart, so a bias of 0.02 moves many of a token's 22 choices and a program
  that drops the bias serves other experts' outputs (the toy cell's test
  breaks it so and comes out not correct), while the scores' own spread of
  0.21 still decides which experts are busy.  A first draw at 0.1, half that
  spread, made a few experts every token's choice: 77.6 of the 128 held
  experts touched a decode step and the busiest expert at 12 times the mean
  (my chip run, PR 45), which is not what a correction bias, trained to
  BALANCE the load, leaves in a deployed model.

Does the initializer blind the comparison, as a 0.02 embedding times 12 tied
to the head did in ``lib/granite_weights.py``?  Here nothing multiplies the
embedding and the head is untied: after the first sublayer the residual is
what the layers added (each about unit variance behind its norm), the head
reads a normed residual through a matrix of its own, logits have unit spread
and the first choice is not the token just read (checked on the toy period
and, in PERF.md section 6, on the chip: the share of served tokens off the
fp32 best is what bfloat16 costs, and float8 moves most of them).

The re-layout is a permutation and reshape of elements, cast to float32.
``layers`` yields one layer at a time, made again from the seed (a layer's
sub-tree under its full path gives the same values as the whole tree did).
Unrolled stacks only (``blocks/layer_<i>/...``).
"""

import zlib

import jax
import jax.numpy as jnp

from lib import granite_weights, weights

SELECT_BIAS_STD = 0.02

# leaf name -> draw(key, shape), float32
OWN_LEAVES = {
    **{k: v for k, v in granite_weights.OWN_LEAVES.items() if k != "embedding"},
    "select_bias": lambda key, shape: SELECT_BIAS_STD * jax.random.normal(
        key, shape, jnp.float32
    ),
}


def make_params(seed: int, abstract, dtype=None):
    """A tree shaped like ``abstract``, every leaf drawn from ``seed`` and
    its path, in ``dtype`` (default: each leaf's own), in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [weights.path_name(p) for p, _ in leaves]

    def leaf(key, name, like):
        kind = dtype or like.dtype
        draw = OWN_LEAVES.get(name.rsplit("/", 1)[-1])
        if draw is None:
            return weights._leaf(key, name, like.shape, kind)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return draw(k, like.shape).astype(kind)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(key, n, like) for n, (_, like) in zip(names, leaves)
        ])

    return jax.jit(build)(weights.seed_key(seed))


def _flat(tree) -> dict:
    return {
        weights.path_name(p): jnp.asarray(v, jnp.float32)
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def layer(tree, n_heads: int, n_kv_heads: int) -> dict:
    """One block's sub-tree (``blocks/layer_<i>``) in the reference's layout;
    its kind is read off the sublayer it holds."""
    f = _flat(tree)
    out = {"ln_g": f["norm/scale"]}
    if "ssm/in_proj/kernel" in f:
        out.update({
            "w_in": f["ssm/in_proj/kernel"],
            "conv_w": f["ssm/conv_weight"].T, "conv_b": f["ssm/conv_bias"],
            "dt_bias": f["ssm/dt_bias"], "A_log": f["ssm/A_log"],
            "D": f["ssm/D"], "norm_g": f["ssm/gate_norm/scale"],
            "w_out": f["ssm/out_proj/kernel"],
        })
    elif "moe/router/kernel" in f:
        out.update({
            "router": f["moe/router/kernel"],
            "router_bias": f["moe/select_bias"],
            "w_dn": f["moe/latent_down/kernel"],
            "w_up": f["moe/latent_up/kernel"],
            "w1": f["moe/experts/up/kernel"], "w2": f["moe/experts/down/kernel"],
            "s1": f["moe/shared_up/kernel"][0], "s2": f["moe/shared_down/kernel"][0],
        })
    else:
        d = f["norm/scale"].shape[0]
        hd = f["attn/q/shard/kernel"].shape[1] // n_heads
        kv = f["attn/kv/shard/kernel"].reshape(d, n_kv_heads, 2, hd)
        out.update({
            "wq": f["attn/q/shard/kernel"].reshape(d, n_heads, hd),
            "wk": kv[:, :, 0], "wv": kv[:, :, 1],
            "wo": f["attn/out/shard/kernel"].reshape(n_heads, hd, d),
        })
    return out


def layers(seed: int, abstract, n_heads: int, n_kv_heads: int, dtype=None):
    """A generator over the layers of the tree ``make_params(seed, abstract,
    dtype)`` would give, each in the reference's layout: ONE layer's float32
    weights exist at a time (the consumer drops a layer before it asks for
    the next)."""
    for i in range(len(abstract["blocks"])):
        name = f"layer_{i}"
        # one expression: a name bound here would keep the tree the layer
        # was made as alive in this frame while the consumer works
        yield layer(
            make_params(
                seed, {"blocks": {name: abstract["blocks"][name]}}, dtype=dtype
            )["blocks"][name], n_heads, n_kv_heads,
        )


def to_reference(seed: int, abstract, n_heads: int, n_kv_heads: int,
                 dtype=None) -> dict:
    """``{"embed", "lnf_g", "head", "layers"}`` with ``layers`` the generator
    above (call again for a second pass over the layers)."""
    top = make_params(
        seed, {k: abstract[k] for k in ("embed", "norm_final", "lm_head")},
        dtype=dtype,
    )
    f = _flat(top)
    return {
        "embed": f["embed/tok/embedding"],
        "lnf_g": f["norm_final/scale"],
        "head": f["lm_head/shard/kernel"],
        "layers": layers(seed, abstract, n_heads, n_kv_heads, dtype),
    }


def layer_bytes(abstract, itemsize: int = 4) -> int:
    """The largest layer's float32 weights: what the reference holds at a
    time beside the top level (``to_reference``'s embedding and head)."""
    return itemsize * max(
        sum(x.size for x in jax.tree_util.tree_leaves(block))
        for block in abstract["blocks"].values()
    )


def slot_states(row) -> list:
    """The recurrent states ``[1, H, P, N]`` of a batch-1 cache tree of the
    program (``CachePool.extract``), one an ``M`` layer in the order of the
    layers (a layer without a mixer has no entry in the tree at all): what
    the reference's ``keep`` states are compared with.  The leaves as they
    are: picking them costs the caller no device program."""
    blocks = row["blocks"]
    return [
        blocks[name]["ssm"]["ssm_state"]
        for name in sorted(blocks, key=lambda n: int(n.rsplit("_", 1)[1]))
        if "ssm" in blocks[name]
    ]
