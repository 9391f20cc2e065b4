"""Seeded weights of a hybrid decoder (Mamba-2 layers beside attention layers),
and the program's parameter tree re-laid out as
``benchmarks/reference/granite_hybrid_ref.py`` names things.

``make_params`` is ``lib/weights.make_params`` (every leaf a function of the
seed and of the leaf's own path; matrices normal with variance ``1 /
fan_in``, biases normal 0.02, norm scales 1 + normal 0.02) with the Mamba-2
ranges for the leaves a recurrence is sensitive to, so that under random
weights the state neither dies nor grows: ``A_log = log(uniform[1, 16])``,
``dt_bias`` the inverse softplus of a step drawn log-uniformly in ``[1e-3,
1e-1]``, ``D = 1``, conv weights uniform in ``+-1/2``.

The token embedding is normal ``EMBED_STD`` = 0.005, not 0.02.  The family
multiplies the embedding by 12 on its way in and ties the head to it: at 0.02
a token's own embedding, still in the residual after 40 layers, puts ITS OWN
logit 5 standard deviations over the rest, the model's first choice is the
token it has just read at 98.6% of positions with a margin of 0.29 (logits
spread 0.11), and no rounding, float8 included, moves a served token off the
first place: the comparison that decides ``correct`` would see nothing.  At
0.005 (my chip run, PR 32, the reference on 512 random tokens: first choice
equal to the input at 0.4% of positions, margin 0.004 at a spread of 0.028)
bfloat16 operands move 7% of first choices and float8 operands 80%.

The re-layout is a permutation and reshape of elements, cast to float32.
``layers`` yields one layer at a time, made again from the seed (a layer's
sub-tree under its full path gives the same values as the whole tree did).
Unrolled stacks only (``blocks/layer_<i>/...``).
"""

import zlib

import jax
import jax.numpy as jnp

from lib import weights


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def _step_bias(key, shape):
    dt = jnp.exp(_uniform(key, shape, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus(bias) == dt


EMBED_STD = 0.005

# leaf name -> draw(key, shape), float32
OWN_LEAVES = {
    "embedding": lambda key, shape: EMBED_STD * jax.random.normal(
        key, shape, jnp.float32
    ),
    "A_log": lambda key, shape: jnp.log(_uniform(key, shape, 1.0, 16.0)),
    "dt_bias": _step_bias,
    "D": lambda key, shape: jnp.ones(shape, jnp.float32),
    "conv_weight": lambda key, shape: _uniform(key, shape, -0.5, 0.5),
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
    """One block's sub-tree (``blocks/layer_<i>``) in the reference's layout."""
    f = _flat(tree)
    out = {
        "ln1_g": f["norm_attn/scale"], "ln2_g": f["norm_mlp/scale"],
        "mlp_in": jnp.concatenate(
            [f["mlp/gate/shard/kernel"], f["mlp/up/shard/kernel"]], axis=1
        ),
        "mlp_out": f["mlp/down/shard/kernel"],
    }
    if "ssm/in_proj/kernel" in f:
        out.update({
            "w_in": f["ssm/in_proj/kernel"],
            "conv_w": f["ssm/conv_weight"].T, "conv_b": f["ssm/conv_bias"],
            "dt_bias": f["ssm/dt_bias"], "A_log": f["ssm/A_log"],
            "D": f["ssm/D"], "norm_g": f["ssm/gate_norm/scale"],
            "w_out": f["ssm/out_proj/kernel"],
        })
        return out
    d = f["norm_attn/scale"].shape[0]
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
    dtype)`` would give, each in the reference's layout."""
    for i in range(len(abstract["blocks"])):
        name = f"layer_{i}"
        made = make_params(
            seed, {"blocks": {name: abstract["blocks"][name]}}, dtype=dtype
        )
        yield layer(made["blocks"][name], n_heads, n_kv_heads)


def to_reference(seed: int, abstract, n_heads: int, n_kv_heads: int,
                 dtype=None) -> dict:
    """``{"embed", "lnf_g", "layers"}`` with ``layers`` the generator above
    (call again for a second pass over the layers)."""
    top = make_params(
        seed, {k: abstract[k] for k in ("embed", "norm_final")}, dtype=dtype
    )
    f = _flat(top)
    return {
        "embed": f["embed/tok/embedding"],
        "lnf_g": f["norm_final/scale"],
        "layers": layers(seed, abstract, n_heads, n_kv_heads, dtype),
    }


def slot_states(row) -> list:
    """The recurrent states ``[1, H, P, N]`` of a batch-1 cache tree of the
    program (``CachePool.extract``), one a recurrent layer in the order of the
    layers: what the reference's ``keep`` states are compared with.  The
    leaves as they are: picking them costs the caller no device program."""
    blocks = row["blocks"]
    return [
        blocks[f"layer_{i}"]["ssm"]["ssm_state"]
        for i in range(len(blocks)) if "ssm" in blocks[f"layer_{i}"]
    ]
