"""The run's weights, made by the benchmark from ``--seed``.

``make_params`` fills a parameter tree of the program's SHAPE (taken with
``jax.eval_shape``: names and shapes, no values) in one jitted call on the
device: every leaf is a function of the seed and of the leaf's own path, so
the same seed gives the same weights whatever the sharding, and a single
layer can be made again by itself.  ``to_reference`` re-lays any tree of that
shape (weights, gradients, Adam moments, differences) out as
``benchmarks/reference/gpt2_ref.py`` names things - a permutation of
elements, so norms carry over.

Scales: matrices are normal with variance ``1 / fan_in`` (so logits have
about unit variance and a token's rank does not hang on the last bit),
embeddings normal 0.02, biases normal 0.02, LayerNorm scales 1 + normal
0.02.  Nothing is zero, so every term of the forward pass is exercised.
"""

import re
import zlib

import jax
import jax.numpy as jnp


def model_overrides(config: dict, **extra) -> dict:
    """The keyword arguments that build a configuration's model from its
    registry entry: the configuration file's, then the cell's own; a
    ``dtype`` is given by name."""
    out = dict(config["model_overrides"], **extra)
    if "dtype" in out:
        out["dtype"] = getattr(jnp, out["dtype"])
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def path_name(path) -> str:
    """``blocks/layer_0/attn/qkv/shard/kernel``: the dictionary keys of a
    tree path.  Boxes such as ``nn.Partitioned`` add no name, and the
    ``sharded`` level that a mesh with a model axis puts above a split
    leaf is dropped (``is_split`` tells)."""
    return "/".join(
        str(k.key) for k in path
        if isinstance(k, jax.tree_util.DictKey) and k.key != "sharded"
    )


def is_split(path) -> bool:
    """Whether the leaf carries a leading axis over the mesh's model axis
    (after the layer axis, in a scanned stack)."""
    return any(
        isinstance(k, jax.tree_util.DictKey) and k.key == "sharded"
        for k in path
    )


def _leaf(key, name: str, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    draw = jax.random.normal(k, shape, jnp.float32)
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        value = 1.0 + 0.02 * draw
    elif last == "kernel":
        value = draw / jnp.sqrt(float(shape[-2]))
    else:  # biases and embeddings
        value = 0.02 * draw
    return value.astype(dtype)


def make_params(seed: int, abstract, dtype=None, out_shardings=None):
    """A tree shaped like ``abstract`` (``ShapeDtypeStruct`` leaves), every
    leaf drawn from ``seed`` and its path, in ``dtype`` (default: each
    leaf's own), in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [path_name(p) for p, _ in leaves]

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(key, n, leaf.shape, dtype or leaf.dtype)
            for n, (_, leaf) in zip(names, leaves)
        ])

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(seed_key(seed))


def to_reference(tree, n_heads: int) -> dict:
    """Re-lay a tree of the program's parameter shape out in the
    reference's layout, as float32.  Handles both unrolled
    (``blocks/layer_<i>/...``) and scanned (``blocks/layers/block/...``
    with a leading layer axis) stacks.  The fused qkv projection holds, for
    each head, its q, k and v columns side by side."""
    flat = {}
    for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = path_name(p)
        v = jnp.asarray(v, jnp.float32)
        if is_split(p):  # one model shard in every cell: drop its axis
            v = jnp.squeeze(v, 1 if "blocks/layers/" in name else 0)
        flat[name] = v

    def layer(get):
        d = get("attn/qkv/shard/kernel").shape[0]
        hd = d // n_heads
        qkv = get("attn/qkv/shard/kernel").reshape(d, n_heads, 3, hd)
        bqkv = get("attn/qkv/shard/bias").reshape(n_heads, 3, hd)
        return {
            "ln1_g": get("norm_attn/scale"), "ln1_b": get("norm_attn/bias"),
            "wq": qkv[:, :, 0], "wk": qkv[:, :, 1], "wv": qkv[:, :, 2],
            "bq": bqkv[:, 0], "bk": bqkv[:, 1], "bv": bqkv[:, 2],
            "wo": get("attn/out/shard/kernel").reshape(n_heads, hd, d),
            "bo": get("attn/out/bias"),
            "ln2_g": get("norm_mlp/scale"), "ln2_b": get("norm_mlp/bias"),
            "w_up": get("mlp/up/shard/kernel"), "b_up": get("mlp/up/shard/bias"),
            "w_down": get("mlp/down/shard/kernel"), "b_down": get("mlp/down/bias"),
        }

    unrolled = sorted(
        {int(m.group(1)) for n in flat
         if (m := re.match(r"blocks/layer_(\d+)/", n))}
    )
    if unrolled:
        layers = [
            layer(lambda s, i=i: flat[f"blocks/layer_{i}/{s}"])
            for i in unrolled
        ]
    else:
        depth = flat["blocks/layers/block/norm_attn/scale"].shape[0]
        layers = [
            layer(lambda s, i=i: flat[f"blocks/layers/block/{s}"][i])
            for i in range(depth)
        ]
    return {
        "wte": flat["embed/tok/embedding"], "wpe": flat["embed/pos/embedding"],
        "lnf_g": flat["norm_final/scale"], "lnf_b": flat["norm_final/bias"],
        "head": flat["lm_head/shard/kernel"], "layers": layers,
    }


def leaf_norms(ref_tree) -> dict:
    """``{name: norm}`` over the leaves of a reference-layout tree."""
    return {k: float(v) for k, v in leaf_norms_device(ref_tree).items()}


def leaf_norms_device(ref_tree) -> dict:
    """``leaf_norms`` as device scalars, for use under ``jax.jit``."""
    out = {}
    for p, v in jax.tree_util.tree_flatten_with_path(ref_tree)[0]:
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in p
        )
        out[name] = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
    return out
