"""The program's parameter tree of a parallel-block expert decoder, re-laid
out as ``benchmarks/reference/cohere2_moe_ref.py`` names things.

A permutation and reshape of elements, cast to float32.  ``layers`` yields one
layer at a time, made again from the seed by ``lib/weights.make_params`` (a
leaf is a function of the seed and of its own path, so one layer's sub-tree
under its full path gives the same values as the whole tree did): a layer is
4.1 GB in float32 at the published widths, and four at once do not fit beside
anything.  Unrolled stacks only (``blocks/layer_<i>/...``).
"""

import jax
import jax.numpy as jnp

from lib import weights


def _flat(tree) -> dict:
    return {
        weights.path_name(p): jnp.asarray(v, jnp.float32)
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def layer(tree, n_heads: int, n_kv_heads: int) -> dict:
    """One block's sub-tree (``blocks/layer_<i>``) in the reference's layout."""
    f = _flat(tree)
    d = f["norm/scale"].shape[0]
    hd = f["attn/q/shard/kernel"].shape[1] // n_heads
    kv = f["attn/kv/shard/kernel"].reshape(d, n_kv_heads, 2, hd)
    return {
        "ln_g": f["norm/scale"],
        "wq": f["attn/q/shard/kernel"].reshape(d, n_heads, hd),
        "wk": kv[:, :, 0], "wv": kv[:, :, 1],
        "wo": f["attn/out/shard/kernel"].reshape(n_heads, hd, d),
        "router": f["moe/router/kernel"],
        "w_gate": f["moe/experts/gate/kernel"],
        "w_up": f["moe/experts/up/kernel"],
        "w_down": f["moe/experts/down/kernel"],
        "s_gate": f["moe/shared_gate/kernel"],
        "s_up": f["moe/shared_up/kernel"],
        "s_down": f["moe/shared_down/kernel"],
    }


def layers(seed: int, abstract, n_heads: int, n_kv_heads: int, dtype=None):
    """A generator over the layers of the tree ``make_params(seed, abstract,
    dtype)`` would give, each in the reference's layout."""
    for i in range(len(abstract["blocks"])):
        name = f"layer_{i}"
        made = weights.make_params(
            seed, {"blocks": {name: abstract["blocks"][name]}}, dtype=dtype
        )
        yield layer(made["blocks"][name], n_heads, n_kv_heads)


def to_reference(seed: int, abstract, n_heads: int, n_kv_heads: int,
                 dtype=None) -> dict:
    """``{"embed", "lnf_g", "layers"}`` with ``layers`` the generator above
    (call again for a second pass over the layers)."""
    top = weights.make_params(
        seed, {k: abstract[k] for k in ("embed", "norm_final")}, dtype=dtype
    )
    f = _flat(top)
    return {
        "embed": f["embed/tok/embedding"],
        "lnf_g": f["norm_final/scale"],
        "layers": layers(seed, abstract, n_heads, n_kv_heads, dtype),
    }
