"""Seeded weights of a block-diffusion expert decoder (``sdar_moe``), and the
program's parameter tree re-laid out as ``benchmarks/reference/sdar_moe_ref.py``
names things.

Weights are ``lib/weights.make_params``'s: every leaf a function of the seed and
of the leaf's own path; matrices (the stacked experts', the router's and the
untied head's too) normal with variance ``1 / fan_in``, norm scales (the
per-head query and key norms among them) 1 + normal 0.02, the token embedding
normal 0.02.  The head is untied, so no token's own logit is lifted over the
rest (PR 32's case does not arise): logits come out with a spread near 1 over
151936 rows.

The re-layout is a permutation and reshape of elements, cast to float32.
``layers`` yields one layer at a time, made again from the seed (a layer's
sub-tree under its full path gives the same values as the whole tree did).
Unrolled stacks only (``blocks/layer_<i>/...``).
"""

import jax
import jax.numpy as jnp

from lib import weights

make_params = weights.make_params


def _flat(tree) -> dict:
    return {
        weights.path_name(p): jnp.asarray(v, jnp.float32)
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def layer(tree, n_heads: int, n_kv_heads: int) -> dict:
    """One block's sub-tree (``blocks/layer_<i>``) in the reference's layout."""
    f = _flat(tree)
    d = f["norm_attn/scale"].shape[0]
    hd = f["attn/q/shard/kernel"].shape[1] // n_heads
    kv = f["attn/kv/shard/kernel"].reshape(d, n_kv_heads, 2, hd)
    return {
        "ln1_g": f["norm_attn/scale"], "ln2_g": f["norm_mlp/scale"],
        "wq": f["attn/q/shard/kernel"].reshape(d, n_heads, hd),
        "wk": kv[:, :, 0], "wv": kv[:, :, 1],
        "wo": f["attn/out/shard/kernel"].reshape(n_heads, hd, d),
        "q_norm_g": f["attn/q_norm/scale"], "k_norm_g": f["attn/k_norm/scale"],
        "router": f["moe/router/kernel"],
        "w_gate": f["moe/experts/gate/kernel"],
        "w_up": f["moe/experts/up/kernel"],
        "w_down": f["moe/experts/down/kernel"],
    }


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
