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

What a draw holds on the device: the tree in the type it was made in, let go
leaf by leaf as each float32 copy is READY (the copy is waited for: nothing
this file dispatches is in flight when it returns), so at most one tree in
the made type plus its float32 copy, and between two draws nothing: the
generator keeps no layer of its own, and a caller that drops the layer it
was given before it asks for the next holds one layer at a time
(``reference/sdar_moe_ref.py::replay_bytes_bound`` counts this).
"""

import jax
import jax.numpy as jnp

from lib import weights

make_params = weights.make_params


def _float32(seed: int, abstract, dtype) -> dict:
    """``{name: float32 leaf}`` of ``make_params(seed, abstract, dtype)``.
    The tree is made as a flat dictionary by name (a leaf is a function of
    the seed and its name alone), so that each leaf can be let go once its
    float32 copy is ready."""
    made = make_params(seed, {
        weights.path_name(p): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]
    }, dtype=dtype)
    out = {}
    while made:
        name, leaf = made.popitem()
        out[name] = jax.block_until_ready(jnp.asarray(leaf, jnp.float32))
        del leaf
    return out


def layer(f: dict, n_heads: int, n_kv_heads: int) -> dict:
    """One block's float32 leaves, by their names under ``blocks/layer_<i>/``,
    in the reference's layout."""
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


def layer_weights(seed: int, abstract, i: int, n_heads: int, n_kv_heads: int,
                  dtype=None) -> dict:
    """Layer ``i`` of the tree ``make_params(seed, abstract, dtype)`` would
    give, in the reference's layout; nothing else of it outlives the call."""
    prefix = f"blocks/layer_{i}/"
    f = _float32(
        seed, {"blocks": {f"layer_{i}": abstract["blocks"][f"layer_{i}"]}}, dtype
    )
    return layer(
        {name[len(prefix):]: leaf for name, leaf in f.items()}, n_heads, n_kv_heads
    )


def layers(seed: int, abstract, n_heads: int, n_kv_heads: int, dtype=None):
    """A generator over ``layer_weights`` of every layer; it holds no layer
    between two draws."""
    for i in range(len(abstract["blocks"])):
        yield layer_weights(seed, abstract, i, n_heads, n_kv_heads, dtype)


def to_reference(seed: int, abstract, n_heads: int, n_kv_heads: int,
                 dtype=None) -> dict:
    """``{"embed", "lnf_g", "head", "layers"}`` with ``layers`` the generator
    above (call ``layers`` again for a second pass over them)."""
    f = _float32(
        seed, {k: abstract[k] for k in ("embed", "norm_final", "lm_head")}, dtype
    )
    return {
        "embed": f["embed/tok/embedding"],
        "lnf_g": f["norm_final/scale"],
        "head": f["lm_head/shard/kernel"],
        "layers": layers(seed, abstract, n_heads, n_kv_heads, dtype),
    }
