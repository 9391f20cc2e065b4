"""The program's parameter tree of a latent-attention expert decoder, re-laid
out as ``benchmarks/reference/pangu_ultra_moe_ref.py`` names things.

A permutation and reshape of elements, cast to float32.  ``layers`` yields one
layer at a time, made again from the seed by ``lib/weights.make_params`` (a
leaf is a function of the seed and of its own path, so one layer's sub-tree
under its full path gives the same values as the whole tree did): an expert
layer of the share is 4.0 GB in float32 at the published widths beside the
2.0 GB draw it is upcast from, and five at once fit beside nothing.  Unrolled
stacks only (``blocks/layer_<i>/...``).
"""

import jax
import jax.numpy as jnp

from lib import weights


def _flat(tree) -> dict:
    return {
        weights.path_name(p): jnp.asarray(v, jnp.float32)
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def layer(tree, n_heads: int) -> dict:
    """One block's sub-tree (``blocks/layer_<i>``) in the reference's layout:
    a dense layer's or an expert layer's, by what the tree holds."""
    f = _flat(tree)
    rank = f["attn/kv_norm/scale"].shape[0]
    heads = lambda name, rows: f[name].reshape(rows, n_heads, -1)
    out = {
        "n_in": f["norm_attn/scale"], "n_post_attn": f["norm_post_attn/scale"],
        "n_pre_mlp": f["norm_mlp/scale"], "n_post_mlp": f["norm_post_mlp/scale"],
        "w_dq": f["attn/q_down/kernel"], "n_q": f["attn/q_norm/scale"],
        "w_uq": heads("attn/q_up/kernel", f["attn/q_norm/scale"].shape[0]),
        "w_dkv": f["attn/kv_down/kernel"], "n_kv": f["attn/kv_norm/scale"],
        "w_ukv": jnp.concatenate(
            [heads("attn/k_up/kernel", rank), heads("attn/v_up/kernel", rank)],
            axis=-1,
        ),
        "w_o": f["attn/out/kernel"].reshape(n_heads, -1, f["norm_attn/scale"].shape[0]),
    }
    if "moe/router/kernel" in f:
        out.update(
            router=f["moe/router/kernel"],
            e_gate=f["moe/experts/gate/kernel"], e_up=f["moe/experts/up/kernel"],
            e_down=f["moe/experts/down/kernel"],
            s_gate=f["moe/shared_gate/kernel"][0], s_up=f["moe/shared_up/kernel"][0],
            s_down=f["moe/shared_down/kernel"][0],
        )
    else:
        out.update(
            w_gate=f["mlp/gate/shard/kernel"], w_up=f["mlp/up/shard/kernel"],
            w_down=f["mlp/down/shard/kernel"],
        )
    return out


def layers(seed: int, abstract, n_heads: int, dtype=None):
    """A generator over the layers of the tree ``make_params(seed, abstract,
    dtype)`` would give, each in the reference's layout."""
    for i in range(len(abstract["blocks"])):
        name = f"layer_{i}"
        made = weights.make_params(
            seed, {"blocks": {name: abstract["blocks"][name]}}, dtype=dtype
        )
        yield layer(made["blocks"][name], n_heads)


def to_reference(seed: int, abstract, n_heads: int, dtype=None) -> dict:
    """``{"embed", "lnf_g", "head", "layers"}`` with ``layers`` the generator
    above (call again for a second pass over the layers)."""
    top = weights.make_params(
        seed, {k: abstract[k] for k in ("embed", "norm_final", "lm_head")},
        dtype=dtype,
    )
    f = _flat(top)
    return {
        "embed": f["embed/tok/embedding"],
        "lnf_g": f["norm_final/scale"],
        "head": f["lm_head/shard/kernel"],
        "layers": layers(seed, abstract, n_heads, dtype),
    }


def tree_to_reference(params, n_heads: int) -> dict:
    """A whole parameter tree that is already made (a test's), re-laid out."""
    f = _flat({k: params[k] for k in ("embed", "norm_final", "lm_head")})
    blocks = params["blocks"]
    return {
        "embed": f["embed/tok/embedding"],
        "lnf_g": f["norm_final/scale"],
        "head": f["lm_head/shard/kernel"],
        "layers": [
            layer(blocks[f"layer_{i}"], n_heads) for i in range(len(blocks))
        ],
    }


def layer_bytes(abstract) -> int:
    """Float32 bytes of the largest single layer of ``abstract``."""
    return max(
        4 * sum(x.size for x in jax.tree.leaves(block))
        for block in abstract["blocks"].values()
    )
