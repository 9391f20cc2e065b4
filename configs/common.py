"""Shared config plumbing for the shipped configs.

ml_collections only allows CLI overrides on *declared* fields, so every
config pre-declares the commonly tuned model knobs here — e.g.
``--config.model_overrides.attn_impl=flash`` works out of the box instead
of raising AttributeError.  Extra kwargs become additional declared fields.
"""

from ml_collections import ConfigDict, config_dict


def model_overrides(**kw) -> ConfigDict:
    # defaults mirror TransformerConfig/GPTConfig so declaring them here is
    # behavior-neutral; they exist to make the fields CLI-addressable
    base = dict(
        # attention: "xla" | "flash" | "ring" | "ulysses"
        attn_impl="xla",
        # flash tile sizes: placeholders (None = derived from the shape,
        # ops.flash_attention.flash_plan); an int is used for both passes
        flash_block_q=config_dict.placeholder(int),
        flash_block_k=config_dict.placeholder(int),
        # sliding-window attention (0 = full causal)
        attn_window=0,
        # remat: "full" | "proj" | "proj_attn" | "dots" (remat=False to disable)
        remat=True,
        remat_policy="full",
        scan_layers=True,
        dropout_rate=0.0,
        loss_chunk=0,
        # MoE routing family (only meaningful with moe_experts > 0)
        moe_router="topk",
        # bidirectional (encoder) attention — pairs with objective="mlm"
        bidirectional=config_dict.placeholder(bool),
        # model-shape knobs: placeholders (None = keep the model's default;
        # the Trainer drops None-valued overrides) so e.g.
        # --config.model_overrides.n_layers=2 works on any config
        vocab_size=config_dict.placeholder(int),
        seq_len=config_dict.placeholder(int),
        n_layers=config_dict.placeholder(int),
        d_model=config_dict.placeholder(int),
        n_heads=config_dict.placeholder(int),
        n_kv_heads=config_dict.placeholder(int),
    )
    base.update(kw)
    return ConfigDict(base)
