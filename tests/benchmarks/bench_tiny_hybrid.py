"""A toy cell of ``drivers/serve_hybrid.py`` for the CPU tests, added to the
throw-away benchmark of ``bench_tiny.py`` as files of its own: a hybrid
decoder of two periods (``mamba, mamba, attention, mamba``), 4 query heads of
16 over 2 K/V heads, 8 state heads of 16 with a state of 16, chunks of 8 in
contexts to 48; ``logits_scaling`` 1/32, so that at a hidden size of 64 the
logits have about unit variance and a broken layer moves a token's rank as it
would at a real width."""

import json
import os

import bench_tiny

CELL = "serve-tiny_hybrid"

KINDS = ["mamba", "mamba", "attention", "mamba"]
PUBLISHED = {
    "attention_multiplier": 0.09, "embedding_multiplier": 3,
    "hidden_size": 64, "layer_types": KINDS * 2, "logits_scaling": 0.03125,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 8, "mamba_proj_bias": False,
    "num_attention_heads": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.5,
    "rms_norm_eps": 1e-05, "shared_intermediate_size": 128,
    "tie_word_embeddings": True, "vocab_size": 256,
}
CONFIG = dict(
    PUBLISHED, name="tiny_hybrid", source="tests only", published=PUBLISHED,
    reduced=[],
    precision={"compute": "bfloat16", "recurrent_state": "float32"},
)
SERVE_CELL = {
    "name": CELL, "driver": "serve_hybrid",
    "end_to_end": ["serve_out_tok_s", "setup_s"],
    "statistics": {"serve_out_tok_s": "out_tok_s"},
    "engine": {"n_slots": 4, "slot_positions": 48, "prefill_buckets": [8, 16],
               "max_prefills_per_tick": 2, "prefill_batch": 1,
               "served_parameters": "bfloat16"},
    "reference_streams": 8, "control_precision": "float8",
    "control_state_precision": "bfloat16",
    "trace_seconds": 1.0, "drain_timeout_s": 60,
    "limits": {"served_logit_gap": 0.1, "served_off_best_share": 10.0,
               "served_state_gap": 0.3, "served_state_bfloat16_share": 1.0},
}
TRAFFIC = {
    "arrivals": {"kind": "closed", "clients": 6, "pool_per_client": 200,
                 "ramp_s": 0.5, "ramp_max_s": 30.0},
    "prompt_tokens": {"kind": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 4, "max": 24},
    "output_tokens": {"kind": "uniform", "min": 8, "max": 16},
}


def make_root(tmp: str) -> str:
    """``bench_tiny.make_root`` plus this file's configuration, cell and
    traffic; the real cell's per-layer metrics list the toy cell too."""
    root = bench_tiny.make_root(tmp)
    path = os.path.join(root, "BENCHMARK.json")
    manifest = json.load(open(path))
    real = next(w["name"] for w in manifest["workloads"]
                if w["config"] == "granite_4_0_h_micro")
    manifest["configs"].append({
        "name": "tiny_hybrid", "source": "tests only",
        "file": "benchmarks/configs/tiny_hybrid.json",
        "reduced": [], "why": "tests",
    })
    manifest["workloads"].append({
        "name": CELL, "config": "tiny_hybrid", "traffic": "tiny_shortchat",
        "chips": 1, "why": "tests",
    })
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    files = {
        "BENCHMARK.json": manifest,
        "benchmarks/configs/tiny_hybrid.json": CONFIG,
        f"benchmarks/workloads/{CELL}.json": SERVE_CELL,
        "benchmarks/traffic/tiny_shortchat.json": TRAFFIC,
    }
    for rel, data in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f, indent=1)
    return root
