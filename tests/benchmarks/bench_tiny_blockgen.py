"""A toy cell of ``drivers/serve_blockgen.py`` for the CPU tests, added to
the throw-away benchmark of ``bench_tiny.py`` as files of its own: a
block-diffusion expert decoder of 3 layers, 4 query heads of 16 over 2 K/V
heads with q/k norms, 8 softmax-routed experts of width 48 top-2, blocks of
4 in contexts to 64, the last id the mask; requests at 4, 2 and 1 denoising
steps a block."""

import json
import os

import bench_tiny

CELL = "serve-tiny_blockgen"

PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 48,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 256,
}
CONFIG = dict(
    PUBLISHED, name="tiny_blockgen", source="tests only", published=PUBLISHED,
    reduced=[], model={"block_len": 4, "mask_token_id": 255},
    precision={"compute": "bfloat16"},
)
SERVE_CELL = {
    "name": CELL, "driver": "serve_blockgen",
    "end_to_end": ["serve_out_tok_s", "setup_s"],
    "statistics": {"serve_out_tok_s": "out_tok_s"},
    "engine": {"n_slots": 4, "slot_positions": 64, "prefill_buckets": [16, 32],
               "max_prefills_per_tick": 2, "prefill_batch": 1,
               "served_parameters": "bfloat16"},
    "reference_streams": 8, "control_precision": "float8",
    "trace_seconds": 1.0, "drain_timeout_s": 60,
    # the widest gaps are there for a broken layer: at a hidden size of 64
    # one expert changed by a rounding moves a logit by more than float8 does
    "limits": {"served_logit_gap": 3.0, "served_off_best_share": 15.0,
               "served_choice_gap": 2.0, "served_choice_off_share": 25.0},
}
TRAFFIC = {
    "arrivals": {"kind": "closed", "clients": 6, "pool_per_client": 200,
                 "ramp_s": 0.5, "ramp_max_s": 30.0},
    "prompt_tokens": {"kind": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 4, "max": 32},
    "output_tokens": {"kind": "uniform", "min": 16, "max": 24},
    "token_ids": {"below": 250},
    "request_knobs": {"denoising_steps": {"values": [4, 2, 1]}},
}


def make_root(tmp: str) -> str:
    """``bench_tiny.make_root`` plus this file's configuration, cell and
    traffic; the real cell's per-layer metrics list the toy cell too."""
    root = bench_tiny.make_root(tmp)
    path = os.path.join(root, "BENCHMARK.json")
    manifest = json.load(open(path))
    real = next(w["name"] for w in manifest["workloads"]
                if w["config"] == "sdar_30b_a3b_depth6")
    manifest["configs"].append({
        "name": "tiny_blockgen", "source": "tests only",
        "file": "benchmarks/configs/tiny_blockgen.json",
        "reduced": [], "why": "tests",
    })
    manifest["workloads"].append({
        "name": CELL, "config": "tiny_blockgen", "traffic": "tiny_blockgen",
        "chips": 1, "why": "tests",
    })
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    files = {
        "BENCHMARK.json": manifest,
        "benchmarks/configs/tiny_blockgen.json": CONFIG,
        f"benchmarks/workloads/{CELL}.json": SERVE_CELL,
        "benchmarks/traffic/tiny_blockgen.json": TRAFFIC,
    }
    for rel, data in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f, indent=1)
    return root
