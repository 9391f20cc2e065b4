"""A toy cell of ``drivers/serve_latent_moe.py`` for the CPU tests, added to
the throw-away benchmark of ``bench_tiny.py`` as files of its own: one period
``MEMEMEM*EME`` of one-sublayer layers at a hidden size of 64: 8 recurrent
heads of 16 in 2 groups with a state of 16, 4 query heads of 16 on 2 K/V
heads, 16 sigmoid-routed ``relu2`` experts of width 24 in a latent of 32,
top-4 with a selection bias and a scale of 2.5, of which 8 are held, one
shared expert of width 48; chunks of 8 in contexts to 48."""

import json
import os

import bench_tiny

CELL = "serve-tiny_latent_moe"

PUBLISHED = {
    "attention_bias": False, "chunk_size": 8, "conv_kernel": 4, "expand": 2,
    "head_dim": 16, "hidden_size": 64,
    "hybrid_override_pattern": "MEMEMEM*EME" * 2,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 16,
    "mamba_hidden_act": "silu", "mamba_num_heads": 8, "mamba_proj_bias": False,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 24, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_group": 1, "n_groups": 2,
    "n_routed_experts": 16, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 22,
    "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
    "routed_scaling_factor": 2.5, "ssm_state_size": 16,
    "tie_word_embeddings": False, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "vocab_size": 256,
}
CONFIG = dict(
    PUBLISHED, num_hidden_layers=11, n_routed_experts=8,
    num_nextn_predict_layers=0,
    name="tiny_latent_moe", source="tests only", published=PUBLISHED,
    reduced=["num_hidden_layers", "n_routed_experts", "num_nextn_predict_layers"],
    # float32 compute over the bfloat16-served weights: at top-4 of 16 with a
    # scale of 2.5 ONE expert flipped at a near-tie of the 4th place moves a
    # logit by a whole standard deviation (read: widest gap 0.05 on two seeds,
    # 0.82 and 1.04 on two others under bfloat16 compute), which the real
    # cell's top-22 of 512 divides by ten; the toy is there for the path, the
    # controls and the broken programs, and holds those to tight limits
    precision={"compute": "float32", "recurrent_state": "float32"},
)
SERVE_CELL = {
    "name": CELL, "driver": "serve_latent_moe",
    "end_to_end": ["serve_out_tok_s", "setup_s"],
    "statistics": {"serve_out_tok_s": "out_tok_s"},
    "engine": {"n_slots": 4, "slot_positions": 48, "prefill_buckets": [8, 16],
               "max_prefills_per_tick": 2, "prefill_batch": 1,
               "served_parameters": "bfloat16"},
    "reference_streams": 6, "control_precision": "float8",
    "control_state_precision": "bfloat16",
    "trace_seconds": 1.0, "drain_timeout_s": 60,
    "limits": {"served_logit_gap": 0.2, "served_off_best_share": 10.0,
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
                if w["config"] == "nemotron_3_super_120b_share4")
    manifest["configs"].append({
        "name": "tiny_latent_moe", "source": "tests only",
        "file": "benchmarks/configs/tiny_latent_moe.json",
        "reduced": CONFIG["reduced"], "why": "tests",
    })
    manifest["workloads"].append({
        "name": CELL, "config": "tiny_latent_moe", "traffic": "tiny_reasoning",
        "chips": 1, "why": "tests",
    })
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    files = {
        "BENCHMARK.json": manifest,
        "benchmarks/configs/tiny_latent_moe.json": CONFIG,
        f"benchmarks/workloads/{CELL}.json": SERVE_CELL,
        "benchmarks/traffic/tiny_reasoning.json": TRAFFIC,
    }
    for rel, data in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f, indent=1)
    return root
