"""A toy cell of ``drivers/serve_latent_attn.py`` for the CPU tests, added to
the throw-away benchmark of ``bench_tiny.py`` as files of its own: one leading
dense layer of width 96 and three expert layers at a hidden size of 64, 4
heads that score at 16 + 8 and sum values at 16 over a latent of 32 (queries
through 48: a cache row of 40), sandwich norms, 16 sigmoid-routed experts of
width 24, top-4 with a scale of 2.5, of which 4 are held, one shared expert;
contexts to 64."""

import json
import os

import bench_tiny

CELL = "serve-tiny_latent_attn"

PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
    "moe_intermediate_size": 24, "n_routed_experts": 16,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 12,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 1,
    "q_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-05, "rope_theta": 25600, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 16,
    "vocab_size": 256,
}
CONFIG = dict(
    PUBLISHED, num_hidden_layers=4, first_k_dense_replace=1,
    n_routed_experts=4, num_nextn_predict_layers=0,
    name="tiny_latent_attn", source="tests only", published=PUBLISHED,
    reduced=["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
             "num_nextn_predict_layers"],
    # float32 compute over the bfloat16-served weights: at top-4 of 16 with a
    # scale of 2.5 ONE expert flipped at a near-tie of the 4th place moves a
    # logit by a large part of a standard deviation under bfloat16 compute;
    # the toy is there for the path, the control and the broken programs, and
    # holds those to tight limits
    precision={"compute": "float32"},
)
SERVE_CELL = {
    "name": CELL, "driver": "serve_latent_attn",
    "end_to_end": ["serve_out_tok_s", "setup_s"],
    "statistics": {"serve_out_tok_s": "out_tok_s"},
    "engine": {"n_slots": 4, "slot_positions": 64, "prefill_buckets": [16, 32],
               "max_prefills_per_tick": 2, "prefill_batch": 1,
               "served_parameters": "bfloat16"},
    "reference_streams": 6, "longest_stream_passes": 32,
    "control_precision": "float8",
    "trace_seconds": 1.0, "drain_timeout_s": 60,
    "limits": {"served_logit_gap": 0.2, "served_off_best_share": 10.0},
}
TRAFFIC = {
    "arrivals": {"kind": "closed", "clients": 6, "pool_per_client": 200,
                 "ramp_s": 0.5, "ramp_max_s": 30.0},
    "prompt_tokens": {"kind": "lognormal", "median": 16, "sigma": 0.6,
                      "min": 6, "max": 40},
    "output_tokens": {"kind": "uniform", "min": 8, "max": 16},
}


def make_root(tmp: str) -> str:
    """``bench_tiny.make_root`` plus this file's configuration, cell and
    traffic; the real cell's per-layer metrics list the toy cell too."""
    root = bench_tiny.make_root(tmp)
    path = os.path.join(root, "BENCHMARK.json")
    manifest = json.load(open(path))
    real = next(w["name"] for w in manifest["workloads"]
                if w["config"] == "openpangu_ultra_moe_718b_share16")
    manifest["configs"].append({
        "name": "tiny_latent_attn", "source": "tests only",
        "file": "benchmarks/configs/tiny_latent_attn.json",
        "reduced": CONFIG["reduced"], "why": "tests",
    })
    manifest["workloads"].append({
        "name": CELL, "config": "tiny_latent_attn", "traffic": "tiny_longdoc",
        "chips": 1, "why": "tests",
    })
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    files = {
        "BENCHMARK.json": manifest,
        "benchmarks/configs/tiny_latent_attn.json": CONFIG,
        f"benchmarks/workloads/{CELL}.json": SERVE_CELL,
        "benchmarks/traffic/tiny_longdoc.json": TRAFFIC,
    }
    for rel, data in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f, indent=1)
    return root
