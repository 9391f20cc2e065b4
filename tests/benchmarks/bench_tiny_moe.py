"""A toy cell of ``drivers/serve_moe.py`` for the CPU tests, added to the
throw-away benchmark of ``bench_tiny.py`` as files of its own: a
parallel-block expert decoder of one period (window 8 in contexts to 40), 8
experts top-4 of which 4 are held, 1 shared; ``logit_scale`` 8, so that at a
hidden size of 32 the logits have about unit variance and a broken layer
moves a token's rank as it would at a real width."""

import json
import os

import bench_tiny

CELL = "serve-tiny_moe"

KINDS = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "expert_selection_fn": "sigmoid", "head_dim": 16, "hidden_size": 32,
    "intermediate_size": 48, "layer_norm_eps": 1e-05, "layer_types": KINDS * 2,
    "logit_scale": 8, "num_attention_heads": 8, "num_experts": 8,
    "num_experts_per_tok": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "num_shared_experts": 1, "rope_theta": 50000,
    "sliding_window": 8, "vocab_size": 1024,
}
CONFIG = dict(
    PUBLISHED, name="tiny_moe", source="tests only", published=PUBLISHED,
    num_hidden_layers=4, num_experts=4, num_attention_heads=4,
    num_key_value_heads=1, vocab_size=256,
    reduced=["num_hidden_layers", "num_experts", "num_attention_heads",
             "num_key_value_heads", "vocab_size"],
    precision={"compute": "bfloat16"},
)
SERVE_CELL = {
    "name": CELL, "driver": "serve_moe",
    "end_to_end": ["serve_out_tok_s", "setup_s"],
    "statistics": {"serve_out_tok_s": "out_tok_s"},
    "engine": {"n_slots": 4, "slot_positions": 40, "prefill_buckets": [16],
               "max_prefills_per_tick": 1, "served_parameters": "bfloat16"},
    "reference_streams": 8, "control_precision": "float8",
    "trace_seconds": 1.0, "drain_timeout_s": 60,
    "limits": {"served_logit_gap": 0.1, "served_off_best_share": 10.0},
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
                if w["config"] == "command_a_plus_share8")
    manifest["configs"].append({
        "name": "tiny_moe", "source": "tests only",
        "file": "benchmarks/configs/tiny_moe.json",
        "reduced": CONFIG["reduced"], "why": "tests",
    })
    manifest["workloads"].append({
        "name": CELL, "config": "tiny_moe", "traffic": "tiny_longshort",
        "chips": 1, "why": "tests",
    })
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    files = {
        "BENCHMARK.json": manifest,
        "benchmarks/configs/tiny_moe.json": CONFIG,
        f"benchmarks/workloads/{CELL}.json": SERVE_CELL,
        "benchmarks/traffic/tiny_longshort.json": TRAFFIC,
    }
    for rel, data in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f, indent=1)
    return root
