"""A throw-away benchmark at toy size, for the CPU tests and rehearsals.

``make_root(tmp)`` copies ``benchmarks/`` into ``tmp`` and adds - as new files
and new manifest entries only - a tiny configuration, a training cell, a
serving cell with its traffic, and one per-layer metric.  Nothing of the real
benchmark is edited: that the harness then runs these is the proof that a
later PR can add a cell, a configuration or a metric as files of its own.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_MODEL = {
    "d_model": 32, "n_layers": 2, "n_heads": 4, "head_dim": 8, "mlp_ratio": 4,
    "seq_len": 64, "vocab_size": 256, "vocab_real": 250,
}

TINY_CONFIG = {
    "name": "tiny", "source": "tests only", "registry": "tiny",
    "model_overrides": {
        "d_model": 32, "n_layers": 2, "n_heads": 4, "seq_len": 64,
        "vocab_size": 256, "dtype": "bfloat16",
    },
    "model": TINY_MODEL, "reduced": [],
}

TINY_TRAIN_CELL = {
    "name": "train-tiny", "driver": "train",
    "end_to_end": ["train_tok_s_chip", "setup_s"],
    "trainer": {
        "model_overrides": {"attn_impl": "xla", "scan_layers": False,
                            "remat_policy": "proj_attn"},
        "mesh": {"data": 1, "model": 1, "pipe": 1, "seq": 1},
        "num_minibatches": 2, "steps": 100000, "optimizer": "adamw",
        "lr_schedule": "cosine", "learning_rate": 0.0006, "warmup_steps": 20,
        "weight_decay": 0.1, "grad_clip": 1.0, "ema_decay": 0.0, "donate": True,
    },
    "steps_per_call": 2, "reference_block_rows": 4,
    "control_precision": "float8",
    "limits": {"loss_gap": 0.02, "grad_norm_gap": 0.05, "update_norm_gap": 0.05},
}

TINY_TRAIN_TRAFFIC = {"kind": "pretrain", "rows_per_step": 8, "seq_len": 64}

TINY_SERVE_CELL = {
    "name": "serve-tiny", "driver": "serve",
    "end_to_end": ["serve_out_tok_s", "setup_s"],
    "statistics": {"serve_out_tok_s": "out_tok_s"},
    "engine": {"n_slots": 4, "prefill_chunk_tokens": 32,
               "max_prefills_per_tick": 2, "served_parameters": "bfloat16"},
    "reference_streams": 4, "control_precision": "float8",
    "trace_seconds": 1.0, "drain_timeout_s": 60,
    "limits": {"served_logit_gap": 0.03},
}

TINY_SERVE_TRAFFIC = {
    "arrivals": {"kind": "closed", "clients": 6, "pool_per_client": 200,
                 "ramp_s": 0.5, "ramp_max_s": 30.0},
    "prompt_tokens": {"kind": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 4, "max": 40},
    "output_tokens": {"kind": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 4, "max": 20},
}

TINY_METRIC = '''"""A throw-away per-layer metric: the steps the window ran."""

META = {"name": "tiny.steps", "layer": "Trainer", "unit": "steps",
        "source": "program_counter", "moves": "train_tok_s_chip"}


def read(run):
    return run.attempted or None
'''


def make_root(tmp: str, device_kind: str = "cpu") -> str:
    """Build the throw-away benchmark under ``tmp``; returns its root."""
    bench = os.path.join(tmp, "benchmarks")
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), bench,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    manifest["configs"].append({
        "name": "tiny", "source": "tests only",
        "file": "benchmarks/configs/tiny.json", "reduced": [], "why": "tests",
    })
    manifest["workloads"] += [
        {"name": "train-tiny", "config": "tiny", "traffic": "tiny_pretrain",
         "chips": 1, "why": "tests"},
        {"name": "serve-tiny", "config": "tiny", "traffic": "tiny_batch",
         "chips": 1, "why": "tests"},
    ]
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tok_s_chip":
            m["workloads"].append("train-tiny")
        if m["name"] == "serve_out_tok_s":
            m["workloads"].append("serve-tiny")
    for m in manifest["per_layer"]:
        if "train-gpt2_125m-1chip" in m.get("workloads", []):
            m["workloads"].append("train-tiny")
        if "serve-gpt2_xl-batch" in m.get("workloads", []):
            m["workloads"].append("serve-tiny")
    manifest["per_layer"].append({
        "name": "tiny.steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "Trainer",
        "moves": "train_tok_s_chip", "workloads": ["train-tiny"],
    })
    files = {
        "BENCHMARK.json": manifest,
        "benchmarks/configs/tiny.json": TINY_CONFIG,
        "benchmarks/workloads/train-tiny.json": TINY_TRAIN_CELL,
        "benchmarks/workloads/serve-tiny.json": TINY_SERVE_CELL,
        "benchmarks/traffic/tiny_pretrain.json": TINY_TRAIN_TRAFFIC,
        "benchmarks/traffic/tiny_batch.json": TINY_SERVE_TRAFFIC,
    }
    for rel, data in files.items():
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(data, f, indent=1)
    with open(os.path.join(bench, "metrics", "tiny.steps.py"), "w") as f:
        f.write(TINY_METRIC)
    return tmp
