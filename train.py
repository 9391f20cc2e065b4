"""Training entrypoint: one script for every parallelism strategy.

Usage:
    python train.py --config=configs/mlp_dp_cpu.py            # reference parity
    python train.py --config=configs/gpt2_125m_dp.py          # pure DP
    python train.py --config=configs/gpt2_125m_tp.py          # 1-D tensor parallel
    python train.py --config=configs/gpt2_350m_pp.py          # 4-stage GPipe
    python train.py --config=configs/llama_1b_3d.py           # DP x TP x PP
    python train.py --config=configs/tiny_3d_cpu.py --config.steps=5

Any config field can be overridden on the CLI (``--config.steps=100``,
``--config.mesh.model=2`` ...) — the flag system the reference imported but
never wired up (SURVEY.md §5, config/flag row).

Telemetry (docs/11_observability.md): ``--trace-out PATH`` records
per-step ``data_wait``/``compute`` spans and writes a Perfetto-openable
Chrome trace at exit; ``--metrics-out PATH`` writes a Prometheus
text-exposition snapshot of the trainer's metric registry (MFU,
tokens/sec, loss gauges).
"""

import sys
import types

from absl import app, flags, logging
from ml_collections import config_flags

_CONFIG = config_flags.DEFINE_config_file("config", None, "Training config file.")
_TRACE_OUT = flags.DEFINE_string(
    "trace_out", "",
    "write a Chrome trace-event JSON of per-step data_wait/compute spans "
    "here (opens in Perfetto; forces a per-step device fence)",
)
_METRICS_OUT = flags.DEFINE_string(
    "metrics_out", "",
    "write a Prometheus text-exposition snapshot of the trainer's metric "
    "registry here at exit",
)


# config fields that steer the run around the Trainer (name -> default);
# everything else in a config file is a TrainerConfig field
RUN_FIELDS = dict(
    simulate_cpu_devices=0,
    checkpoint_dir="",
    checkpoint_every=100,
    data_path="",
    # "flat": contiguous seq_len windows; "packed": EOS-delimited documents
    # packed whole into rows with segment_ids (in-kernel attention masking)
    data_format="flat",
    eos_id=50256,  # GPT-2's <|endoftext|>
    eval_steps=0,
    # >0: evaluate on the held-out split every N steps during fit;
    # keep_best then also snapshots the lowest-eval-loss state to
    # {checkpoint_dir}/best
    eval_every=0,
    keep_best=False,
)


def build_trainer(cd, tracer=None):
    """``(Trainer, run options)`` from a config file's ConfigDict — the one
    construction path, shared with ``chip_smoke.py`` so the smoke drives the
    object this CLI drives."""
    from tpu_parallel.train_lib import Trainer, TrainerConfig

    trainer_cd = dict(cd)
    run = types.SimpleNamespace(
        **{k: trainer_cd.pop(k, default) for k, default in RUN_FIELDS.items()}
    )
    # fraction of the token stream held out for eval (never trained on);
    # defaults on whenever eval is requested over a real dataset
    run.eval_fraction = trainer_cd.pop(
        "eval_fraction", 0.1 if (run.eval_steps or run.eval_every) else 0.0
    )
    config = TrainerConfig.from_config_dict(trainer_cd)
    return Trainer(config, tracer=tracer), run


def main(argv):
    del argv
    cd = _CONFIG.value
    from tpu_parallel.runtime import (
        enable_compilation_cache,
        initialize,
        process_info,
        simulate_cpu_devices,
    )

    # Distributed bootstrap first: jax.distributed.initialize must run before
    # the first backend touch (simulate_cpu_devices initializes the backend to
    # validate its post-condition).
    initialize()
    enable_compilation_cache()
    sim = cd.get("simulate_cpu_devices", 0)
    if sim:
        simulate_cpu_devices(sim)
    logging.info("topology: %s", process_info())

    tracer = None
    if _TRACE_OUT.value:
        from tpu_parallel.obs import Tracer

        tracer = Tracer()
    trainer, run = build_trainer(cd, tracer)
    config = trainer.config
    logging.info(
        "model=%s params=%.1fM mesh=%s",
        config.model,
        trainer.num_params / 1e6,
        dict(trainer.mesh.shape),
    )

    data_loader = None
    if run.data_path:
        from tpu_parallel.data import DataLoader, PackedDataset, TokenDataset

        paths = (
            run.data_path.split(",") if "," in run.data_path else run.data_path
        )
        if run.data_format == "packed":
            if isinstance(paths, list):
                raise NotImplementedError(
                    "packed datasets read a single .bin stream "
                    "(concatenate shards at prepare time)"
                )
            dataset = PackedDataset(
                paths, trainer.model_config.seq_len, eos_id=run.eos_id
            )
        elif run.data_format == "flat":
            dataset = TokenDataset(paths, trainer.model_config.seq_len)
        else:
            raise ValueError(f"data_format={run.data_format!r} (flat | packed)")
        data_loader = DataLoader(
            dataset,
            trainer.mesh,
            config.global_batch_size,
            seed=config.seed,
            holdout_fraction=run.eval_fraction,
            batch_spec=trainer.batch_spec,
        )
        if run.eval_steps:
            # fail fast: an eval split smaller than one batch (or
            # eval_fraction=0) should abort before training, not after it
            data_loader.eval_view()

    def log_fn(step, metrics):
        parts = " ".join(f"{k}={v:.4f}" for k, v in sorted(metrics.items()))
        logging.info("step %d: %s", step, parts)

    if (run.eval_every or run.keep_best) and not run.checkpoint_dir:
        raise ValueError(
            "eval_every/keep_best run inside the fault-tolerant fit loop — "
            "set checkpoint_dir too"
        )
    if run.checkpoint_dir:
        # fault-tolerant path: auto-resume + periodic saves + exact data replay
        final = trainer.fit(
            run.checkpoint_dir,
            data_loader=data_loader,
            checkpoint_every=run.checkpoint_every,
            log_fn=log_fn,
            eval_every=run.eval_every,
            eval_steps=run.eval_steps or 10,
            keep_best=run.keep_best,
        )
    else:
        final = trainer.train(
            # prefetch overlaps batch assembly + H2D with the device step
            batch_iter=data_loader.prefetch() if data_loader else None,
            log_fn=log_fn,
        )
    logging.info("final: %s", final)
    if run.eval_steps:
        # held-out split: windows the train loader can never sample
        eval_iter = iter(data_loader.eval_view()) if data_loader else None
        ev = trainer.evaluate(batch_iter=eval_iter, steps=run.eval_steps)
        logging.info("eval: %s", ev)
    if tracer is not None:
        from tpu_parallel.obs import write_chrome_trace

        logging.info("trace: %s", write_chrome_trace(tracer, _TRACE_OUT.value))
    if _METRICS_OUT.value:
        from tpu_parallel.obs import write_prometheus

        logging.info(
            "metrics: %s",
            write_prometheus(trainer.registry, _METRICS_OUT.value),
        )


if __name__ == "__main__":
    # absl flags spell underscores; accept the GNU-style dashed forms the
    # docs advertise (--trace-out / --metrics-out) too
    sys.argv = [
        a.replace("--trace-out", "--trace_out").replace(
            "--metrics-out", "--metrics_out"
        )
        for a in sys.argv
    ]
    app.run(main)
