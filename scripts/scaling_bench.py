"""Scaling-efficiency harness: DP / TP / PP / SP / EP step time vs devices.

Usage: ``python scripts/scaling_bench.py [strategy ...]`` — no args runs
every strategy (dp tp pp pp_1f1b sp ep).

BASELINE.json's metric is "tokens/sec/chip AND DP/TP/PP scaling efficiency"
— this harness produces the scaling half.  For each strategy it runs the
same logical workload on meshes of 1/2/4/8 devices and reports one JSON
line per point:

    {"strategy": "dp", "n_chips": 4, "step_time_ms": ...,
     "tokens_per_sec": ..., "efficiency_vs_1": ...}

Scaling regimes (efficiency definitions):

- **DP — weak scaling**: global batch grows with the mesh, per-chip work
  constant.  Perfect = constant step time; efficiency = t1 / tn.
- **TP — strong scaling**: fixed batch, the model axis splits every
  projection.  Perfect = time / n; efficiency = t1 / (n * tn).
- **PP — strong scaling with the GPipe bubble**: fixed batch cut into
  microbatches over n stages; ideal includes the bubble factor
  (m + n - 1) / m, reported separately as ``ideal_fraction``.
- **SP — strong scaling over the token axis**: fixed batch x seq, ring
  attention rotates K/V around the seq axis.  Same efficiency definition
  as TP; the communication is the ring rotation, not projection
  all-reduces.
- **EP — strong scaling of routed-expert FLOPs**: 8 fixed experts sharded
  over the model axis (TP rides along structurally); efficiency as TP —
  read the ep lines against tp as the incremental cost of routed dispatch
  at equal mesh shape.

Without 8 local accelerators the harness simulates 8 CPU devices — the
numbers then measure *structural* overhead (collective count, schedule
shape), not ICI bandwidth, but the harness runs unchanged on a real slice
(it uses whatever ``jax.devices()`` offers when that is >= 8).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from tpu_parallel.runtime import simulate_cpu_devices

    # Use a real slice when one is attached; otherwise simulate 8 CPU
    # devices.  The simulation must be decided before the first backend
    # touch, so probe the accelerator count via the env rather than
    # jax.devices() (which would initialize the wrong backend).
    want_real = os.environ.get("SCALING_BENCH_REAL", "") == "1"
    if not want_real:
        simulate_cpu_devices(8)

    import jax

    if jax.device_count() < 8:
        raise SystemExit(
            f"need 8 devices for the 1/2/4/8 sweep, have {jax.device_count()} "
            "(unset SCALING_BENCH_REAL to simulate on CPU)"
        )

    from tpu_parallel.runtime import MeshConfig, device_record, make_mesh
    from tpu_parallel.train_lib import Trainer, TrainerConfig
    from tpu_parallel.utils.profiling import sync

    per_chip_batch = 8
    seq_len = 128
    base = dict(
        n_layers=8,
        d_model=128,
        n_heads=8,
        seq_len=seq_len,
        vocab_size=512,
        dropout_rate=0.0,
        remat=False,
    )

    def run(strategy: str, n: int) -> dict:
        devices = jax.devices()[:n]
        overrides = dict(base)
        if strategy == "dp":
            mesh_cfg, batch = MeshConfig(data=n), per_chip_batch * n
        elif strategy == "tp":
            mesh_cfg, batch = MeshConfig(data=1, model=n), per_chip_batch
        elif strategy == "pp":
            mesh_cfg, batch = MeshConfig(data=1, pipe=n), per_chip_batch
            overrides["num_microbatches"] = per_chip_batch
        elif strategy == "pp_1f1b":
            # the memory-bounded schedule: same mesh/microbatching as pp,
            # gradients computed inside the interleaved fwd/bwd scan
            # (parallel/pp.py pipeline_1f1b_grads) — reads against pp as
            # the structural cost of the 1F1B buffer walk + second ring
            mesh_cfg, batch = MeshConfig(data=1, pipe=n), per_chip_batch
            overrides["num_microbatches"] = per_chip_batch
            overrides["pipe_schedule"] = "1f1b"
        elif strategy == "sp":
            # sequence parallelism: fixed batch x seq, tokens sharded over
            # the ring — strong scaling like TP, communication is the K/V
            # rotation instead of the projection all-reduces
            mesh_cfg, batch = MeshConfig(data=1, seq=n), per_chip_batch
            overrides["attn_impl"] = "ring"
        elif strategy == "ep":
            # expert parallelism: FIXED 8 routed experts sharded over the
            # model axis (each rank runs 8/n experts' FLOPs + one psum
            # combine) — strong scaling of the expert MLP work.  The model
            # axis also splits attention (structural TP rides along); the
            # ep lines therefore read against tp as the incremental cost
            # of routed dispatch at equal mesh shape.
            mesh_cfg, batch = MeshConfig(data=1, model=n), per_chip_batch
            overrides["moe_experts"] = 8
        else:
            raise ValueError(strategy)
        config = TrainerConfig(
            model="tiny",
            model_overrides=overrides,
            mesh=mesh_cfg,
            global_batch_size=batch,
            steps=8,
            log_every=10_000,
            donate=True,
        )
        trainer = Trainer(config, mesh=make_mesh(mesh_cfg, devices=devices))
        trainer.init()
        state, metrics = trainer.state, None
        for _ in range(2):  # compile + settle
            state, metrics = trainer.funcs.step_fn(
                state, metrics, trainer.example_batch
            )
        sync((state, metrics))
        iters = 8
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = trainer.funcs.step_fn(
                state, metrics, trainer.example_batch
            )
        sync((state, metrics))
        dt = (time.perf_counter() - t0) / iters
        return dict(
            strategy=strategy,
            simulated=jax.devices()[0].platform == "cpu",
            **device_record(),
            n_chips=n,
            step_time_ms=round(dt * 1e3, 3),
            tokens_per_sec=round(batch * seq_len / dt, 1),
            global_batch=batch,
        )

    results = []
    valid = ("dp", "tp", "pp", "pp_1f1b", "sp", "ep")
    wanted = sys.argv[1:] or list(valid)
    unknown = [w for w in wanted if w not in valid]
    if unknown:
        raise SystemExit(f"unknown strategies {unknown}; valid: {valid}")
    for strategy in wanted:
        t1 = None
        for n in (1, 2, 4, 8):
            r = run(strategy, n)
            if n == 1:
                t1 = r["step_time_ms"]
            if strategy == "dp":  # weak scaling: ideal is constant step time
                r["efficiency_vs_1"] = round(t1 / r["step_time_ms"], 4)
            else:  # strong scaling: ideal is t1 / n
                r["efficiency_vs_1"] = round(t1 / (n * r["step_time_ms"]), 4)
            if strategy == "pp":
                m = per_chip_batch  # microbatches
                r["ideal_fraction"] = round(m / (m + n - 1), 4)
            elif strategy == "pp_1f1b":
                m = per_chip_batch
                r["ideal_fraction"] = round(m / (m + 2 * n - 2), 4)
            results.append(r)
            print(json.dumps(r), flush=True)
    return results


if __name__ == "__main__":
    main()
