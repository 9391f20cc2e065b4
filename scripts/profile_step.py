"""Profile one training-step workload and print where the time goes.

Runs a few steps of the bench config (GPT-2 125M, on a TPU — it fails
without one) under ``jax.profiler.trace``, then reads the captured
``.xplane.pb`` with ``jax.profiler.ProfileData`` and prints the top ops by
device time — the evidence needed to close the MFU gap (BASELINE.md north
star) instead of guessing at configs.

Usage:
    python scripts/profile_step.py [batch] [remat] [attn] [chunk] [scan] [k=v...]
e.g.
    python scripts/profile_step.py 16 proj xla 0 1 scan_group=2
"""

import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_and_trace(batch, remat, attn, chunk, logdir, scan=None, extra=None):
    from tpu_parallel.runtime import MeshConfig, require_tpu
    from tpu_parallel.train_lib import Trainer, TrainerConfig
    from tpu_parallel.utils.profiling import sync, trace

    overrides = dict(
        dropout_rate=0.0, attn_impl=attn, loss_chunk=chunk, **(extra or {}),
    )
    if scan is not None:
        overrides["scan_layers"] = scan
    if remat in ("dots", "proj", "proj_attn"):
        overrides.update(remat=True, remat_policy=remat)
    else:
        overrides.update(remat=remat in ("1", "full"))
    print(f"device: {require_tpu()}")
    config = TrainerConfig(
        model="gpt2_125m",
        model_overrides=overrides,
        mesh=MeshConfig(data=-1),
        global_batch_size=batch,
        steps=5,
        log_every=10_000,
        donate=False,  # donation confuses repeated stepping here
    )
    trainer = Trainer(config)
    trainer.init()
    state, metrics = trainer.state, None
    for _ in range(3):  # compile + settle outside the trace
        state, metrics = trainer.funcs.step_fn(state, metrics, trainer.example_batch)
    sync((state, metrics))
    with trace(logdir):
        for _ in range(3):
            state, metrics = trainer.funcs.step_fn(
                state, metrics, trainer.example_batch
            )
        sync((state, metrics))


def summarize(logdir, top=30):
    """Aggregate per-op device time from the newest xplane.pb."""
    from jax.profiler import ProfileData

    xplanes = sorted(
        glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not xplanes:
        print("no xplane.pb captured", file=sys.stderr)
        return
    planes = list(ProfileData.from_file(xplanes[-1]).planes)
    print(f"trace: {xplanes[-1]} ({os.path.getsize(xplanes[-1]) / 1e6:.1f} MB)")
    for plane in planes:
        lines = [(line.name, len(list(line.events))) for line in plane.lines]
        print(f"plane {plane.name!r}: {sum(n for _, n in lines)} events on "
              f"{len(lines)} lines {lines[:8]}")

    printed = False
    for plane in planes:
        is_device = plane.name.startswith("/device:") or "TPU" in plane.name
        if not is_device:
            continue
        printed = True
        print(f"\n=== plane: {plane.name} ===")
        totals = {}
        for line in plane.lines:
            # only the synchronous per-op schedule: a TPU plane also carries
            # "Steps" / "XLA Modules" (whole-step spans) and "Async XLA Ops"
            # (copies in flight UNDER the compute), which would double-count
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                totals[ev.name] = totals.get(ev.name, 0) + ev.duration_ns
        if not totals:
            print("(no 'XLA Ops' events on this plane)")
            continue
        grand = sum(totals.values())
        print(f"{'time%':>7}  {'ms':>9}  op")
        for name, ns in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
            print(f"{ns / grand * 100:7.2f}  {ns / 1e6:9.3f}  {name[:90]}")
        print(f"total attributed: {grand / 1e6:.3f} ms across {len(totals)} ops")
    if not printed:
        names = ", ".join(p.name for p in planes)
        print(f"no device plane with op events (planes: {names})")


def main():
    args = sys.argv[1:]
    batch = int(args[0]) if len(args) > 0 else 16
    remat = args[1] if len(args) > 1 else "proj"
    attn = args[2] if len(args) > 2 else "xla"
    chunk = int(args[3]) if len(args) > 3 else 0
    scan = (args[4] != "0") if len(args) > 4 else None
    extra = {}
    for kv in args[5:]:
        key, val = kv.split("=", 1)
        try:
            val = int(val)
        except ValueError:
            pass
        extra[key] = val
    logdir = os.environ.get("PROFILE_DIR", "/tmp/tpu_parallel_profile")
    run_and_trace(batch, remat, attn, chunk, logdir, scan=scan, extra=extra)
    summarize(logdir)


if __name__ == "__main__":
    main()
