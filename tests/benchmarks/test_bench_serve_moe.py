"""``drivers/serve_moe.py`` end to end on the CPU, on a toy cell added as
files of its own (``bench_tiny_moe.py``): HTTP/SSE through the daemon, the
reference given the same share; the timed path broken two ways and the float8
control each come out over the limit."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny_moe  # noqa: E402

SEED = 2 ** 31 + 4242


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_moe.make_root(str(tmp_path_factory.mktemp("bench_moe")))


@pytest.fixture
def fresh_programs():
    """The engine caches its jitted programs by model: a test that breaks
    the model's code needs them traced anew, and must not leave its broken
    ones behind."""
    from tpu_parallel.serving import engine

    caches = (engine._engine_fns, engine._fused_engine_fn)
    for cache in caches:
        cache.cache_clear()
    yield
    for cache in caches:
        cache.cache_clear()


def drive(root, control=False, trace=0, seconds=2.0):
    import run

    return run.run_cell(
        bench_tiny_moe.CELL, SEED, seconds, trace, control, check_device=False,
        bench_dir=os.path.join(root, "benchmarks"), root=root,
    )


def checks(capsys):
    out = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("check "):
            name, value = line[6:].split(": ", 1)
            out[name] = float(value.split()[0])
    return out


def test_toy_cell_its_control_and_its_counters(root, capsys):
    out = drive(root, control=True)
    text = capsys.readouterr().out
    assert out["correct"] is True, text[-3000:]
    assert set(out["metrics"]) == {"serve_out_tok_s", "setup_s"}
    assert out["attempted"] >= 6 and out["failed"] == 0
    limits = bench_tiny_moe.SERVE_CELL["limits"]
    control = next(l for l in text.splitlines() if l.startswith("control float8:"))
    numbers = dict(kv.split("=") for kv in control.split(": ", 1)[1].split())
    assert set(numbers) == set(limits)
    assert all(float(v) > limits[k] for k, v in numbers.items()), control
    flips = next(l for l in text.splitlines() if l.startswith("router alone"))
    assert "change their top-4 set" in flips
    counters = next(l for l in text.splitlines() if l.startswith("engine counters:"))
    held = int(counters.split("moe_assignments_held ")[1].split(",")[0])
    total = int(counters.split("moe_assignments_total ")[1].split(",")[0])
    assert 0.35 < held / total < 0.65  # 4 of 8 experts held


def test_traced_run_reports_the_counter_metrics(root):
    out = drive(root, trace=1)
    assert out["correct"] is True
    metrics = out["metrics"]
    assert 1.0 <= metrics["moe.rows_per_expert_max_over_mean.longshort"]["value"]
    assert 0 < metrics["moe.experts_touched.longshort"]["value"] <= 4
    assert 0 < metrics["engine.occupancy.longshort"]["value"] <= 100
    assert metrics["engine.busy_tick_ms.longshort"]["value"] > 0
    # no device plane on the CPU: the trace readers find nothing, and say so
    assert "moe.time_share.longshort" not in metrics
    assert "moe.expert_matmul_roofline.longshort" not in metrics
    assert "device.idle_share.longshort" not in metrics


def test_a_window_layer_made_full_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    from tpu_parallel.models.layers import Attention

    monkeypatch.setattr(Attention, "window", property(lambda self: 0))
    out = drive(root)
    limit = bench_tiny_moe.SERVE_CELL["limits"]["served_logit_gap"]
    assert out["correct"] is False
    assert checks(capsys)["served_logit_gap"] > limit
    assert out["failed"] == 0  # every stream ended; what they carried is wrong


def test_a_held_experts_output_dropped_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    from tpu_parallel.models import moe

    real = moe._grouped_ffn

    def without_the_first(rows, weights, group_sizes):
        w_gate, w_up, w_down = weights
        return real(rows, (w_gate, w_up, w_down.at[0].set(0)), group_sizes)

    monkeypatch.setattr(moe, "_grouped_ffn", without_the_first)
    out = drive(root)
    limit = bench_tiny_moe.SERVE_CELL["limits"]["served_logit_gap"]
    assert out["correct"] is False
    assert checks(capsys)["served_logit_gap"] > limit
