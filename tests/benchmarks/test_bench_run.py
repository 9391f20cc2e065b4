"""The harness end to end on the CPU, on the throw-away benchmark of
``bench_tiny.py``: a cell, a configuration, traffic and a metric added as
files of their own run with no edit to a file of the real benchmark; a
broken timed path comes out ``correct: false``; the lower-precision control
fails a limit; without a chip ``run.py`` prints no result."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402

SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def bench_run():
    import run

    return run


def drive(bench_run, root, cell, trace=0, control=False, seconds=0.5):
    """A run with the look for a chip skipped and everything else real."""
    return bench_run.run_cell(
        cell, SEED, seconds, trace, control, check_device=False,
        bench_dir=os.path.join(root, "benchmarks"), root=root,
    )


def test_run_without_a_chip_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "train-gpt2_125m-1chip", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_training_cell_added_as_files(bench_run, root, monkeypatch):
    from lib import peaks

    monkeypatch.setitem(
        peaks.PEAKS_BY_KIND, "cpu", {"flops": 1e12, "hbm_bytes_per_s": 1e11}
    )
    plain = drive(bench_run, root, "train-tiny", control=True)
    assert plain["correct"] is True, plain
    assert set(plain["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert plain["metrics"]["train_tok_s_chip"]["value"] > 0
    assert plain["attempted"] >= 2 and plain["failed"] == 0
    assert plain["device"]["platform"] == "cpu"
    traced = drive(bench_run, root, "train-tiny", trace=1)
    assert traced["correct"] is True
    # the throw-away metric and the real readers that find something to read
    assert traced["metrics"]["tiny.steps"]["value"] == traced["attempted"]
    assert 0 <= traced["metrics"]["train.data_wait_share"]["value"] < 100
    assert traced["metrics"]["train.mfu"]["value"] > 0
    # no device plane on the CPU: trace readers return nothing, and say so
    assert "train.step_device_ms" not in traced["metrics"]


def test_training_control_fails_a_limit(bench_run, root, capsys):
    """The reference in float8, in the program's place, is not correct."""
    cell = json.load(open(os.path.join(
        root, "benchmarks", "workloads", "train-tiny.json")))
    drive(bench_run, root, "train-tiny", control=True)
    line = next(l for l in capsys.readouterr().out.splitlines()
                if l.startswith("control float8:"))
    numbers = dict(kv.split("=") for kv in line.split(": ", 1)[1].split())
    assert any(float(v) > cell["limits"][k] for k, v in numbers.items()), line


def test_step_that_returns_its_state_unchanged_is_not_correct(
    bench_run, root, monkeypatch
):
    import jax

    from tpu_parallel import train_lib

    real_build = train_lib.build_train_functions

    def build(*args, **kwargs):
        funcs = real_build(*args, **kwargs)

        def frozen(state, metrics, batch):
            keep = jax.tree.map(lambda x: x.copy(), state.params)
            new_state, new_metrics = funcs.step_fn(state, metrics, batch)
            return new_state.replace(params=keep), new_metrics

        return dataclasses.replace(funcs, step_fn=frozen)

    monkeypatch.setattr(train_lib, "build_train_functions", build)
    out = drive(bench_run, root, "train-tiny")
    assert out["correct"] is False
