"""The serving driver end to end on the CPU, on the throw-away benchmark:
HTTP/SSE through the daemon from a child that never imports JAX."""

import json
import os
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


def drive(root, cell, control=False, seconds=2.0):
    import run

    return run.run_cell(
        cell, SEED, seconds, 0, control, check_device=False,
        bench_dir=os.path.join(root, "benchmarks"), root=root,
    )


def test_serving_cell_and_its_control(root, capsys):
    out = drive(root, "serve-tiny", control=True)
    assert out["correct"] is True, out
    assert set(out["metrics"]) == {"serve_out_tok_s", "setup_s"}
    assert out["attempted"] >= 6 and out["failed"] == 0
    assert all(m["value"] > 0 for m in out["metrics"].values())
    cell = json.load(open(os.path.join(
        root, "benchmarks", "workloads", "serve-tiny.json")))
    line = next(l for l in capsys.readouterr().out.splitlines()
                if l.startswith("control float8:"))
    assert float(line.split("=")[1]) > cell["limits"]["served_logit_gap"], line


def test_altered_token_is_not_correct(root, monkeypatch):
    from tpu_parallel.serving import ServingEngine

    real = ServingEngine._sample_first

    def altered(self, logits, outs):
        return [(t + 1) % 250 for t in real(self, logits, outs)]

    monkeypatch.setattr(ServingEngine, "_sample_first", altered)
    out = drive(root, "serve-tiny")
    assert out["correct"] is False
    assert out["failed"] == 0  # every stream ended; what they carried is wrong
