"""The yardstick's own arithmetic: the trace reduction on a hand-built
trace, the traffic generator, and the copies taken from the program."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import flops, peaks, traffic, xplane  # noqa: E402

MS = 10 ** 9  # picoseconds in a millisecond

# One chip, times in ms: fusion [0,2) all-gather [1,4) fusion [5,6), a while
# that encloses everything, an async all-reduce [5.5,7.5); a second chip
# that idles but for one op.  Host: a tick over [2,5.2) and the window [0,8).
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: %(d8)d }
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(d2)d }
    events { metadata_id: 2 offset_ps: %(d1)d duration_ps: %(d3)d }
    events { metadata_id: 3 offset_ps: %(d5)d duration_ps: %(d1)d }
  }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: %(d5h)d duration_ps: %(d2)d }
  }
  lines { id: 3 name: "Steps" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(d8)d }
  }
  event_metadata { key: 1 value { id: 1 name: "%%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(%%p.1), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "all-gather.3" } }
  event_metadata { key: 3 value { id: 3 name: "attn._attend.7" } }
  event_metadata { key: 4 value { id: 4 name: "all-reduce-start.2" } }
  event_metadata { key: 9 value { id: 9 name: "%%while.4 = (s32[], f32[768]{0}) while(%%tuple.1), body=%%all-gather_body" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: %(d1)d duration_ps: %(d1)d }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(d8)d }
    events { metadata_id: 2 offset_ps: %(d2)d duration_ps: %(d3p2)d }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.launch" } }
}
""" % {"d1": MS, "d2": 2 * MS, "d3": 3 * MS, "d5": 5 * MS, "d8": 8 * MS,
       "d5h": 5 * MS + MS // 2, "d3p2": 3 * MS + MS // 5}


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
    import jax

    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(TRACE)
    folder = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t0"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(raw)
    return str(folder.parent.parent.parent)


def test_trace_reduction(trace_path):
    path = xplane.find_trace(trace_path)
    out = xplane.reduce_trace(
        path, annotations=("engine.",), window_annotation="bench_window"
    )
    assert out["window_from"] == "host"
    chip = out["chips"][0]
    ms = lambda s: round(s * 1e3, 6)
    assert ms(chip["window_s"]) == 8.0
    # the while encloses its body and does not count: busy is [0,4) + [5,6)
    assert ms(chip["busy_s"]) == 5.0
    # collectives: [1,4) and [5.5,7.5); compute covers [0,2) and [5,6)
    assert ms(chip["collective_s"]) == 5.0
    assert ms(chip["collective_exposed_s"]) == 3.5
    assert dict(chip["device_ops"])["all-gather.3"] == pytest.approx(3e-3)
    assert "while.4" not in chip["op_seconds"]
    # idle gaps [4,5) and [6,8): the first lies under the host's launch span
    gaps = dict(chip["idle_gaps"])
    assert gaps["engine.launch"] == pytest.approx(1e-3)
    assert gaps["(no host span)"] == pytest.approx(2e-3)
    assert ms(out["chips"][1]["busy_s"]) == 1.0
    # without the host's span the window is the device's first to last op
    alone = xplane.reduce_trace(path)["chips"][0]
    assert ms(alone["window_s"]) == 6.0 and ms(alone["busy_s"]) == 5.0


def test_interval_arithmetic():
    assert xplane.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert xplane.subtract([(0, 1), (2, 3)], [(0, 3)]) == []
    assert xplane.total(xplane.clip([(0, 5), (7, 9)], 4, 8)) == 2


MIX = {
    "arrivals": {"kind": "closed", "clients": 4, "pool_per_client": 25},
    "prompt_tokens": {"kind": "lognormal", "median": 192, "sigma": 0.8,
                      "min": 16, "max": 512},
    "output_tokens": {"kind": "uniform", "min": 256, "max": 512},
}


def test_traffic_repeats_for_a_seed_and_differs_between_seeds():
    a = traffic.make_requests(MIX, 2 ** 31 + 5, 50257, 1024)
    b = traffic.make_requests(MIX, 2 ** 31 + 5, 50257, 1024)
    c = traffic.make_requests(MIX, 7, 50257, 1024)
    assert a == b and a != c
    assert len(a) == 100
    # the seed lays the lengths out and pairs prompts with answers anew...
    shape = lambda rs: [(len(r["prompt"]), r["max_new_tokens"]) for r in rs]
    assert shape(a) != shape(c) and sorted(shape(a)) != sorted(shape(c))
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, c))
    # ...of the same set of sizes, so every seed does the same work
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, c))
    assert all(16 <= len(r["prompt"]) <= 512 for r in a)
    assert all(256 <= r["max_new_tokens"] <= 512 for r in a)
    # a pair that would overrun the context is cut to it
    tight = traffic.make_requests(MIX, 7, 50257, 600)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 600 for r in tight)
    assert all(1 <= t < 50257 for r in a for t in r["prompt"])
    median = sorted(len(r["prompt"]) for r in a)[50]
    assert 170 <= median <= 215


def test_unknown_arrivals_and_percentiles():
    with pytest.raises(ValueError, match="unknown arrivals"):
        traffic.make_requests(dict(MIX, arrivals={"kind": "poisson"}), 3, 50257, 1024)
    assert traffic.stratified({"kind": "uniform", "min": 0, "max": 10}, 5) == [1, 3, 5, 7, 9]
    assert traffic.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)
    assert traffic.median([3, 1, 2]) == 2


def test_copies_still_agree_with_the_program():
    """``lib/flops.py`` and ``lib/peaks.py`` were copied from
    ``tpu_parallel/utils/profiling.py``: a later change to either side
    shows here."""
    from tpu_parallel.models import gpt2_125m
    from tpu_parallel.utils import profiling

    for overrides in ({}, {"d_model": 1600, "n_layers": 48, "n_heads": 25}):
        cfg = gpt2_125m(**overrides)
        mine = flops.train_flops_per_token({
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "vocab_size": cfg.vocab_size, "seq_len": cfg.seq_len,
            "mlp_ratio": cfg.mlp_ratio,
        })
        assert mine == profiling.transformer_flops_per_token(cfg)
    assert {k: v["flops"] for k, v in peaks.PEAKS_BY_KIND.items()} == (
        profiling.PEAK_FLOPS_BY_KIND)
    assert peaks.peaks("TPU v5 lite") == {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")


def test_attention_cost_and_roofline():
    model = {"d_model": 768, "n_layers": 12, "seq_len": 1024}
    cost = flops.causal_attention_train_cost(16, model)
    assert cost["flops"] == 7 * 16 * 1024 * 1024 * 768
    assert cost["bytes"] == 12 * 16 * 1024 * 768 * 2
    seconds, bound = flops.roofline_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and seconds == pytest.approx(cost["flops"] / 197e12)
