"""What this cell adds to the yardstick: device time by scope from a
hand-built trace, the routed experts' cost from counters, the configuration
file against the catalog's keys."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import moe_cost, xplane_scopes  # noqa: E402

MS = 10 ** 9  # picoseconds in a millisecond

# One chip, times in ms: a router fusion [0,1), a grouped matmul [1,3) twice
# ([1,3) and [4,6)), an attention fusion [3,4), all inside a while [0,6);
# the scope is the ``tf_op`` stat of the op's metadata, once as a string and
# once as a reference into the stat names.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: %(d6)d }
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(d1)d }
    events { metadata_id: 2 offset_ps: %(d1)d duration_ps: %(d2)d }
    events { metadata_id: 3 offset_ps: %(d3)d duration_ps: %(d1)d }
    events { metadata_id: 2 offset_ps: %(d4)d duration_ps: %(d2)d }
  }
  lines { id: 2 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(d6)d }
  }
  event_metadata { key: 1 value { id: 1 name: "%%fusion.7 = f32[8,128]{1,0} fusion(%%p.1), kind=kLoop"
    stats { metadata_id: 5 str_value: "jit(f)/blocks/layer_0/moe/moe.router/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%%ragged-dot.3 = bf16[256,4096]{1,0} custom-call(%%a, %%b)"
    stats { metadata_id: 5 ref_value: 6 } } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.9 = bf16[8,128]{1,0} fusion(%%p.2), kind=kOutput"
    stats { metadata_id: 5 str_value: "jit(f)/blocks/layer_0/attn/attn.window/dot_general:" } } }
  event_metadata { key: 9 value { id: 9 name: "%%while.4 = (s32[]) while(%%t.1), body=%%b" } }
  stat_metadata { key: 5 value { id: 5 name: "tf_op" } }
  stat_metadata { key: 6 value { id: 6 name: "jit(f)/blocks/layer_0/moe/moe.experts/ragged_dot:" } }
}
planes { id: 2 name: "/host:CPU" }
""" % {"d1": MS, "d2": 2 * MS, "d3": 3 * MS, "d4": 4 * MS, "d6": 6 * MS}


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    import jax

    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(TRACE)
    path = tmp_path_factory.mktemp("trace") / "host.xplane.pb"
    path.write_bytes(raw)
    return str(path)


def test_device_time_by_scope(trace_file):
    out = xplane_scopes.by_pattern(
        trace_file, (r"moe\.", r"moe\.router", r"attn\.window", r"ragged-dot",
                     r"attn\.full")
    )
    ms = lambda s: round(s * 1e3, 6)
    assert ms(out["busy_s"]) == 6.0  # the while encloses and does not count
    assert ms(out[r"moe\."]["seconds"]) == 5.0 and out[r"moe\."]["events"] == 3
    assert ms(out[r"moe\.router"]["seconds"]) == 1.0
    assert ms(out[r"attn\.window"]["seconds"]) == 1.0
    assert out["ragged-dot"] == {"seconds": pytest.approx(4e-3), "events": 2}
    assert out[r"attn\.full"] == {"seconds": 0.0, "events": 0}


def test_a_trace_without_a_device_plane_gives_nothing(tmp_path):
    import jax

    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/host:CPU" }'
    )
    path = tmp_path / "cpu.xplane.pb"
    path.write_bytes(raw)
    assert xplane_scopes.by_pattern(str(path), ("moe",)) is None


def test_routed_experts_cost_is_a_lower_bound_from_counters():
    experts = {"d_model": 4096, "width": 4096, "bytes_per_value": 2}
    before = {"moe_calls": 100, "moe_assignments_held": 3200,
              "moe_experts_touched_mean": 14.0}
    after = {"moe_calls": 132, "moe_assignments_held": 4224,
             "moe_experts_touched_mean": 14.0}
    gained = moe_cost.counters_between(before, after)
    assert gained == {"calls": 32, "held_rows": 1024,
                      "touched": pytest.approx(448.0)}
    cost = moe_cost.routed_experts_cost(
        gained["held_rows"], gained["touched"], experts
    )
    assert cost["flops"] == 1024 * 6 * 4096 * 4096
    # 448 touched experts' three matrices, and each row in and out
    assert cost["bytes"] == 2 * (448 * 3 * 4096 * 4096 + 1024 * 2 * 4096)
    assert moe_cost.counters_between({}, {}) == {
        "calls": 0, "held_rows": 0, "touched": 0.0
    }


def test_configuration_file_against_the_catalog():
    """Every key of the published ``config.json`` is in the file's top
    level, unchanged unless ``reduced`` names it; no width is reduced."""
    data = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "command_a_plus_share8.json"
    )))
    published = data["published"]
    changed = {k for k, v in published.items() if data[k] != v}
    assert changed == set(data["reduced"])
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size",
                       "num_attention_heads", "num_key_value_heads"}
    assert (data["hidden_size"], data["head_dim"], data["intermediate_size"],
            data["num_experts_per_tok"], data["num_shared_experts"],
            data["sliding_window"], data["rope_theta"]) == (
        4096, 128, 4096, 8, 4, 4096, 50000)
    assert (data["num_hidden_layers"], data["num_experts"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["vocab_size"]) == (4, 16, 16, 1, 32768)
    assert published["num_experts"] == 128 and data["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if json.loads(l)["name"] == "command-a-plus-05-2026")
        assert published == row["config"]
        assert data["source"] == row["source_url"]


def test_message_table_is_xplane_protos():
    """The hand-built message types have the field numbers, types and labels
    of ``tsl/profiler/protobuf/xplane.proto``, where TensorFlow's generated
    module is installed to compare with."""
    pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    for name in ("XSpace", "XPlane", "XLine", "XEvent", "XStat",
                 "XEventMetadata", "XStatMetadata"):
        theirs = {
            f.name: (f.number, f.type, f.label)
            for f in getattr(pb2, name).DESCRIPTOR.fields
        }
        ours = {f[0]: tuple(f[1:4]) for f in xplane_scopes._MESSAGES[name]}
        assert ours == theirs, name
