"""``drivers/serve_hybrid.py`` end to end on the CPU, on a toy cell added as
files of its own (``bench_tiny_hybrid.py``): HTTP/SSE through the daemon, the
served streams held to one uninterrupted recurrence of the reference; the
timed path broken (a slot's state not cleared between its occupants; a state
rounded to bfloat16) and each control come out over a limit; the cost
functions of the two rooflines and the span's work counted from a trace."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny_hybrid  # noqa: E402
from lib import ssm_cost  # noqa: E402

SEED = 2 ** 31 + 5151


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_hybrid.make_root(
        str(tmp_path_factory.mktemp("bench_hybrid"))
    )


@pytest.fixture
def fresh_programs():
    """The engine caches its jitted programs by model: a test that breaks
    the program's code needs them traced anew, and must not leave its broken
    ones behind."""
    import jax

    from tpu_parallel.serving import cache_pool, engine

    caches = (engine._engine_fns, engine._fused_engine_fn,
              cache_pool.default_row_fns)

    def clear():
        for cache in caches:
            cache.cache_clear()
        jax.clear_caches()  # the pool's row functions are traced by identity

    clear()
    yield
    clear()


def drive(root, control=False, trace=0, seconds=2.0):
    import run

    return run.run_cell(
        bench_tiny_hybrid.CELL, SEED, seconds, trace, control,
        check_device=False, bench_dir=os.path.join(root, "benchmarks"),
        root=root,
    )


def checks(text):
    out = {}
    for line in text.splitlines():
        if line.startswith("check "):
            name, value = line[6:].split(": ", 1)
            out[name] = float(value.split()[0])
    return out


def test_toy_cell_its_controls_and_its_counters(root, capsys):
    out = drive(root, control=True)
    text = capsys.readouterr().out
    assert out["correct"] is True, text[-3000:]
    assert set(out["metrics"]) == {"serve_out_tok_s", "setup_s"}
    assert out["attempted"] >= 6 and out["failed"] == 0
    limits = bench_tiny_hybrid.SERVE_CELL["limits"]
    control = next(l for l in text.splitlines() if l.startswith("control float8:"))
    numbers = dict(
        kv.split("=") for kv in control.split(": ", 1)[1].split(" (")[0].split()
    )
    assert set(numbers) == set(limits)
    assert any(float(v) > limits[k] for k, v in numbers.items()), control
    # the state rounded to bfloat16 moves no served token, and is told by
    # the one number that reads the state's own bits
    rounded = next(l for l in text.splitlines()
                   if l.startswith("control state_bfloat16:"))
    assert rounded.endswith("over its limit: served_state_bfloat16_share)")
    assert "served_state_bfloat16_share=100 " in rounded
    got = checks(text)
    assert 0 < got["served_state_gap"] < limits["served_state_gap"]
    assert got["served_state_bfloat16_share"] < 0.1
    probe = next(l for l in text.splitlines() if l.startswith("state probe:"))
    assert probe.endswith("8 held")
    assert "ssm_plan: {'ssm_layers': 6, 'attention_layers': 2" in text
    counters = next(l for l in text.splitlines() if l.startswith("engine counters:"))
    real = int(counters.split("prefill_tokens_real ")[1].split(",")[0])
    padded = int(counters.split("prefill_tokens_padded ")[1].split(",")[0])
    assert real > 0 and padded > 0
    # one row a call: the groups the scheduler admits add no compile shape
    shapes = next(l for l in text.splitlines() if l.startswith("warm-up:"))
    assert "('prefill', 1, 8), ('prefill', 1, 16)" in shapes
    assert "('prefill', 2" not in shapes
    assert checks(text)["compiles_in_window"] == 0


def test_traced_run_reports_the_counter_metrics(root):
    out = drive(root, trace=1)
    assert out["correct"] is True
    metrics = out["metrics"]
    assert 0 < metrics["engine.prefill_pad_share.shortchat"]["value"] < 100
    assert 0 < metrics["engine.occupancy.shortchat"]["value"] <= 100
    assert metrics["engine.busy_tick_ms.shortchat"]["value"] > 0
    assert "engine.launch_ahead_share.shortchat" in metrics
    # no device plane on the CPU: the trace readers find nothing, and say so
    for name in ("ssm.time_share", "ssm.state_update_roofline",
                 "ssm.scan_roofline", "device.idle_share"):
        assert f"{name}.shortchat" not in metrics


def test_a_state_left_between_occupants_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    """Whole-prompt admission that keeps the slot's old state (the scatter
    skips the state leaves): the next request continues its predecessor's
    recurrence."""
    from tpu_parallel.serving import cache_pool

    real = cache_pool.beam_cache_batch_axis

    def without_the_state(path, leaf):
        if cache_pool._leaf_name(path).startswith("ssm_state"):
            return None
        return real(path, leaf)

    monkeypatch.setattr(cache_pool, "beam_cache_batch_axis", without_the_state)
    out = drive(root)
    text = capsys.readouterr().out
    limits = bench_tiny_hybrid.SERVE_CELL["limits"]
    assert out["correct"] is False
    got = checks(text)
    assert (got["served_logit_gap"] > limits["served_logit_gap"]
            or got["served_off_best_share"] > limits["served_off_best_share"])
    assert got["served_state_gap"] > limits["served_state_gap"]
    assert out["failed"] == 0  # every stream ended; what they carried is wrong


def test_a_state_kept_in_bfloat16_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    """The program with its recurrent state rounded to bfloat16 after every
    update (what halving the state's stream would do): its served tokens and
    its state's distance stay within their limits, and ``correct`` is false
    by the state's own bits."""
    import jax

    from tpu_parallel.models import ssm

    def rounded(fn):
        def call(*args, **kwargs):
            y, state = fn(*args, **kwargs)
            return y, jax.lax.reduce_precision(state, 8, 7)

        return call

    monkeypatch.setattr(ssm, "ssd_step", rounded(ssm.ssd_step))
    monkeypatch.setattr(ssm, "ssd_scan", rounded(ssm.ssd_scan))
    out = drive(root)
    text = capsys.readouterr().out
    limits = bench_tiny_hybrid.SERVE_CELL["limits"]
    got = checks(text)
    assert out["correct"] is False and out["failed"] == 0
    assert got["served_state_bfloat16_share"] == 100.0
    # (the toy's widest logit gap sits near its limit with any state: its
    # sample is whatever the window's timing ended, so it is not held here)
    for name in ("served_off_best_share", "served_state_gap"):
        assert got[name] <= limits[name], text[-3000:]


def test_configuration_file_against_the_catalog():
    """Every key of the published ``config.json`` is in the file's top level,
    unchanged: nothing is reduced; and the parameters, counted from the keys,
    are the 3,191,396,096 that one chip holds whole."""
    import json

    data = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "granite_4_0_h_micro.json"
    )))
    published = data["published"]
    assert data["reduced"] == [] and data["reduced_why"] == {}
    assert {k for k, v in published.items() if data[k] != v} == set()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if json.loads(l)["name"] == "granite-4.0-h-micro")
        assert published == row["config"]
        assert data["source"] == row["source_url"]
    d, inter = data["hidden_size"], data["shared_intermediate_size"]
    heads, kv = data["num_attention_heads"], data["num_key_value_heads"]
    hd = d // heads
    m_heads, m_p = data["mamba_n_heads"], data["mamba_d_head"]
    groups, n, k = data["mamba_n_groups"], data["mamba_d_state"], data["mamba_d_conv"]
    d_inner = m_heads * m_p
    assert d_inner == data["mamba_expand"] * d
    conv = d_inner + 2 * groups * n
    mamba = (d * (2 * d_inner + 2 * groups * n + m_heads) + conv * k + conv
             + 3 * m_heads + d_inner + d_inner * d)
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    shared = d * 2 * inter + inter * d + 2 * d  # the MLP and a layer's two norms
    kinds = data["layer_types"]
    assert len(kinds) == data["num_hidden_layers"] == 40
    assert kinds == (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    total = (kinds.count("mamba") * (mamba + shared)
             + kinds.count("attention") * (attention + shared)
             + data["vocab_size"] * d + d)
    assert (mamba, attention) == (25_847_232, 10_485_760)
    assert total == data["model"]["parameters"] == 3_191_396_096
    state = kinds.count("mamba") * (m_heads * m_p * n * 4 + (k - 1) * conv * 2)
    assert state == data["model"]["state_bytes_per_slot"]
    assert data["model"]["kv_bytes_per_position"] == (
        kinds.count("attention") * 2 * kv * hd * 2
    )
    assert data["precision"]["recurrent_state"] == "float32"
    cell = json.load(open(os.path.join(
        REPO, "benchmarks", "workloads",
        "serve-granite_4_0_h_micro-shortchat.json",
    )))
    assert cell["engine"]["slot_positions"] == data["model"]["slot_positions"]


# -- lib/ssm_cost.py -----------------------------------------------------------

GRANITE = {"layers": 36, "heads": 64, "head_dim": 64, "d_state": 128,
           "groups": 1, "state_bytes": 4, "bytes_per_value": 2}


def test_state_update_cost_is_the_state_once_in_and_once_out():
    one = ssm_cost.state_update_cost(1, GRANITE)
    state = 64 * 64 * 128
    assert one["bytes"] == 36 * (2 * state * 4 + (2 * 4096 + 256 + 64) * 2)
    assert one["flops"] == 36 * 5 * state
    # 64 live slots: 9.66 GB of state and 0.04 GB of rows a decode step
    step = ssm_cost.state_update_cost(64, GRANITE)
    assert 9.6e9 < step["bytes"] < 9.8e9
    assert ssm_cost.state_update_cost(128, GRANITE)["bytes"] == 2 * step["bytes"]
    # a bfloat16 state would halve the stream: the cost follows the stated type
    half = ssm_cost.state_update_cost(64, dict(GRANITE, state_bytes=2))
    assert half["bytes"] < 0.51 * step["bytes"]


def test_scan_cost_counts_real_tokens_and_one_state_a_prompt():
    cost = ssm_cost.scan_cost(192, 1, GRANITE)
    state = 64 * 64 * 128
    assert cost["bytes"] == 36 * (192 * (2 * 4096 + 256 + 64) * 2 + state * 4)
    assert cost["flops"] == 36 * 192 * 4 * state
    # under the chunked form's own count (its products inside a chunk on top)
    chunked = 36 * 192 * (4 * state + 192 * (128 + 4096))
    assert cost["flops"] < chunked
    two = ssm_cost.scan_cost(384, 2, GRANITE)
    assert two["bytes"] == 2 * cost["bytes"] and two["flops"] == 2 * cost["flops"]
    assert ssm_cost.scan_cost(0, 0, GRANITE) == {"flops": 0, "bytes": 0}


def test_span_work_is_counted_from_the_trace():
    window = {"tokens_out": 57_000, "prefills": 360, "prefill_calls": 360,
              "decode_ticks": 120, "prefill_tokens_real": 90_000,
              "prefill_tokens_padded": 30_000}
    # 3 ops a layer-step: two saw 37 steps, one was cut by the span's edge
    step = {1: 37, 2: 37, 3: 36}
    # two calls of one row x 256 (an op of the loop over chunks ran twice a
    # call), one of 1 x 64 whose last op fell outside the trace
    scan = {("1", "256"): {10: 2, 11: 2, 12: 4}, ("1", "64"): {20: 1, 21: 1, 22: 0}}
    got = ssm_cost.span_work(step, scan, window, 8)
    assert got["decode_steps"] == pytest.approx(110 / 3)
    assert got["live_slots"] == pytest.approx((57_000 - 360) / (120 * 8))
    assert got["slot_steps"] == pytest.approx(110 / 3 * 59.0)
    assert got["prefill_calls"] == {"1x64": 1, "1x256": 2}
    assert got["positions"] == 2 * 256 + 64
    assert got["prompt_tokens"] == pytest.approx(0.75 * 576)
    assert got["prompts"] == pytest.approx(3.0)
    nothing = ssm_cost.span_work({}, {}, {}, 8)
    assert nothing["slot_steps"] == 0 and nothing["prompt_tokens"] == 0


MS = 10 ** 9  # picoseconds in a millisecond
# One chip: a decode tick's loop runs the two ops of a layer's state update
# twice (ids 1, 2; the third run of id 1 is cut off by the trace's end), a
# prefill of 1 x 256 runs its scan op once; an op outside the scopes, and the
# enclosing while, count for nothing.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: %(d8)d }
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(d1)d }
    events { metadata_id: 2 offset_ps: %(d1)d duration_ps: %(d1)d }
    events { metadata_id: 1 offset_ps: %(d2)d duration_ps: %(d1)d }
    events { metadata_id: 2 offset_ps: %(d3)d duration_ps: %(d1)d }
    events { metadata_id: 3 offset_ps: %(d4)d duration_ps: %(d1)d }
    events { metadata_id: 4 offset_ps: %(d5)d duration_ps: %(d1)d }
    events { metadata_id: 1 offset_ps: %(d6)d duration_ps: %(d1)d }
  }
  event_metadata { key: 1 value { id: 1 name: "%%fusion.1 = f32[4,8,16,16]{3,2,1,0} fusion(%%p.1), kind=kLoop"
    stats { metadata_id: 5 str_value: "jit(f)/while/body/blocks/layer_0/ssm/ssm.step/mul:" } } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.2 = f32[4,8,16]{2,1,0} fusion(%%p.2), kind=kLoop"
    stats { metadata_id: 5 str_value: "jit(f)/while/body/blocks/layer_0/ssm/ssm.step/reduce_sum:" } } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.3 = f32[1,1,8,256,256]{4,3,2,1,0} fusion(%%p.3), kind=kOutput"
    stats { metadata_id: 5 str_value: "jit(g)/blocks/layer_0/ssm/ssm.scan/call1x256/dot_general:" } } }
  event_metadata { key: 4 value { id: 4 name: "%%fusion.4 = bf16[1,256,64]{2,1,0} fusion(%%p.4), kind=kLoop"
    stats { metadata_id: 5 str_value: "jit(g)/blocks/layer_0/mlp/dot_general:" } } }
  event_metadata { key: 9 value { id: 9 name: "%%while.4 = (s32[]) while(%%t.1), body=%%b"
    stats { metadata_id: 5 str_value: "jit(f)/while/body/blocks/layer_0/ssm/ssm.step/while:" } } }
  stat_metadata { key: 5 value { id: 5 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU" }
""" % {f"d{i}": i * MS for i in range(1, 9)}


def test_runs_of_each_compiled_op_by_scope(tmp_path):
    import jax

    from drivers import serve_hybrid
    from lib import xplane_counts

    path = tmp_path / "host.xplane.pb"
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(TRACE))
    runs = xplane_counts.executions(str(path), serve_hybrid.RUNS)
    assert runs == {"step": {(): {1: 3, 2: 2}}, "scan": {("1", "256"): {3: 1}}}
    work = ssm_cost.span_work(
        runs["step"][()], runs["scan"],
        {"tokens_out": 100, "prefills": 4, "prefill_calls": 4, "decode_ticks": 3,
         "prefill_tokens_real": 768, "prefill_tokens_padded": 256}, 8,
    )
    assert work["decode_steps"] == 2.5 and work["live_slots"] == 4.0
    assert work["prefill_calls"] == {"1x256": 1}
    assert work["prompt_tokens"] == pytest.approx(192.0)
    cpu = tmp_path / "cpu.xplane.pb"
    cpu.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/host:CPU" }'
    ))
    assert xplane_counts.executions(str(cpu), serve_hybrid.RUNS) is None


# -- the state probe and the state's two numbers ---------------------------------


def test_state_numbers_read_distance_and_the_bits():
    import numpy as np

    from drivers import serve_hybrid

    rng = np.random.default_rng(0)
    ref = [[rng.standard_normal((4, 8, 16)).astype(np.float32) for _ in range(3)]
           for _ in range(2)]
    same = [[r.copy() for r in layers] for layers in ref]
    gap, share = serve_hybrid.state_numbers(same, ref)
    assert gap == 0.0 and share < 1.0
    off = [[r.copy() for r in layers] for layers in ref]
    off[1][2] = off[1][2] * 1.5  # one layer of one stream, half its norm off
    gap, _ = serve_hybrid.state_numbers(off, ref)
    assert gap == pytest.approx(0.5, rel=1e-6)
    short = [[(r.view(np.uint32) & 0xFFFF0000).view(np.float32) for r in layers]
             for layers in ref]
    gap, share = serve_hybrid.state_numbers(short, ref)
    assert share == 100.0 and 0 < gap < 0.01
    with pytest.raises(ValueError):
        serve_hybrid.state_numbers([ref[0][:2]], [ref[0]])


def test_state_probe_holds_the_longest_and_a_seeded_reservoir():
    import types

    from drivers import serve_hybrid

    class Pool:
        def extract(self, slot):
            return {"blocks": {"layer_0": {"ssm": {"ssm_state": [f"state of slot {slot}"]}},
                               "layer_1": {"attn": {}}}}

    class Engine:
        def __init__(self):
            self.pool, self.released = Pool(), []
            self._slot_out = {}

        def release_slot(self, slot):
            self.released.append(slot)

    def stream(engine, slot, prompt, tokens, reason="length"):
        engine._slot_out[slot] = types.SimpleNamespace(
            request=types.SimpleNamespace(prompt=list(prompt)),
            tokens=[0] * tokens, finish_reason=reason,
        )
        engine.release_slot(slot)

    engine = Engine()
    probe = serve_hybrid.StateProbe(engine, 7, most=3)
    stream(engine, 0, [1, 2], 4)  # before the window: one read, to compile
    stream(engine, 1, [1, 3], 4)
    assert probe.reads == 1 and probe.longest is None
    probe.active = True
    stream(engine, 2, [5] * 3, 5)
    stream(engine, 3, [6] * 9, 9)  # the longest from here on
    stream(engine, 4, [7] * 2, 2, reason="cancelled")  # not a finished stream
    for slot in range(5, 40):
        stream(engine, slot % 8, [slot] * 2, 3)
    probe.active = False
    stream(engine, 0, [9] * 30, 30)  # after the window
    assert engine.released[:5] == [0, 1, 2, 3, 4] and len(engine.released) == 41
    assert probe.seen == 36  # every finished stream of the window but the longest
    held = probe.close()
    assert len(held) == 3 and held[0].prompt == (6,) * 9 and held[0].tokens == 9
    assert str(held[0].state[0]) == "state of slot 3"
    assert len({s.prompt for s in held}) == 3 and probe.engine is None
    # a reservoir: not just the first streams that came
    assert any(s.prompt[0] > 6 for s in held[1:])
    assert probe.reads < 20
