"""``drivers/serve_latent_moe.py`` end to end on the CPU, on a toy cell added
as files of its own (``bench_tiny_latent_moe.py``): HTTP/SSE through the
daemon, the served streams held to one uninterrupted pass of the reference;
the timed path broken (the router's selection bias dropped; a state rounded
to bfloat16) and each control come out over a limit; the configuration's
file against the catalog and its parameter count from its keys; the cell's
entries and traffic as the issue names them; the cost function of the
experts' roofline and the readers on hand-made facts; the comparison's
memory a layer at a time."""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny_latent_moe  # noqa: E402
import run as bench_run  # noqa: E402
from drivers import serve_latent_moe  # noqa: E402
from lib import latent_moe_cost, moe_cost  # noqa: E402

SEED = 2 ** 31 + 4545
CELL = "serve-nemotron_3_super_120b_share4-reasoning"
CONFIG = "nemotron_3_super_120b_share4"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_latent_moe.make_root(
        str(tmp_path_factory.mktemp("bench_latent_moe"))
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
        jax.clear_caches()

    clear()
    yield
    clear()


def drive(root, control=False, trace=0, seconds=2.0):
    return bench_run.run_cell(
        bench_tiny_latent_moe.CELL, SEED, seconds, trace, control,
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
    limits = bench_tiny_latent_moe.SERVE_CELL["limits"]
    control = next(l for l in text.splitlines() if l.startswith("control float8:"))
    numbers = dict(
        kv.split("=") for kv in control.split(": ", 1)[1].split(" (")[0].split()
    )
    assert set(numbers) == set(limits)
    assert any(float(v) > limits[k] for k, v in numbers.items()), control
    rounded = next(l for l in text.splitlines()
                   if l.startswith("control state_bfloat16:"))
    # (a state rounded after every step also flips an expert here and there,
    # which at the toy's top-4 of 16 can carry its distance past the limit)
    assert "served_state_bfloat16_share" in rounded.split("over its limit: ")[1]
    assert "served_state_bfloat16_share=100 " in rounded
    # the witness is the reference in the precision the program computes in:
    # the toy's is float32, the reference itself, and moves no first choice
    witness = next(l for l in text.splitlines()
                   if l.startswith("control witness_float32:"))
    assert "served_off_best_share=0 " in witness
    got = checks(text)
    assert 0 < got["served_state_gap"] < limits["served_state_gap"]
    assert got["served_state_bfloat16_share"] < 0.1
    probe = next(l for l in text.splitlines() if l.startswith("state probe:"))
    assert probe.endswith("6 held")
    assert ("ssm_plan: {'ssm_layers': 5, 'attention_layers': 1, "
            "'expert_layers': 5, 'layers': 11") in text
    assert "'latent': 32, 'matrices': 2, 'ffn': 'relu2'" in text
    # (a held stream whose last token reached its client after the window
    # closed is left out of the sample)
    reference = next(l for l in text.splitlines()
                     if l.startswith("reference: ") and " streams, " in l)
    assert int(reference.split()[1]) >= 4 and "5 states a stream" in reference
    counters = next(l for l in text.splitlines() if l.startswith("engine counters:"))
    assert int(counters.split("moe_calls ")[1].split(",")[0]) > 0
    shapes = next(l for l in text.splitlines() if l.startswith("warm-up:"))
    assert "('prefill', 1, 8), ('prefill', 1, 16)" in shapes
    assert got["compiles_in_window"] == 0
    # the engine's buffers go before the comparison starts, whoever still
    # holds the engine
    assert "engine's weights and pool deleted: " in text
    # what the comparison held is stated (the CPU's runtime samples nothing)
    assert "comparison memory: sampled peak not read, bound " in text


def test_traced_run_reports_the_counter_metrics(root):
    out = drive(root, trace=1)
    assert out["correct"] is True
    metrics = out["metrics"]
    assert 0 < metrics["engine.occupancy.reasoning"]["value"] <= 100
    assert metrics["engine.busy_tick_ms.reasoning"]["value"] > 0
    assert metrics["engine.device_wait_ms.reasoning"]["value"] >= 0
    assert "engine.launch_ahead_share.reasoning" in metrics
    assert 0 < metrics["moe.experts_touched.reasoning"]["value"] <= 8
    assert metrics["moe.rows_per_expert_max_over_mean.reasoning"]["value"] >= 1
    assert "engine.device_tick_ms" in metrics
    # no device plane on the CPU: the trace readers find nothing, and say so
    for name in ("ssm.time_share", "ssm.state_update_roofline",
                 "moe.time_share", "moe.latent_proj_time_share",
                 "moe.expert_matmul_roofline", "device.idle_share"):
        assert f"{name}.reasoning" not in metrics


def test_a_dropped_selection_bias_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    """The program that chooses its experts by score alone (the correction
    bias dropped) serves other experts' outputs: the bias is drawn from the
    seed and is not zero."""
    from tpu_parallel.models import moe

    real = moe._route
    monkeypatch.setattr(moe, "_route", lambda es, logits, bias: real(es, logits, None))
    out = drive(root)
    text = capsys.readouterr().out
    limits = bench_tiny_latent_moe.SERVE_CELL["limits"]
    got = checks(text)
    assert out["correct"] is False and out["failed"] == 0
    assert (got["served_logit_gap"] > limits["served_logit_gap"]
            or got["served_off_best_share"] > limits["served_off_best_share"])


def test_a_state_kept_in_bfloat16_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    """The program with its recurrent state rounded to bfloat16 after every
    update: ``correct`` is false by the state's own bits."""
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
    got = checks(text)
    assert out["correct"] is False and out["failed"] == 0
    assert got["served_state_bfloat16_share"] == 100.0


# -- the configuration, the cell and the traffic --------------------------------


def read(*rel):
    return json.load(open(os.path.join(REPO, "benchmarks", *rel)))


def test_configuration_file_against_the_catalog():
    """Every key of the published ``config.json`` is in the file's top level,
    changed only where ``reduced`` says (no width among them); the
    parameters, counted from the keys, are the 4,648,163,712 of one chip's
    share of one stage."""
    data = read("configs", f"{CONFIG}.json")
    published = data["published"]
    assert data["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size", "num_nextn_predict_layers"]
    assert set(data["reduced_why"]) == set(data["reduced"])
    assert {k for k, v in published.items() if data[k] != v} == set(data["reduced"])
    assert (data["num_hidden_layers"], data["n_routed_experts"],
            data["vocab_size"], data["num_nextn_predict_layers"]) == (
        11, 128, 32768, 0)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if json.loads(l)["name"]
                   == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert published == row["config"]
        assert data["source"] == row["source_url"]
    pattern = data["hybrid_override_pattern"]
    assert len(pattern) == 88 and pattern[:11] == "MEMEMEM*EME"
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (40, 40, 8)
    assert serve_latent_moe.parameters(data) == 4_648_163_712
    assert data["model"]["parameters"] == 4_648_163_712
    # the uncut model from the same count: the published 120.67 B
    whole = dict(published, num_nextn_predict_layers=0, published=published)
    assert 120.6e9 < serve_latent_moe.parameters(whole) < 120.7e9
    m_heads, m_p, n = data["mamba_num_heads"], data["mamba_head_dim"], data["ssm_state_size"]
    conv = m_heads * m_p + 2 * data["n_groups"] * n
    state = 5 * (m_heads * m_p * n * 4 + (data["conv_kernel"] - 1) * conv * 2)
    assert state == data["model"]["state_bytes_per_slot"] == 21_278_720
    assert data["model"]["kv_bytes_per_position"] == (
        2 * data["num_key_value_heads"] * data["head_dim"] * 2
    ) == 1024
    assert data["precision"]["recurrent_state"] == "float32"
    for key in ("attention_positions", "latent_projections", "expert_form",
                "in_proj_split", "gate_before_norm", "time_step_limit",
                "recurrent_state_type", "router", "correction_bias",
                "initializer", "chunk_size"):
        assert len(data["assumed"][key]) > 40, key
    assert "32 v5e chips" in data["deployment"]
    cell = read("workloads", f"{CELL}.json")
    assert cell["engine"]["slot_positions"] == data["model"]["slot_positions"]
    cfg = serve_latent_moe.model_config(data, cell["engine"])
    assert (cfg.d_model, cfg.n_layers, cfg.recurrent_layers, cfg.routed_layers,
            cfg.vocab_size, cfg.seq_len) == (4096, 11, 5, 5, 32768, 4096)
    es = next(s.experts for s in cfg.layer_specs if s.experts is not None)
    assert (es.n_experts, es.top_k, es.held, es.latent, es.width, es.ffn,
            es.shared_width, es.route_scale) == (
        512, 22, (0, 128), 1024, 2688, "relu2", 5376, 5.0)


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "reasoning", 1
    )
    metric = next(m for m in manifest["end_to_end"]
                  if m["name"] == "serve_out_tok_s")
    assert metric["workloads"][-1] == CELL and metric["bound"] == 0.08
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "moe.time_share.reasoning", "moe.latent_proj_time_share.reasoning",
        "moe.expert_matmul_roofline.reasoning", "moe.experts_touched.reasoning",
        "moe.rows_per_expert_max_over_mean.reasoning",
        "ssm.time_share.reasoning", "ssm.state_update_roofline.reasoning",
        "engine.occupancy.reasoning", "engine.busy_tick_ms.reasoning",
        "engine.device_wait_ms.reasoning",
        "engine.launch_ahead_share.reasoning", "device.idle_share.reasoning",
        # what the next change to this cell starts from: prefill as a
        # quarter of the device, and the pump's lock
        "engine.prefill_tick_extra_ms.reasoning",
        "engine.prefill_pad_share.reasoning",
        "daemon.submit_ms_p50.reasoning", "clients.tpot_ms_p50.reasoning",
    ]
    assert manifest["per_layer"][-len(mine):] == mine  # appended at the end
    for name in ("engine.device_tick_ms", "engine.device_idle_share",
                 "engine.device_prefill_share",
                 "engine.device_prefill_ms_per_ktok"):
        clock = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert clock["workloads"][-1] == CELL
    mix = read("traffic", "reasoning.json")
    assert mix["arrivals"]["clients"] == 192
    assert (mix["arrivals"]["ramp_s"], mix["arrivals"]["ramp_max_s"]) == (20.0, 120.0)
    assert mix["prompt_tokens"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.8, "min": 32, "max": 2048,
    }
    assert mix["output_tokens"] == {
        "kind": "lognormal", "median": 768, "sigma": 0.6, "min": 256, "max": 2048,
    }
    cell = read("workloads", f"{CELL}.json")
    engine = cell["engine"]
    assert (engine["n_slots"], engine["slot_positions"],
            engine["max_prefills_per_tick"], engine["prefill_batch"]) == (
        128, 4096, 2, 1)
    assert max(engine["prefill_buckets"]) == 2048
    assert mix["arrivals"]["clients"] == 1.5 * engine["n_slots"]
    assert cell["reference_streams"] == 6
    assert set(cell["limits"]) == {
        "served_off_best_share", "served_logit_gap", "served_state_gap",
        "served_state_bfloat16_share",
    }
    # every request fits its slot and generates its whole budget
    from lib import traffic

    requests = traffic.make_requests(mix, SEED, 32768, 4096)
    assert len(requests) == 192 * mix["arrivals"]["pool_per_client"]
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 4096 for r in requests)
    assert all(max(r["prompt"]) < 32768 for r in requests[:50])
    assert min(r["max_new_tokens"] for r in requests) == 256


# -- lib/latent_moe_cost.py and the readers ------------------------------------

EXPERTS = {"latent": 1024, "width": 2688, "bytes_per_value": 2}


def test_routed_experts_cost_counts_two_matrices_at_the_latent_width():
    one = latent_moe_cost.routed_experts_cost(1, 1, EXPERTS)
    assert one["flops"] == 4 * 1024 * 2688
    assert one["bytes"] == 2 * (2 * 1024 * 2688 + 2 * 1024)
    # a decode step of 128 slots: 704 held rows, all 128 experts touched,
    # 5 layers: the 7.05 GB the issue counts
    step = latent_moe_cost.routed_experts_cost(5 * 704, 5 * 128, EXPERTS)
    assert 7.0e9 < step["bytes"] < 7.1e9
    # the accepted count (three matrices of d_model x width) read on these
    # experts would overstate the bytes by half: a sound kernel over 100%
    three = moe_cost.routed_experts_cost(
        5 * 704, 5 * 128, {"d_model": 1024, "width": 2688, "bytes_per_value": 2}
    )
    assert 1.49 < three["bytes"] / step["bytes"] < 1.51
    twice = latent_moe_cost.routed_experts_cost(2 * 5 * 704, 2 * 5 * 128, EXPERTS)
    assert twice == {k: 2 * v for k, v in step.items()}


def reader(name):
    return bench_run.load_module(
        os.path.join(REPO, "benchmarks", "metrics", name + ".py"),
        "reader_" + name.replace(".", "_"),
    )


def test_the_readers_on_hand_made_facts():
    scopes = {
        "ragged-dot": {"seconds": 0.0900, "events": 100},
        r"moe\.|ragged-dot": {"seconds": 0.11, "events": 900},
        r"moe\.latent_": {"seconds": 0.004, "events": 100},
        r"ssm\.": {"seconds": 0.08, "events": 900},
        r"ssm\.step": {"seconds": 0.07, "events": 400},
        r"^sort": {"seconds": 0.0, "events": 0},
        "busy_s": 0.2,
    }
    run = types.SimpleNamespace(
        facts={"scopes": scopes, "experts": EXPERTS,
               "traced_experts": {"calls": 50.0, "held_rows": 50 * 704.0,
                                  "touched": 50 * 128.0},
               "ssm": {"layers": 5, "heads": 128, "head_dim": 64, "d_state": 128,
                       "groups": 8, "state_bytes": 4, "bytes_per_value": 2},
               "span_ssm": {"slot_steps": 10 * 128.0, "decode_steps": 10.0,
                            "live_slots": 128.0}},
        device={"kind": "TPU v5 lite"}, log=lambda msg: None,
    )
    least = 50 * 2 * (128 * 2 * 1024 * 2688 + 704 * 2 * 1024) / 819e9
    roofline = reader("moe.expert_matmul_roofline.reasoning")
    assert roofline.read(run) == pytest.approx(100 * least / 0.09)
    assert 90 < roofline.read(run) < 100
    assert reader("moe.time_share.reasoning").read(run) == pytest.approx(55.0)
    assert reader("moe.latent_proj_time_share.reasoning").read(run) == pytest.approx(2.0)
    assert reader("ssm.time_share.reasoning").read(run) == pytest.approx(40.0)
    state = reader("ssm.state_update_roofline.reasoning")
    # 10 steps x 128 slots x 5 layers x 2 x 4.19 MB of state (and the rows)
    bytes_ = 10 * 128 * 5 * (2 * 128 * 64 * 128 * 4
                             + (2 * 8192 + 2 * 8 * 128 + 128) * 2)
    assert state.read(run) == pytest.approx(100 * bytes_ / 819e9 / 0.07)
    # a program without the latent scopes (the parent), or nothing traced
    del scopes[r"moe\.latent_"]
    assert reader("moe.latent_proj_time_share.reasoning").read(run) is None
    run.facts["experts"] = {"d_model": 4096, "width": 2688, "bytes_per_value": 2}
    assert roofline.read(run) is None
    run.facts = {}
    for name in ("moe.expert_matmul_roofline", "moe.time_share",
                 "moe.latent_proj_time_share", "ssm.time_share",
                 "ssm.state_update_roofline"):
        assert reader(name + ".reasoning").read(run) is None
    run.counters, run.device_trace = {}, None
    for name in ("moe.experts_touched", "moe.rows_per_expert_max_over_mean",
                 "engine.occupancy", "engine.busy_tick_ms",
                 "engine.device_wait_ms", "engine.launch_ahead_share",
                 "device.idle_share"):
        assert reader(name + ".reasoning").read(run) is None
    run.counters = {"slot_occupancy_mean": 0.97, "launch_ahead_share": 1.0,
                    "busy_tick_ms_mean": 180.0, "moe_experts_touched_mean": 127.6}
    assert reader("engine.occupancy.reasoning").read(run) == pytest.approx(97.0)
    assert reader("engine.launch_ahead_share.reasoning").read(run) == 100.0
    assert reader("engine.busy_tick_ms.reasoning").read(run) == 180.0
    assert reader("moe.experts_touched.reasoning").read(run) == 127.6


@pytest.mark.parametrize("name, have, want", [
    ("engine.prefill_tick_extra_ms",
     {"counters": {"prefill_tick_ms_mean": 290.0, "decode_only_tick_ms_mean": 180.0}},
     110.0),
    ("engine.prefill_pad_share",
     {"counters": {"prefill_tokens_real": 3000, "prefill_tokens_padded": 1000}},
     25.0),
    ("daemon.submit_ms_p50", {"samples": {"submit_s": [0.1, 0.2, 0.6]}}, 200.0),
    ("clients.tpot_ms_p50", {"samples": {"tpot_s": [0.027, 0.028, 0.031]}}, 28.0),
])
def test_the_readers_of_prefill_and_of_the_pumps_lock(name, have, want):
    """What the cell's findings named as open (prefill a quarter of the
    device, submits behind the pump's lock) leaves a number a traced run,
    and nothing where the run has nothing to read."""
    run = types.SimpleNamespace(counters={}, samples={}, facts={},
                                device_trace=None, log=lambda msg: None)
    assert reader(name + ".reasoning").read(run) is None
    for key, value in have.items():
        setattr(run, key, value)
    assert reader(name + ".reasoning").read(run) == pytest.approx(want)
    assert reader(name + ".reasoning").META["name"] == name + ".reasoning"


# -- the comparison's device memory ----------------------------------------------


def test_one_layers_weights_are_alive_at_a_time(monkeypatch):
    """Every draw of a layer's weights records what is alive on the device
    (``jax.live_arrays()``): when layer 1, 2, ... is drawn, nothing of an
    earlier layer is, neither its float32 weights (the loop drops its layer
    before it asks for the next) nor the tree it was made as."""
    import jax
    import jax.numpy as jnp

    from lib import nemotron_weights
    from reference import nemotron_h_ref as ref

    run = types.SimpleNamespace(
        config=bench_tiny_latent_moe.CONFIG, cell=bench_tiny_latent_moe.SERVE_CELL,
    )
    from tpu_parallel.models import GPTLM

    cfg = serve_latent_moe.model_config(run.config, run.cell["engine"])
    model = GPTLM(cfg)
    abstract = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
        train=False,
    ))["params"]
    made = (SEED, abstract, cfg.n_heads, cfg.n_kv_heads, jnp.bfloat16)
    weights = nemotron_weights.to_reference(*made)
    alive, real = [], nemotron_weights.make_params

    def make_params(*args, **kw):
        alive.append(sum(a.nbytes for a in jax.live_arrays()))
        return real(*args, **kw)

    monkeypatch.setattr(nemotron_weights, "make_params", make_params)
    sequences = [jnp.arange(1, 25, dtype=jnp.int32), jnp.arange(3, 43, dtype=jnp.int32)]
    seen = []
    logits, states = ref.forward_each(
        weights, sequences, serve_latent_moe.reference_shape(run.config),
        keep=[20, 30], watch=seen.append,
    )
    assert len(alive) == 11 and len(logits) == 2 and len(states[0]) == 5
    assert seen == [f"layer {i} {k}" for i, k in enumerate("MEMEMEM*EME")]
    one_layer = nemotron_weights.layer_bytes(abstract)
    # what is rightly alive beside the first draw's: the streams' rows and
    # the states kept so far (at toy sizes a state is a tenth of a layer)
    rows = sum(len(t) for t in sequences) * cfg.d_model * 4
    kept = [2 * 8 * 16 * 16 * 4 * "MEMEMEM*EME"[:i].count("M") for i in range(11)]
    over = [(held - alive[0] - rows - kept[i]) / one_layer
            for i, held in enumerate(alive) if i]
    assert all(abs(x) < 0.1 for x in over), over  # no layer stays
