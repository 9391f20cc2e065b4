"""``drivers/serve_latent_attn.py`` end to end on the CPU, on a toy cell added
as files of its own (``bench_tiny_latent_attn.py``): HTTP/SSE through the
daemon, the served streams held to the reference; the timed path broken (a
post-sublayer norm dropped; the cache row rounded to float8) and the float8
control come out over a limit; the configuration's file against the catalog
and its parameter counts from its keys; the cell's entries and traffic as the
issue names them; ``lib/mla_cost.py`` against hand-counted cases and the
readers on hand-made facts."""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny_latent_attn  # noqa: E402
import run as bench_run  # noqa: E402
from drivers import serve_latent_attn  # noqa: E402
from lib import mla_cost  # noqa: E402

SEED = 2 ** 31 + 4747
CELL = "serve-openpangu_ultra_moe_718b_share16-longdoc"
CONFIG = "openpangu_ultra_moe_718b_share16"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_latent_attn.make_root(
        str(tmp_path_factory.mktemp("bench_latent_attn"))
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
        bench_tiny_latent_attn.CELL, SEED, seconds, trace, control,
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


def test_toy_cell_its_control_and_its_counters(root, capsys):
    out = drive(root, control=True)
    text = capsys.readouterr().out
    assert out["correct"] is True, text[-3000:]
    assert set(out["metrics"]) == {"serve_out_tok_s", "setup_s"}
    assert out["attempted"] >= 6 and out["failed"] == 0
    limits = bench_tiny_latent_attn.SERVE_CELL["limits"]
    control = next(l for l in text.splitlines() if l.startswith("control float8:"))
    numbers = dict(
        kv.split("=") for kv in control.split(": ", 1)[1].split(" (")[0].split()
    )
    assert set(numbers) == set(limits)
    assert any(float(v) > limits[k] for k, v in numbers.items()), control
    # the witness is the reference in the precision the program computes in:
    # the toy's is float32, the reference itself, and moves no first choice
    witness = next(l for l in text.splitlines()
                   if l.startswith("control witness_float32:"))
    assert "served_off_best_share=0 " in witness
    got = checks(text)
    assert got["served_logit_gap"] < limits["served_logit_gap"]
    assert got["longest_stream_passes"] == 33
    assert "latent_plan: {'layers': 4, 'of_layers': 4, 'heads': 4" in text
    assert "'row': 40" in text and "'decode': 'absorbed'" in text
    assert "attn_plan: {'decode': {'path': 'xla'}}" in text
    reference = next(l for l in text.splitlines()
                     if l.startswith("reference: ") and " streams, " in l)
    assert int(reference.split()[1]) >= 4
    counters = next(l for l in text.splitlines() if l.startswith("engine counters:"))
    assert int(counters.split("latent_positions_read ")[1].split(",")[0]) > 0
    assert "latent_bytes_per_position 640" in counters
    assert int(counters.split("moe_calls ")[1].split(",")[0]) > 0
    shapes = next(l for l in text.splitlines() if l.startswith("warm-up:"))
    assert "('prefill', 1, 16), ('prefill', 1, 32), ('prefill', 1, 64)" in shapes
    assert got["compiles_in_window"] == 0
    assert "engine's weights and pool deleted: " in text
    assert "comparison memory: sampled peak not read, bound " in text


def test_traced_run_reports_the_counter_metrics(root, capsys):
    out = drive(root, trace=1)
    assert out["correct"] is True
    # the probe keeps what every watched program held, through the window's
    # fresh metrics record: its sums are the record's own counters (a launch
    # between the counters' reading and the probe's may differ them by one)
    text = capsys.readouterr().out
    probe = next(l for l in text.splitlines() if l.startswith("span probe, in"))
    calls, real, ticks, rows = (
        int(w) for w in probe.replace(",", "").split() if w.isdigit()
    )
    counters = next(l for l in text.splitlines() if l.startswith("engine counters:"))
    counted = lambda key: int(counters.split(f" {key} ")[1].split(",")[0])
    assert 0 <= calls - counted("prefill_calls") <= 1
    assert 0 <= real - counted("prefill_tokens_real") <= 64
    assert 0 <= ticks - counted("decode_ticks") <= 1
    assert 0 <= rows - counted("latent_positions_read") <= 8 * 8 * 4 * 64
    assert counted("latent_positions_read") > 0
    # every program's completion is in the trace's host plane while it runs
    said = text.split("the trace holds ")[1].split(" programs")[0].split()
    assert 0 < int(said[0]) < int(said[-1])
    metrics = out["metrics"]
    assert 0 < metrics["engine.occupancy.longdoc"]["value"] <= 100
    assert metrics["engine.busy_tick_ms.longdoc"]["value"] > 0
    assert metrics["engine.device_wait_ms.longdoc"]["value"] >= 0
    assert "engine.launch_ahead_share.longdoc" in metrics
    assert 0 < metrics["moe.experts_touched.longdoc"]["value"] <= 4
    assert metrics["moe.rows_per_expert_max_over_mean.longdoc"]["value"] >= 1
    assert 0 < metrics["engine.prefill_pad_share.longdoc"]["value"] < 100
    assert "engine.device_tick_ms" in metrics
    # no device plane on the CPU: the trace readers find nothing, and say so
    for name in ("mla.time_share", "mla.proj_time_share",
                 "mla.prefill_attention_roofline",
                 "mla.decode_attention_roofline", "moe.time_share",
                 "moe.expert_matmul_roofline", "device.idle_share"):
        assert f"{name}.longdoc" not in metrics


def test_a_dropped_post_attention_norm_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    """The program without its sandwich norms' output halves (a plain
    pre-norm block over the same weights) serves another model's tokens."""
    from tpu_parallel.models import layers

    def plain(config, x, mixer, mixer_kwargs, mlp_fn):
        norm = lambda name, y: layers.make_norm(config, name)(y).astype(config.dtype)
        x = x + mixer(norm("norm_attn", x), **mixer_kwargs)
        layers.make_norm(config, "norm_post_attn")(x)  # the parameter stays
        x = x + mlp_fn(norm("norm_mlp", x))
        layers.make_norm(config, "norm_post_mlp")(x)
        return x

    monkeypatch.setattr(layers, "sandwich_block", plain)
    out = drive(root)
    text = capsys.readouterr().out
    limits = bench_tiny_latent_attn.SERVE_CELL["limits"]
    got = checks(text)
    assert out["correct"] is False and out["failed"] == 0
    assert (got["served_logit_gap"] > limits["served_logit_gap"]
            or got["served_off_best_share"] > limits["served_off_best_share"])


def test_a_cache_row_kept_in_float8_is_not_correct(
    root, monkeypatch, capsys, fresh_programs
):
    """The program that stores its latent rows in a lower precision than the
    configuration states (float8 behind the bfloat16 leaf): what decode reads
    back is not what prefill computed from."""
    import jax.numpy as jnp

    from tpu_parallel.models import latent_attention

    real = latent_attention.LatentAttention._store

    def rounded(self, cached, cached_p, cache_index, row, *rest):
        row = row.astype(jnp.float8_e5m2).astype(row.dtype)  # two mantissa bits
        return real(self, cached, cached_p, cache_index, row, *rest)

    monkeypatch.setattr(latent_attention.LatentAttention, "_store", rounded)
    out = drive(root)
    text = capsys.readouterr().out
    limits = bench_tiny_latent_attn.SERVE_CELL["limits"]
    got = checks(text)
    assert out["correct"] is False and out["failed"] == 0
    assert (got["served_logit_gap"] > limits["served_logit_gap"]
            or got["served_off_best_share"] > limits["served_off_best_share"])


# -- the configuration, the cell and the traffic --------------------------------


def read(*rel):
    return json.load(open(os.path.join(REPO, "benchmarks", *rel)))


def test_configuration_file_against_the_catalog():
    """Every key of the published ``config.json`` is in the file's top level,
    changed only where ``reduced`` says (no width among them); the
    parameters, counted from the keys, are the 4,919,139,840 of one chip's
    share of the first stage, and 719.09 B over the uncut keys."""
    data = read("configs", f"{CONFIG}.json")
    published = data["published"]
    assert data["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers",
    ]
    assert set(data["reduced_why"]) == set(data["reduced"])
    assert {k for k, v in published.items() if data[k] != v} == set(data["reduced"])
    assert (data["num_hidden_layers"], data["first_k_dense_replace"],
            data["n_routed_experts"], data["vocab_size"],
            data["num_nextn_predict_layers"]) == (5, 1, 16, 19200, 0)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if json.loads(l)["name"] == "openPangu-Ultra-MoE-718B")
        assert published == row["config"]
        assert data["source"] == row["source_url"]
    # every width, all heads, both ranks, the router's outputs, top-8 and the
    # scale as published
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["num_attention_heads"],
            data["q_lora_rank"], data["kv_lora_rank"], data["qk_nope_head_dim"],
            data["qk_rope_head_dim"], data["v_head_dim"],
            published["n_routed_experts"], data["num_experts_per_tok"],
            data["routed_scaling_factor"]) == (
        7680, 18432, 2048, 128, 1536, 512, 128, 64, 128, 256, 8, 2.5)
    assert serve_latent_attn.parameters(data) == 4_919_139_840
    assert data["model"]["parameters"] == 4_919_139_840
    whole = serve_latent_attn.parameters(published)
    assert whole == data["model"]["parameters_uncut"]
    assert 719.08e9 < whole < 719.10e9  # published as 718 B
    # the arithmetic of the cut, piece by piece
    d = data["hidden_size"]
    attention = (d * 1536 + 1536 + 1536 * 128 * 192 + d * 576 + 512
                 + 512 * 128 * 256 + 128 * 128 * d)
    assert attention == 196_577_280
    assert attention + 4 * d + 3 * d * 18432 == 621_281_280
    assert attention + 4 * d + d * 256 + 3 * d * 2048 == 245_760_000
    assert 3 * d * 2048 == 47_185_920
    assert data["model"]["cache_bytes_per_position"] == 5 * 576 * 2 == 5760
    for key in ("router", "sandwich_norm_placement", "rope_pairing",
                "rotary_key", "inner_norm_eps", "score_scale", "shared_expert",
                "dense_mlp", "cache_row_dtype", "initializer"):
        assert len(data["assumed"][key]) >= 8, key
    assert "sixteen v5e chips" in data["deployment"]
    cell = read("workloads", f"{CELL}.json")
    cfg = serve_latent_attn.model_config(data, cell["engine"])
    assert (cfg.d_model, cfg.n_layers, cfg.routed_layers, cfg.vocab_size,
            cfg.seq_len, cfg.mlp_dim, cfg.sandwich_norm, cfg.rope_theta) == (
        7680, 5, 4, 19200, 8192, 18432, True, 25.6e6)
    assert len(cfg.layer_head) == 1 and cfg.layer_head[0].mlp == "dense"
    spec = cfg.layer_pattern[0]
    assert (spec.attn, spec.latent.q_rank, spec.latent.kv_rank, spec.latent.row) == (
        "latent", 1536, 512, 576)
    es = spec.experts
    assert (es.n_experts, es.top_k, es.held, es.width, es.score, es.shared,
            es.route_scale, es.select_bias) == (
        256, 8, (0, 16), 2048, "sigmoid", 1, 2.5, False)


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "longdoc", 1
    )
    assert len(entry["why"]) <= 200
    metric = next(m for m in manifest["end_to_end"]
                  if m["name"] == "serve_out_tok_s")
    # (`in`, not `[-1] ==`: the next cell is appended after this one)
    assert CELL in metric["workloads"] and metric["bound"] == 0.08
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "mla.time_share.longdoc", "mla.proj_time_share.longdoc",
        "mla.prefill_attention_roofline.longdoc",
        "mla.decode_attention_roofline.longdoc", "moe.time_share.longdoc",
        "moe.expert_matmul_roofline.longdoc", "moe.experts_touched.longdoc",
        "moe.rows_per_expert_max_over_mean.longdoc",
        "engine.prefill_pad_share.longdoc", "engine.occupancy.longdoc",
        "engine.busy_tick_ms.longdoc", "engine.device_wait_ms.longdoc",
        "engine.launch_ahead_share.longdoc", "device.idle_share.longdoc",
    ]
    first = manifest["per_layer"].index(mine[0])  # appended together
    assert manifest["per_layer"][first:first + len(mine)] == mine
    for m in mine:  # each reader is there under its metric's name
        module = reader(m["name"])
        assert module.META["name"] == m["name"] and module.META["unit"] == m["unit"]
    for name in ("engine.device_tick_ms", "engine.device_idle_share",
                 "engine.device_prefill_share",
                 "engine.device_prefill_ms_per_ktok"):
        clock = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL in clock["workloads"]
    mix = read("traffic", "longdoc.json")
    assert (mix["arrivals"]["ramp_s"], mix["arrivals"]["ramp_max_s"]) == (20.0, 120.0)
    assert mix["prompt_tokens"] == {
        "kind": "lognormal", "median": 4096, "sigma": 0.6, "min": 1024, "max": 7680,
    }
    assert mix["output_tokens"] == {"kind": "uniform", "min": 128, "max": 512}
    cell = read("workloads", f"{CELL}.json")
    engine = cell["engine"]
    assert (engine["slot_positions"], engine["max_prefills_per_tick"],
            engine["prefill_batch"]) == (8192, 2, 1)
    # the issue's 48 slots and 72 clients, or its fallback of 32 and 48 where
    # the 8192-token prefill's temporaries do not fit beside the larger pool
    assert (engine["n_slots"], mix["arrivals"]["clients"]) in ((48, 72), (32, 48))
    assert engine["prefill_buckets"] == [1024, 2048, 3072, 4096, 6144]
    assert cell["reference_streams"] == 6 and cell["longest_stream_passes"] == 4096
    assert set(cell["limits"]) == {"served_off_best_share", "served_logit_gap"}
    # every request fits its slot and generates its whole budget
    from lib import traffic

    requests = traffic.make_requests(mix, SEED, 19200, 8192)
    assert len(requests) == mix["arrivals"]["clients"] * mix["arrivals"]["pool_per_client"]
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 8192 for r in requests)
    assert all(max(r["prompt"]) < 19200 for r in requests[:20])
    assert min(len(r["prompt"]) for r in requests) >= 1024
    assert max(len(r["prompt"]) for r in requests) == 7680
    assert min(r["max_new_tokens"] for r in requests) >= 128


def test_an_older_cells_test_reads_the_manifest_as_of_its_cell():
    """``tests/conftest.py::manifest_as_of``: what PR 45's test of cell 6,
    which pins the ends of the manifest's lists, is shown."""
    from conftest import manifest_as_of

    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert manifest_as_of(manifest, CELL) == manifest  # nothing came later
    older = "serve-nemotron_3_super_120b_share4-reasoning"
    seen = manifest_as_of(manifest, older)
    assert [w["name"] for w in seen["workloads"]] == [
        w["name"] for w in manifest["workloads"]][:-1]
    assert CONFIG not in [c["name"] for c in seen["configs"]]
    assert not [m for m in seen["per_layer"] if "longdoc" in m["name"]]
    assert all(CELL not in m.get("workloads", ()) for m in
               seen["per_layer"] + seen["end_to_end"])
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert len(seen["per_layer"]) == len(manifest["per_layer"]) - len(mine)
    # what stays is what was there, in its order, entry for entry
    strip = lambda m: {k: ([c for c in v if c != CELL] if k == "workloads" else v)
                       for k, v in m.items()}
    assert seen["per_layer"] == [
        strip(m) for m in manifest["per_layer"] if m not in mine]


# -- lib/mla_cost.py and the readers -------------------------------------------

MLA = {"layers": 5, "heads": 128, "qk": 192, "v": 128, "kv_rank": 512,
       "row": 576, "bytes_per_value": 2}


def test_mla_cost_against_hand_counted_cases():
    # a stored row: 128 heads score at 576 and sum at 512; 1,152 bytes
    one = mla_cost.stored_rows_cost(1, MLA)
    assert one == {"flops": 278_528, "bytes": 1_152}
    assert 241 < one["flops"] / one["bytes"] < 242  # a v5e's ridge is 240.5
    many = mla_cost.stored_rows_cost(1000, MLA)
    assert many == {k: 1000 * v for k, v in one.items()}
    # a prompt of 4096 through one layer: the causal half of 2 x 128 x 320 x S^2
    flash = mla_cost.prefill_attention_cost(4096 ** 2, MLA)
    assert flash["flops"] == 128 * 320 * 4096 ** 2 == 40960 * 4096 ** 2
    assert flash["bytes"] == 2 * 128 * 2 * 320 * 4096
    # the span's work is the sum over the programs that ran in it, each with
    # what IT computed.  Engine clock = trace clock + 100 (the marks say so).
    # The span is [1.0, 3.0] on the trace's clock: a prefill of 1500 half
    # inside at its start, a tick whole, a prefill of 3000 whole, a tick a
    # quarter inside at its end, a prefill wholly outside
    programs = [
        {"kind": "prefill", "start": 100.8, "done": 101.2, "real": 1500},
        {"kind": "tick", "start": 101.2, "done": 101.4, "rows": 16_000, "tokens": 240},
        {"kind": "prefill", "start": 101.4, "done": 102.7, "real": 3000},
        {"kind": "tick", "start": 102.7, "done": 103.9, "rows": 20_000, "tokens": 200},
        {"kind": "prefill", "start": 103.9, "done": 104.5, "real": 7000},
    ]
    marks = {1: 1.4, 2: 2.7, 3: 3.9 + 0.004}  # one stamp came late: the middle one counts
    span = mla_cost.span_work(programs, marks, 1.0, 3.0, 8, 2)
    assert span["prefill_calls"] == pytest.approx(1.5)
    assert span["prefill_tokens"] == pytest.approx(0.5 * 1500 + 3000)
    assert span["flash_sum_sq"] == pytest.approx(2 * (0.5 * 1500 ** 2 + 3000 ** 2))
    assert span["decode_steps"] == pytest.approx(8 * 1.25)
    assert span["stored_rows"] == pytest.approx(16_000 + 0.25 * 20_000)
    assert span["decode_tokens"] == pytest.approx(240 + 0.25 * 200)
    assert span["programs_s"] == pytest.approx(2.0) and span["span_s"] == pytest.approx(2.0)
    assert mla_cost.span_work(programs, {}, 1.0, 3.0, 8, 2) is None  # no mark
    assert mla_cost.span_work(programs, {9: 1.0}, 1.0, 3.0, 8, 2) is None
    # the experts' passes: 2 expert layers, 16 held; half an assignment a token
    # and layer is held; a decode pass touches 9, a prefill pass all 16
    counters = {"decode_ticks": 10, "tokens_out": 10 * 8 * 30 + 12, "prefills": 12,
                "prefill_calls": 12, "prefill_tokens_real": 12 * 2000,
                "moe_calls": (10 * 8 + 12) * 2,
                "moe_assignments_held": (10 * 8 * 30 + 12 * 2000) * 2 * 0.5,
                "moe_experts_touched_mean": (10 * 8 * 2 * 9.0 + 12 * 2 * 16) / 184}
    passes = mla_cost.span_expert_passes(span, counters, 8, 2, 16)
    assert passes["calls"] == pytest.approx((10 + 1.5) * 2)
    assert passes["held_rows"] == pytest.approx(0.5 * (290 + 3750.0) * 2)
    assert passes["touched"] == pytest.approx(10 * 2 * 9.0 + 1.5 * 2 * 16)
    # not clamped: a window's mean under what its prefill passes alone
    # touch gives a negative count, which shows
    off = dict(counters, moe_experts_touched_mean=1.0)
    assert mla_cost.span_expert_passes(span, off, 8, 2, 16)["touched"] == (
        pytest.approx(10 * 2 * (184 - 24 * 16) / 160 + 1.5 * 2 * 16)
    )
    nothing = dict.fromkeys(span, 0.0)
    assert mla_cost.span_expert_passes(nothing, {}, 8, 4, 16) == {
        "calls": 0.0, "held_rows": 0.0, "touched": 0.0,
    }


def test_the_pool_dealt_wave_by_wave():
    """Every wave (what all clients send k-th) holds one prompt and one
    budget of every stratum of neighbouring sizes; the set of sizes is the
    generator's; the seed decides the rest."""
    import random

    from lib import traffic

    mix = read("traffic", "longdoc.json")
    arrivals = mix["arrivals"]
    clients, waves = arrivals["clients"], arrivals["pool_per_client"]
    plain = traffic.make_requests(mix, SEED, 19200, 8192)
    dealt = serve_latent_attn.WaveTraffic.make_requests(mix, SEED, 19200, 8192)
    sizes = lambda rs: sorted(len(r["prompt"]) for r in rs)
    budgets = lambda rs: sorted(r["max_new_tokens"] for r in rs)
    assert sizes(dealt) == sizes(plain) and budgets(dealt) == budgets(plain)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 8192 for r in dealt)
    ordered, totals = sizes(plain), []
    for k in range(waves):
        wave = dealt[k * clients:(k + 1) * clients]
        for j, n in enumerate(sizes(wave)):  # one of every stratum
            assert ordered[j * waves] <= n <= ordered[(j + 1) * waves - 1]
        totals.append(sum(len(r["prompt"]) for r in wave))
    assert max(totals) - min(totals) < 0.01 * min(totals)
    plain_totals = [
        sum(len(r["prompt"]) for r in plain[k * clients:(k + 1) * clients])
        for k in range(waves)
    ]
    assert max(plain_totals) - min(plain_totals) > 0.05 * min(plain_totals)
    again = serve_latent_attn.WaveTraffic.make_requests(mix, SEED, 19200, 8192)
    other = serve_latent_attn.WaveTraffic.make_requests(mix, SEED + 1, 19200, 8192)
    assert again == dealt and other != dealt
    with pytest.raises(ValueError, match="whole number of waves"):
        serve_latent_attn.deal_waves(list(range(10)), 4, random.Random(0))


def reader(name):
    return bench_run.load_module(
        os.path.join(REPO, "benchmarks", "metrics", name + ".py"),
        "reader_" + name.replace(".", "_"),
    )


def test_the_readers_on_hand_made_facts():
    scopes = {
        r"attn\.latent": {"seconds": 0.12, "events": 900},
        r"mla\.(q_proj|kv_down|kv_up|absorb|out_proj)": {"seconds": 0.05, "events": 500},
        r"mla\.scores/stored": {"seconds": 0.030, "events": 400},
        r"mla\.scores/flash": {"seconds": 0.020, "events": 10},
        "ragged-dot": {"seconds": 0.0300, "events": 100},
        r"moe\.|ragged-dot": {"seconds": 0.07, "events": 900},
        r"moe\.experts/cond(:|$)": {"seconds": 0.01, "events": 8},
        "busy_s": 0.2,
    }
    experts = {"d_model": 7680, "width": 2048, "bytes_per_value": 2}
    run = types.SimpleNamespace(
        facts={"scopes": scopes, "mla": MLA, "experts": experts,
               "span_mla": {"decode_steps": 16.0,
                            "stored_rows": 16 * 700_000.0,
                            "prefill_calls": 2.0, "prefill_tokens": 7200.0,
                            "flash_sum_sq": 10 * 3600.0 ** 2},
               "traced_experts": {"calls": 64.0, "held_rows": 64 * 16.0,
                                  "touched": 64 * 10.0}},
        device={"kind": "TPU v5 lite"}, log=lambda msg: None,
    )
    assert reader("mla.time_share.longdoc").read(run) == pytest.approx(60.0)
    assert reader("mla.proj_time_share.longdoc").read(run) == pytest.approx(25.0)
    assert reader("moe.time_share.longdoc").read(run) == pytest.approx(30.0)
    decode = reader("mla.decode_attention_roofline.longdoc").read(run)
    least = 16 * 700_000 * max(278_528 / 197e12, 1_152 / 819e9)
    assert decode == pytest.approx(100 * least / 0.030) and 50 < decode < 56
    prefill = reader("mla.prefill_attention_roofline.longdoc").read(run)
    assert prefill == pytest.approx(100 * 40960 * 10 * 3600.0 ** 2 / 197e12 / 0.020)
    assert 100 < prefill < 140  # hand-made: a sound kernel cannot read this
    matmul = reader("moe.expert_matmul_roofline.longdoc").read(run)
    bytes_ = 2 * (64 * 10 * 3 * 7680 * 2048 + 64 * 16 * 2 * 7680)
    assert matmul == pytest.approx(100 * bytes_ / 819e9 / 0.03)
    # a program without the latent scopes (the parent), or nothing traced
    traced = ("mla.time_share", "mla.proj_time_share",
              "mla.prefill_attention_roofline", "mla.decode_attention_roofline",
              "moe.time_share", "moe.expert_matmul_roofline")
    for facts in ({"scopes": {"busy_s": 0.2}}, {"scopes": None}, {}):
        run.facts = dict(facts, mla=MLA, experts=experts)
        for name in traced:
            assert reader(name + ".longdoc").read(run) is None, (name, facts)
    run.facts = {}
    run.counters, run.device_trace = {}, None
    for name in ("moe.experts_touched", "moe.rows_per_expert_max_over_mean",
                 "engine.prefill_pad_share", "engine.occupancy",
                 "engine.busy_tick_ms", "engine.device_wait_ms",
                 "engine.launch_ahead_share", "device.idle_share"):
        assert reader(name + ".longdoc").read(run) is None
    run.counters = {"slot_occupancy_mean": 0.97, "launch_ahead_share": 1.0,
                    "busy_tick_ms_mean": 180.0, "moe_experts_touched_mean": 12.6,
                    "prefill_tokens_real": 3000, "prefill_tokens_padded": 1000,
                    "tick_device_wait_ms_mean": 90.0,
                    "moe_rows_per_expert_max_over_mean": 2.5}
    assert reader("engine.occupancy.longdoc").read(run) == pytest.approx(97.0)
    assert reader("engine.launch_ahead_share.longdoc").read(run) == 100.0
    assert reader("engine.busy_tick_ms.longdoc").read(run) == 180.0
    assert reader("engine.device_wait_ms.longdoc").read(run) == 90.0
    assert reader("moe.experts_touched.longdoc").read(run) == 12.6
    assert reader("moe.rows_per_expert_max_over_mean.longdoc").read(run) == 2.5
    assert reader("engine.prefill_pad_share.longdoc").read(run) == 25.0
