"""``drivers/serve_blockgen.py`` end to end on the CPU, on a toy cell added
as files of its own (``bench_tiny_blockgen.py``): HTTP/SSE through the
daemon with a per-request knob, the served streams replayed in the reference
step by step; the timed path broken (the least confident positions filled)
and the control come out over a limit; the configuration's file against the
catalog and its parameter count from its keys; the knob's way from the
traffic file into a submit body; the readers on hand-made facts."""

import http.server
import json
import os
import sys
import threading
import time
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny_blockgen  # noqa: E402
import run as bench_run  # noqa: E402
from drivers import serve_blockgen  # noqa: E402
from lib import loadgen_knobs  # noqa: E402

SEED = 2 ** 31 + 7171
CELL = "serve-sdar_30b_a3b_depth6-blockgen"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_blockgen.make_root(
        str(tmp_path_factory.mktemp("bench_blockgen"))
    )


def drive(root, control=False, trace=0, seconds=2.0):
    return bench_run.run_cell(
        bench_tiny_blockgen.CELL, SEED, seconds, trace, control,
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
    limits = bench_tiny_blockgen.SERVE_CELL["limits"]
    assert set(limits) <= set(checks(text))
    control = next(l for l in text.splitlines() if l.startswith("control float8:"))
    numbers = dict(
        kv.split("=") for kv in control.split(": ", 1)[1].split(" (")[0].split()
    )
    assert set(numbers) == set(limits)
    assert any(float(v) > limits[k] for k, v in numbers.items()), control
    assert "block_plan: {'block_len': 4, 'mask_token_id': 255" in text
    probe = next(l for l in text.splitlines() if l.startswith("stream probe:"))
    assert probe.endswith("8 held")
    reference = next(l for l in text.splitlines() if l.startswith("reference: "))
    # the three tiers of the toy mix reached the comparison's sample or not,
    # but nothing else did
    steps = reference.split("steps a block [")[1].split("]")[0].split(", ")
    assert set(steps) <= {"4", "2", "1"}
    counters = next(l for l in text.splitlines() if l.startswith("engine counters:"))
    forwards = int(counters.split("block_forwards ")[1].split(",")[0])
    commits = int(counters.split("block_commit_forwards ")[1].split(",")[0])
    assert forwards > commits > 0
    shapes = next(l for l in text.splitlines() if l.startswith("warm-up:"))
    assert "('prefill', 1, 16), ('prefill', 1, 32)" in shapes
    assert checks(text)["compiles_in_window"] == 0


def test_traced_run_reports_the_counter_metrics(root):
    out = drive(root, trace=1)
    assert out["correct"] is True
    metrics = out["metrics"]
    assert 0.5 < metrics["diffusion.tokens_per_forward.blockgen"]["value"] < 4
    assert 0 < metrics["diffusion.commit_forward_share.blockgen"]["value"] < 50
    assert 0 < metrics["engine.occupancy.blockgen"]["value"] <= 100
    assert 0 < metrics["moe.experts_touched.blockgen"]["value"] <= 8
    assert metrics["moe.rows_per_expert_max_over_mean.blockgen"]["value"] >= 1
    assert metrics["engine.busy_tick_ms.blockgen"]["value"] > 0
    assert "engine.device_wait_ms.blockgen" in metrics
    assert "engine.launch_ahead_share.blockgen" in metrics
    # no device plane on the CPU: the trace readers find nothing, and say so
    for name in ("moe.time_share", "moe.expert_matmul_roofline",
                 "attn.block_time_share", "diffusion.unmask_time_share",
                 "device.idle_share"):
        assert f"{name}.blockgen" not in metrics


def test_filling_the_least_confident_positions_is_not_correct(
    root, monkeypatch, capsys
):
    """The program with its choice turned round (the LEAST confident masked
    positions filled first): every stream still ends, the served ids are
    still the model's picks, and ``correct`` is false by the choice's own
    numbers."""
    import jax

    from tpu_parallel.serving import engine

    real = engine.unmask_choice

    def least_confident(conf, masked, nstep, dsteps, threshold):
        return real(-conf, masked, nstep, dsteps, threshold)

    engine._block_engine_fns.cache_clear()
    monkeypatch.setattr(engine, "unmask_choice", least_confident)
    try:
        out = drive(root)
    finally:
        engine._block_engine_fns.cache_clear()
        jax.clear_caches()
    text = capsys.readouterr().out
    limits = bench_tiny_blockgen.SERVE_CELL["limits"]
    got = checks(text)
    assert out["correct"] is False and out["failed"] == 0
    assert got["served_choice_off_share"] > limits["served_choice_off_share"]
    assert got["served_choice_gap"] > 0


def test_configuration_file_against_the_catalog():
    """Every key of the published ``config.json`` is in the file's top level;
    ``num_hidden_layers`` alone differs, and is listed; the parameters,
    counted from the keys, are the 4,361,055,744 of one pipeline stage with
    the embedding and the head."""
    data = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "sdar_30b_a3b_depth6.json"
    )))
    published = data["published"]
    assert data["reduced"] == ["num_hidden_layers"] == list(data["reduced_why"])
    assert {k for k, v in published.items() if data[k] != v} == {
        "num_hidden_layers"
    }
    assert (published["num_hidden_layers"], data["num_hidden_layers"]) == (48, 6)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if json.loads(l)["name"] == "SDAR-30B-A3B-Chat")
        assert published == row["config"]
        assert data["source"] == row["source_url"]
        assert set(row["not_given"]) == {"block length", "noise schedule"}
        assert {"block_len", "noise_schedule"} <= set(data["assumed"])
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"] if c["name"] == data["name"])
    assert entry["reduced"] == data["reduced"]
    assert entry["source"] == data["source"]
    d, heads, kv = data["hidden_size"], data["num_attention_heads"], data["num_key_value_heads"]
    hd, experts, width = data["head_dim"], data["num_experts"], data["moe_intermediate_size"]
    attention = 2 * d * heads * hd + 2 * d * kv * hd  # q, o, k, v
    layer = (attention + 2 * hd + 2 * d + d * experts
             + experts * 3 * d * width)
    assert layer == data["model"]["parameters_per_layer"] == 623_120_640
    total = (data["num_hidden_layers"] * layer
             + 2 * data["vocab_size"] * d + d)
    assert total == data["model"]["parameters"] == 4_361_055_744
    assert data["model"]["kv_bytes_per_position"] == (
        data["num_hidden_layers"] * 2 * kv * hd * 2
    ) == 12_288
    cell = json.load(open(os.path.join(
        REPO, "benchmarks", "workloads", f"{CELL}.json"
    )))
    assert cell["engine"]["slot_positions"] == data["model"]["slot_positions"]
    assert all(b % data["model"]["block_len"] == 0
               for b in cell["engine"]["prefill_buckets"])
    cfg = serve_blockgen.model_config(data, cell["engine"])
    assert (cfg.block_len, cfg.mask_token_id) == (4, 151669)
    assert cfg.layer_specs[0].experts.n_experts == 128


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "sdar_30b_a3b_depth6", "blockgen", 1
    )
    metric = next(m for m in manifest["end_to_end"]
                  if m["name"] == "serve_out_tok_s")
    assert CELL in metric["workloads"] and metric["bound"] == 0.08
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 13
    for m in mine:
        assert m["moves"] == "serve_out_tok_s"
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "metrics", m["name"] + ".py"
        ))
    mix = json.load(open(os.path.join(
        REPO, "benchmarks", "traffic", "blockgen.json"
    )))
    assert mix["arrivals"]["clients"] == 96
    assert mix["prompt_tokens"] == {
        "kind": "lognormal", "median": 768, "sigma": 0.8, "min": 64,
        "max": 3072,
    }
    assert mix["output_tokens"] == {"kind": "uniform", "min": 512, "max": 512}
    assert mix["token_ids"]["below"] == 151643
    assert mix["request_knobs"]["denoising_steps"]["values"] == [4, 2]
    cell = json.load(open(os.path.join(
        REPO, "benchmarks", "workloads", f"{CELL}.json"
    )))
    assert cell["engine"]["n_slots"] == 64
    assert cell["engine"]["prefill_buckets"] == [256, 512, 1024, 2048, 3072]
    assert mix["arrivals"]["clients"] == 1.5 * cell["engine"]["n_slots"]


def test_knobs_are_drawn_in_equal_shares_and_paired_by_the_seed():
    mix = dict(bench_tiny_blockgen.TRAFFIC)
    make = serve_blockgen.KnobTraffic.make_requests
    one, again, other = make(mix, 5, 250, 64), make(mix, 5, 250, 64), make(mix, 6, 250, 64)
    assert one == again
    steps = [r["denoising_steps"] for r in one]
    assert {s: steps.count(s) for s in (4, 2, 1)} == {4: 400, 2: 400, 1: 400}
    assert steps != [r["denoising_steps"] for r in other]
    assert all(0 < t < 250 for r in one for t in r["prompt"])
    # a mix without knobs is lib/traffic.py's own
    plain = {k: v for k, v in mix.items() if k != "request_knobs"}
    assert set(make(plain, 5, 250, 64)[0]) == {"prompt", "max_new_tokens"}


def test_the_load_generator_posts_a_requests_further_keys(tmp_path):
    """``lib/loadgen_knobs.py`` is ``lib/loadgen.py`` plus the request's
    other keys in the submit body, found again by the dedupe token."""
    seen = []

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            seen.append(json.loads(body))
            payload = json.dumps({"finish_reason": "nope"}).encode()
            self.send_response(429)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def log_message(self, *args):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    plan = {
        "port": server.server_address[1], "t0": 0.0, "clients": 1,
        "drain_timeout_s": 5, "tag": "t",
        "requests": [
            {"prompt": [1, 2], "max_new_tokens": 3, "denoising_steps": 2},
            {"prompt": [3], "max_new_tokens": 4},
        ],
    }
    plan_path, result_path = tmp_path / "plan.json", tmp_path / "out.json"
    plan_path.write_text(json.dumps(plan))
    before = loadgen_knobs.http.client.HTTPConnection
    stdin, sys.stdin = sys.stdin, open(os.devnull)
    try:
        assert loadgen_knobs.main(["", str(plan_path), str(result_path)]) == 0
    finally:
        loadgen_knobs.http.client.HTTPConnection = before
        loadgen_knobs.KNOBS.clear()
        sys.stdin = stdin
        server.shutdown()
    assert seen == [
        {"prompt": [1, 2], "max_new_tokens": 3, "dedupe_token": "t-0",
         "denoising_steps": 2},
        {"prompt": [3], "max_new_tokens": 4, "dedupe_token": "t-1"},
    ]
    records = json.loads(result_path.read_text())["records"]
    assert [r["error"] for r in records] == ["submit 429: nope"] * 2


def test_decisions_are_the_forwards_that_chose():
    # two blocks of 4: the first holds a prompt's tail of 2 and fills one a
    # step; the second fills two a step
    steps = [-1, -1, 1, 0, 0, 1, 0, 1]
    got = serve_blockgen.decisions(steps, 4)
    assert [(t, rows.tolist(), chosen.tolist()) for t, rows, chosen in got] == [
        (0, [2, 3], [False, True]),
        (0, [4, 5, 6, 7], [True, False, True, False]),
    ]


def test_the_readers_on_hand_made_facts():
    reader = bench_run.load_module(os.path.join(
        REPO, "benchmarks", "metrics",
        "moe.expert_matmul_roofline.blockgen.py",
    ), "roofline_reader")
    experts = {"d_model": 2048, "width": 768, "bytes_per_value": 2}
    scopes = {"ragged-dot": {"seconds": 0.0177, "events": 24},
              "busy_s": 0.04, r"diffusion\.unmask": {"seconds": 0.002, "events": 9}}
    run = types.SimpleNamespace(
        facts={"scopes": scopes, "experts": experts,
               "traced_experts": {"calls": 12.0, "held_rows": 12 * 2048.0,
                                  "touched": 12 * 128.0}},
        device={"kind": "TPU v5 lite"}, log=lambda msg: None,
    )
    # 12 passes of all 128 experts' three matrices: 14.5 GB at 819 GB/s
    least = 12 * (128 * 3 * 2048 * 768 * 2 + 2048 * 2 * 2048 * 2) / 819e9
    assert reader.read(run) == pytest.approx(100 * least / 0.0177)
    assert 95 < reader.read(run) < 105
    share = bench_run.load_module(os.path.join(
        REPO, "benchmarks", "metrics", "diffusion.unmask_time_share.blockgen.py",
    ), "share_reader")
    assert share.read(run) == pytest.approx(5.0)
    run.facts = {}
    assert reader.read(run) is None and share.read(run) is None


# -- the comparison's device memory (PR 43) ------------------------------------


@pytest.fixture(scope="module")
def toy_reference():
    """The toy cell's model as the comparison sees it: what ``to_reference``
    takes (weights made in bfloat16, as served), the reference's ``shape``
    and two streams of different padded shapes, at 2 and 4 steps a block."""
    import compare_blockgen

    run = types.SimpleNamespace(
        config=bench_tiny_blockgen.CONFIG, cell=bench_tiny_blockgen.SERVE_CELL,
        traffic=bench_tiny_blockgen.TRAFFIC,
    )
    built = serve_blockgen.describe(run)
    made = (SEED, built.abstract, built.cfg.n_heads, built.cfg.n_kv_heads,
            built.served)
    streams = []
    for positions, generated, steps in ((27, 17, 2), (44, 24, 4)):
        s = compare_blockgen.make_streams(
            SEED + positions, 1, positions, generated, steps, 4, 250
        )[0]
        streams.append({"prompt": s.prompt, "tokens": s.tokens,
                        "fill_steps": s.fill_steps})
    return types.SimpleNamespace(
        made=made, streams=streams, mask_id=built.cfg.mask_token_id,
        shape=serve_blockgen.reference_shape(run.config),
    )


def test_replay_gives_the_rows_of_the_plain_walk(toy_reference):
    """``replay`` (layers outermost, one held at a time, every call jitted
    and waited for) against the plain way: every layer's weights held at
    once in a list, one stream at a time through the un-jitted ``layer`` /
    ``layer_over``, stream by stream and step by step."""
    import jax
    import jax.numpy as jnp

    from lib import sdar_weights
    from reference import sdar_moe_ref as ref

    toy, pad, size = toy_reference, 16, 4
    weights = sdar_weights.to_reference(*toy.made)
    replayed = ref.replay(weights, toy.streams, toy.shape, toy.mask_id, pad=pad)
    every_layer = list(sdar_weights.layers(*toy.made))
    assert len(every_layer) == 3
    assert [len(r["hidden"]) for r in replayed] == [2, 4]
    assert len({-(-(len(s["prompt"]) + len(s["tokens"])) // size * size // pad)
                for s in toy.streams}) == 2  # two padded shapes
    with jax.default_matmul_precision("highest"):
        for stream, got in zip(toy.streams, replayed):
            seq = list(stream["prompt"]) + list(stream["tokens"])
            first = len(stream["prompt"]) // size * size
            seq = seq[:len(seq) // size * size]
            steps = np.array(
                ([-1] * (len(stream["prompt"]) - first)
                 + list(stream["fill_steps"]))[:len(seq) - first]
            )
            padded = seq + [0] * (-len(seq) % pad)
            x = weights["embed"][jnp.asarray(padded)]
            noisy = {
                t: weights["embed"][jnp.asarray(
                    np.where(steps < t, np.array(seq[first:]), toy.mask_id)
                )] for t in range(int(steps.max()) + 1)
            }
            positions = jnp.arange(first, len(seq))
            for lw in every_layer:
                x, k, v = ref.layer(x, lw, toy.shape)
                noisy = {t: ref.layer_over(h, positions, k, v, lw, toy.shape)
                         for t, h in noisy.items()}
            assert set(got["hidden"]) == set(noisy)
            for t, rows in noisy.items():
                assert float(jnp.std(rows)) > 0.1  # the rows carry weight
                np.testing.assert_allclose(got["hidden"][t], rows, atol=1e-5)


def test_no_earlier_layer_is_alive_when_a_layer_is_drawn(toy_reference, monkeypatch):
    """Every draw of weights records what is alive on the device
    (``jax.live_arrays()``): when layer 1, 2, ... is drawn, nothing of an
    earlier layer is, neither its float32 weights (the loop drops its layer
    before it asks for the next) nor the tree it was made as (let go leaf by
    leaf as its float32 copy is ready): the draws after the first read what
    the first read, the top-level weights and the streams' rows.  On the
    files as they were before PR 43 this FAILS (read there: 1.5 layers'
    worth over the first draw at every later one, the previous layer's
    float32 weights bound in ``replay``'s loop and its bfloat16 tree, half
    as large, bound in the generator's frame; 0.04 on PR 43's)."""
    import jax

    from lib import sdar_weights
    from reference import sdar_moe_ref as ref

    toy = toy_reference
    alive = []
    real = sdar_weights.make_params

    def make_params(*args, **kw):
        alive.append(sum(a.nbytes for a in jax.live_arrays()))
        return real(*args, **kw)

    weights = sdar_weights.to_reference(*toy.made)
    monkeypatch.setattr(sdar_weights, "make_params", make_params)
    replayed = ref.replay(weights, toy.streams, toy.shape, toy.mask_id, pad=16)
    assert len(alive) == 3 and len(replayed) == 2
    one_layer = sum(
        a.nbytes for a in jax.tree.leaves(
            sdar_weights.layer_weights(*toy.made[:2], 0, *toy.made[2:])
        )
    )
    over = [(held - alive[0]) / one_layer for held in alive[1:]]
    assert all(abs(x) < 0.25 for x in over), over
    # and forward(), which generate() and tests/test_block_diffusion.py use
    del alive[:]
    weights = dict(weights, layers=sdar_weights.layers(*toy.made))
    ref.forward(weights, list(toy.streams[0]["prompt"]), toy.shape)
    over = [(held - alive[0]) / one_layer for held in alive[1:]]
    assert len(alive) == 3 and all(abs(x) < 0.25 for x in over), over


def test_the_replays_bound_at_the_cells_shapes():
    """``replay_bytes_bound`` of the cell's published sizes and the worst
    sample of its mix (eight streams of 3072 + 512 positions, four steps a
    block) leaves a quarter of the chip free, with and without the control's
    second pass; it grows with the longest stream and not with the layers."""
    from reference import sdar_moe_ref as ref

    config = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "sdar_30b_a3b_depth6.json"
    )))
    mix = json.load(open(os.path.join(
        REPO, "benchmarks", "traffic", "blockgen.json"
    )))
    cell = json.load(open(os.path.join(
        REPO, "benchmarks", "workloads", f"{CELL}.json"
    )))
    limit = 16_909_336_064  # the runtime's bytes_limit of one v5e chip
    sizes = dict(config["model"], n_layers=None)
    worst = dict(longest=3072 + 512, generated=512, streams=8, steps=4,
                 pad=serve_blockgen.REFERENCE_PAD)
    one = ref.replay_bytes_bound(sizes, **worst)
    two = ref.replay_bytes_bound(sizes, passes=2, **worst)
    assert 6.0e9 < one < two < 0.75 * limit
    assert two == serve_blockgen.replay_bound(config, mix, cell["reference_streams"])
    deeper = dict(config, num_hidden_layers=48)
    assert serve_blockgen.replay_bound(deeper, mix, 8) == two
    assert ref.replay_bytes_bound(sizes, **dict(worst, longest=2048)) < one
    assert ref.replay_bytes_bound(sizes, **dict(worst, streams=16)) > one
    # the terms a reader can check by hand: top-level weights and one layer
    top = 4 * (2 * 151936 * 2048 + 2048)
    layer = 4 * config["model"]["parameters_per_layer"]
    assert one > top + layer + 2 * config["model"]["parameters_per_layer"]


def test_the_comparison_alone_repeats_its_numbers(root, capsys):
    """``benchmarks/compare_blockgen.py`` on the toy cell: streams from the
    seed in the shape the probe hands over, the cell's own ``compare``, the
    same four numbers in every repeat, the bound beside them."""
    import compare_blockgen

    held = compare_blockgen.make_streams(9, 2, 30, 20, 2, 4, 250)
    assert [len(s.prompt) for s in held] == [10, 10]
    # the first block holds the prompt's tail of 2: its two masked positions
    # are what step 0 fills
    assert held[0].fill_steps[:2] == [0, 0] and len(held[0].tokens) == 18
    assert all(sorted(held[1].fill_steps[i:i + 4]) == [0, 0, 1, 1]
               for i in range(2, 18, 4))
    records = compare_blockgen.alone(
        SEED, streams=3, positions=40, steps=4, repeats=2,
        workload=bench_tiny_blockgen.CELL, root=root,
        bench_dir=os.path.join(root, "benchmarks"),
    )
    assert len(records) == 2
    assert records[0]["numbers"] == records[1]["numbers"]
    assert set(records[0]["numbers"]) == set(
        bench_tiny_blockgen.SERVE_CELL["limits"]
    )
    assert records[0]["bound_bytes"] > 0
    assert "comparison alone: serve-tiny_blockgen" in capsys.readouterr().out


def test_a_run_logs_what_its_comparison_held(root, capsys):
    """``lib/serve_window.py`` says, after the comparison, what it held: the
    family's sampled peak and bound (the CPU's runtime reports no bytes in
    use: "not read") beside the process's own counters; nothing is added to
    what decides ``correct``."""
    out = drive(root, seconds=1.0)
    text = capsys.readouterr().out
    line = next(l for l in text.splitlines()
                if l.startswith("comparison memory: "))
    assert "sampled peak not read, bound 0.0" in line and "limit not read" in line
    assert text.index("reference and comparison:") < text.index(line)
    assert out["correct"] is True
    assert not any("memory" in name for name in checks(text))


def test_the_comparison_waits_until_the_engine_is_gone():
    """What PR 43 found on the chip: a handler thread of the HTTP server,
    still parked on its stream's queue when the window's code lets the
    engine go, holds the daemon and with it the engine (weights and pool,
    11.95 GB), so ONE collection frees nothing and the comparison starts on
    a full chip.  ``wait_freed`` collects until the engine is no more and
    names the threads that were alive after the first collection."""
    import weakref

    from lib import serve_window

    class Engine:
        pass

    engine = Engine()
    engine.release_slot = lambda: engine  # a cycle, as the probe's wrapper makes
    parked = threading.Thread(
        target=lambda held: time.sleep(0.6), args=(engine,), name="parked-handler"
    )
    parked.start()
    gone = weakref.ref(engine)
    del engine
    said = []
    run = types.SimpleNamespace(log=said.append)
    device = types.SimpleNamespace(memory_stats=lambda: None)
    t0 = time.perf_counter()
    serve_window.wait_freed(run, gone, device)
    assert gone() is None and 0.4 < time.perf_counter() - t0 < 5
    assert said[0].startswith("engine freed after ") and len(said) == 1
    assert int(said[0].split("after ")[1].split()[0]) >= 2
    assert "threads alive after the first: ['parked-handler']" in said[0]
    parked.join(timeout=5)
    assert not parked.is_alive()
    # nothing holds it: one collection, no thread named
    engine = Engine()
    gone = weakref.ref(engine)
    del engine
    serve_window.wait_freed(run, gone, device)
    assert "after 1 collection(s)" in said[1] and "threads" not in said[1]
    # never let go: it says so after its patience and goes on
    engine = Engine()
    serve_window.wait_freed(run, weakref.ref(engine), device, patience_s=0.3)
    assert said[2].startswith("engine STILL REFERENCED after ")
