"""Durable-daemon tests: write-ahead journal semantics, in-process
crash + journal-replay recovery (bitwise greedy parity through a
kill), dedupe-token idempotence, the drain/fast-shutdown contract on a
fake clock, the stdlib HTTP+SSE face, and the real-subprocess SIGTERM
smoke that ``scripts/check_all.py`` also runs."""

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_parallel.cluster import Frontend, FrontendConfig
from tpu_parallel.daemon import (
    CORRUPT_CRC,
    CORRUPT_GARBAGE,
    CORRUPT_SEQ,
    EXIT_CLEAN,
    EXIT_FORCED,
    REC_RECOVERY,
    REC_SHUTDOWN,
    REC_SUBMIT,
    REC_TERMINAL,
    REC_TOKENS,
    REJECT_DEGRADED,
    DaemonConfig,
    DaemonHTTPServer,
    IOFaultPlan,
    JournalCorrupt,
    JournalWriter,
    ServingDaemon,
    WallClock,
    encode_record,
    load_state,
    read_journal,
    record_crc_ok,
    replay_state,
)
from tpu_parallel.daemon import iofaults
from tpu_parallel.daemon.journal import ROTATE_SUFFIX, drop_torn_tail
from tpu_parallel.models import GPTLM, tiny_test
from tpu_parallel.models.generate import generate
from tpu_parallel.obs.registry import MetricRegistry
from tpu_parallel.serving import (
    REJECT_DRAINING,
    REJECTED,
    Request,
    SchedulerConfig,
    ServingEngine,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    """Callable clock + sleep — the daemon's full fake-time surface."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def sleep(self, seconds: float) -> None:
        self.t += seconds


@pytest.fixture(scope="module")
def env():
    cfg = tiny_test(dtype=jnp.float32, remat=False)
    model = GPTLM(cfg)
    rng = jax.random.PRNGKey(11)
    lens = [3, 5, 4, 7]
    prompts = [
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, i), (L,), 1, cfg.vocab_size
            )
        )]
        for i, L in enumerate(lens)
    ]
    probe = jax.random.randint(rng, (1, max(lens)), 1, cfg.vocab_size)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]
    refs = [
        [int(t) for t in np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=8,
        ))[0]]
        for p in prompts
    ]
    return cfg, model, params, prompts, refs


def _factory(env, steps=1, **fe_kw):
    cfg, model, params, _, _ = env

    def frontend_factory(clock):
        engine = ServingEngine(
            model, params, n_slots=2,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            decode_steps_per_tick=steps,
        )
        return Frontend(
            [engine], router="least",
            config=FrontendConfig(restart=None, **fe_kw),
            clock=clock, registry=MetricRegistry(),
        )

    return frontend_factory


def _daemon(env, path, clock=None, fe_kw=None, steps=1, **cfg_kw):
    cfg_kw.setdefault("fsync_batch", 4)
    return ServingDaemon(
        _factory(env, steps=steps, **(fe_kw or {})), str(path),
        clock=clock or FakeClock(),
        config=DaemonConfig(**cfg_kw),
    )


# -- journal unit semantics -------------------------------------------------


def test_journal_roundtrip_seq_and_fsync_batching(tmp_path):
    path = str(tmp_path / "j.jsonl")
    clk = FakeClock()
    w = JournalWriter(path, clk, fsync_batch=3)
    base_syncs = w.fsyncs
    for i in range(4):
        w.append({"record": "tokens", "request_id": "r", "tokens": [i]})
    # 4 non-sync-now records at batch 3: exactly one batched fsync fired
    assert w.fsyncs == base_syncs + 1
    w.append({"record": REC_SUBMIT, "request_id": "s", "prompt": [1]})
    assert w.fsyncs == base_syncs + 2  # submits sync immediately
    w.close()
    records, torn = read_journal(path)
    assert torn == 0
    seqs = [r["seq"] for r in records]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert records[0]["record"] == "journal_meta"
    # a new writer continues the sequence instead of restarting it
    w2 = JournalWriter(path, clk, next_seq=load_state(path).next_seq)
    rec = w2.append({"record": "tokens", "request_id": "r", "tokens": []})
    assert rec["seq"] > seqs[-1]
    w2.close()


def test_journal_torn_tail_tolerated_midfile_corruption_raises(tmp_path):
    path = str(tmp_path / "j.jsonl")
    w = JournalWriter(path, FakeClock())
    w.append({"record": REC_SUBMIT, "request_id": "a", "prompt": [1]})
    w.append({"record": REC_TOKENS, "request_id": "a", "tokens": [5]})
    w.close()
    with open(path, "a") as fh:
        fh.write('{"record": "tokens", "request_id": "a", "toke')  # torn
    records, torn = read_journal(path)
    assert torn == 1
    assert [r["record"] for r in records][-1] == REC_TOKENS
    # the same garbage MID-file is corruption, not a torn tail
    lines = open(path).read().splitlines()
    lines.insert(1, "not json at all")
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    with pytest.raises(JournalCorrupt):
        read_journal(path)


def test_replay_state_folds_tokens_by_index_and_terminals():
    records = [
        {"record": REC_SUBMIT, "seq": 0, "request_id": "a",
         "dedupe_token": "da", "prompt": [1], "max_new_tokens": 4},
        {"record": REC_TOKENS, "seq": 1, "request_id": "a",
         "index": 0, "tokens": [10, 11]},
        # overlapping re-delivery (post-recovery re-stream): idempotent
        {"record": REC_TOKENS, "seq": 2, "request_id": "a",
         "index": 1, "tokens": [11, 12]},
        {"record": REC_SUBMIT, "seq": 3, "request_id": "b",
         "dedupe_token": "db", "prompt": [2], "max_new_tokens": 4},
        {"record": REC_TERMINAL, "seq": 4, "request_id": "a",
         "status": "finished", "finish_reason": "length"},
    ]
    state = replay_state(records)
    assert state.entries["a"].tokens == [10, 11, 12]
    assert not state.entries["a"].unfinished
    assert [e.request_id for e in state.unfinished] == ["b"]
    assert state.dedupe == {"da": "a", "db": "b"}
    assert state.next_seq == 5
    assert not state.clean_shutdown


# -- crash + replay recovery (the tentpole contract) ------------------------


def test_crash_replay_recovers_unfinished_bitwise(env, tmp_path):
    """kill -9 simulation mid-stream: the restarted daemon re-admits
    every accepted-but-unfinished request from the journal with its
    durable prefix forced, finishes them, and the full streams equal
    the never-crashed greedy reference bitwise.  Dedupe-token retries
    after the crash return the SAME records — no duplicate admission,
    no duplicate completion, nothing lost."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d1 = _daemon(env, path)
    for i in range(3):
        rec = d1.submit(
            Request(prompt=prompts[i], max_new_tokens=8,
                    request_id=f"r{i}"),
            dedupe_token=f"tok-{i}",
        )
        assert rec["status"] == "queued"
    for _ in range(5):
        d1.tick()
    partial = [len(d1.result(f"r{i}")["tokens"]) for i in range(3)]
    assert any(0 < n < 8 for n in partial), partial  # crash lands mid-stream
    d1.journal.abort()  # the kill -9: no shutdown record, no final sync

    d2 = _daemon(env, path)
    st = load_state(str(path))
    assert st.recoveries == 1  # the restart journaled its replay
    # idempotent client retry: same dedupe token, same record, and the
    # journal must NOT grow a second submit for it
    submits_before = sum(
        1 for r in read_journal(str(path))[0]
        if r["record"] == REC_SUBMIT
    )
    dup = d2.submit(
        Request(prompt=prompts[0], max_new_tokens=8),
        dedupe_token="tok-0",
    )
    assert dup["request_id"] == "r0" and dup["recovered"]
    assert int(d2.registry.counter("daemon_dedupe_hits_total").value) == 1
    for _ in range(60):
        if all(
            d2.result(f"r{i}")["status"] == "finished" for i in range(3)
        ):
            break
        d2.tick()
    for i in range(3):
        rec = d2.result(f"r{i}")
        assert rec["status"] == "finished"
        assert rec["tokens"] == refs[i]  # bitwise through the crash
    submits_after = sum(
        1 for r in read_journal(str(path))[0]
        if r["record"] == REC_SUBMIT
    )
    assert submits_after == submits_before  # zero duplicate admissions
    assert d2.frontend._reserved == 0
    pool = d2.frontend.replicas[0].engine.pool
    assert pool.n_free == pool.n_slots  # zero leaked reservations
    assert int(
        d2.registry.counter("daemon_recovered_requests_total").value
    ) == 3


def test_kill_replay_and_drain_with_a_tick_in_flight(env, tmp_path):
    """The fused engine keeps a tick queued on the device across the
    pump's ticks.  A kill -9 then loses that tick's tokens and nothing
    that was delivered: the restarted daemon finishes every stream
    bitwise, the journal holds each request's tokens gap-free, in order
    and before its terminal, as with the per-step engine.  A drain that
    starts with a tick in flight collects it and exits clean."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d1 = _daemon(env, path, steps=2)
    for i in range(3):  # a queue deeper than the two slots
        d1.submit(Request(prompt=prompts[i], max_new_tokens=8,
                          request_id=f"r{i}"))
    for _ in range(2):
        d1.tick()
    engine = d1.frontend.replicas[0].engine
    assert engine._pending is not None  # the kill lands on a tick in flight
    assert engine.metrics.summary()["overlapped_dispatches"] >= 1
    seen = {f"r{i}": list(d1.result(f"r{i}")["tokens"]) for i in range(3)}
    assert any(0 < len(t) < 8 for t in seen.values()), seen
    d1.journal.abort()  # the kill -9

    d2 = _daemon(env, path, steps=2)
    for i in range(3):
        # what a client saw before the kill is a prefix of the truth
        assert refs[i][: len(seen[f"r{i}"])] == seen[f"r{i}"]
    for _ in range(8):  # the recovered tails drain, the queued one seats
        d2.tick()
        if d2.frontend.replicas[0].engine._pending is not None:
            break
    assert d2.frontend.replicas[0].engine._pending is not None
    d2.request_drain()  # SIGTERM, with a tick in flight
    assert d2.run(max_ticks=100) == EXIT_CLEAN
    engine = d2.frontend.replicas[0].engine
    assert engine._pending is None and not engine.has_work()
    assert engine.pool.n_free == engine.pool.n_slots
    assert d2.frontend._reserved == 0
    for i in range(3):
        rec = d2.result(f"r{i}")
        assert rec["status"] == "finished" and rec["tokens"] == refs[i]
    records, torn = read_journal(str(path))
    assert torn == 0 and records[-1]["record"] == REC_SHUTDOWN
    assert records[-1]["clean"]
    for i in range(3):
        mine = [r for r in records if r.get("request_id") == f"r{i}"]
        kinds = [r["record"] for r in mine]
        assert kinds[0] == REC_SUBMIT and kinds[-1] == REC_TERMINAL
        assert kinds.count(REC_TERMINAL) == 1
        have = 0
        for r in mine:
            if r["record"] == REC_TOKENS:
                # a re-stream after recovery may overlap, never skip
                assert r["index"] <= have
                have = max(have, r["index"] + len(r["tokens"]))
        assert have == 8
    st = load_state(str(path))
    assert st.clean_shutdown and not st.unfinished


def test_recovery_synthesizes_lost_terminals(env, tmp_path):
    """A crash can eat the terminal record after the last token was
    durable: recovery must close such requests (length / delivered-EOS)
    instead of re-admitting and over-generating."""
    path = str(tmp_path / "j.jsonl")
    w = JournalWriter(path, FakeClock())
    w.append({"record": REC_SUBMIT, "request_id": "full",
              "dedupe_token": "tf", "prompt": [3, 4],
              "max_new_tokens": 3})
    w.append({"record": REC_TOKENS, "request_id": "full", "index": 0,
              "tokens": [7, 8, 9]})  # budget exhausted, terminal lost
    w.append({"record": REC_SUBMIT, "request_id": "eos",
              "dedupe_token": "te", "prompt": [3, 4],
              "max_new_tokens": 6, "eos_token_id": 42})
    w.append({"record": REC_TOKENS, "request_id": "eos", "index": 0,
              "tokens": [7, 42]})  # EOS delivered, terminal lost
    w.abort()
    d = _daemon(env, path)
    full, eos = d.result("full"), d.result("eos")
    assert full["status"] == "finished"
    assert full["finish_reason"] == "length"
    assert eos["status"] == "finished" and eos["finish_reason"] == "eos"
    assert not d.frontend.has_work()  # nothing re-admitted
    assert int(
        d.registry.counter("daemon_recovered_completions_total").value
    ) == 2
    # and the synthesized terminals are durable for the NEXT restart
    st = load_state(path)
    assert not st.unfinished


def test_recovery_rejection_is_loud_and_typed(env, tmp_path):
    """A replayed request the restarted config can no longer admit
    terminates REJECTED with the frontend's typed reason — journaled —
    never silently dropped."""
    _, _, _, prompts, _ = env
    path = tmp_path / "j.jsonl"
    d1 = _daemon(env, path)
    d1.submit(Request(prompt=prompts[0], max_new_tokens=8,
                      request_id="big"), dedupe_token="tb")
    d1.tick()
    d1.journal.abort()
    # restart with a token budget too small for the replay
    d2 = _daemon(env, path, fe_kw={"max_inflight_tokens": 4})
    rec = d2.result("big")
    assert rec["status"] == REJECTED
    assert rec["finish_reason"] == "token_budget"
    terminals = [
        r for r in read_journal(str(path))[0]
        if r["record"] == REC_TERMINAL and r["request_id"] == "big"
    ]
    assert len(terminals) == 1 and terminals[0]["status"] == REJECTED


# -- dedupe idempotence ------------------------------------------------------


def test_dedupe_completed_request_returns_cached_result(env, tmp_path):
    _, _, _, prompts, refs = env
    d = _daemon(env, tmp_path / "j.jsonl")
    d.submit(Request(prompt=prompts[0], max_new_tokens=8,
                     request_id="x", dedupe_token="same"))
    for _ in range(30):
        if d.result("x")["status"] == "finished":
            break
        d.tick()
    accepted = int(d.registry.counter("daemon_accepted_total").value)
    again = d.submit(Request(prompt=prompts[0], max_new_tokens=8,
                             dedupe_token="same"))
    assert again["request_id"] == "x" and again["tokens"] == refs[0]
    assert int(
        d.registry.counter("daemon_accepted_total").value
    ) == accepted  # no second admission
    assert not d.frontend.has_work()


# -- drain / shutdown contract ----------------------------------------------


def test_sigterm_drain_finishes_inflight_rejects_new_exits_clean(
    env, tmp_path
):
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d = _daemon(env, path)
    d.submit(Request(prompt=prompts[0], max_new_tokens=8,
                     request_id="r0"))
    d.tick()
    d.request_drain()  # SIGTERM equivalent
    rc = d.run(max_ticks=100)
    assert rc == EXIT_CLEAN
    assert d.result("r0")["tokens"] == refs[0]  # in-flight finished
    # late submission refused typed `draining`
    late = d.submit(Request(prompt=prompts[1], max_new_tokens=4))
    assert late["status"] == REJECTED
    assert late["finish_reason"] == REJECT_DRAINING
    records, torn = read_journal(str(path))
    assert torn == 0
    assert records[-1]["record"] == REC_SHUTDOWN and records[-1]["clean"]
    st = load_state(str(path))
    assert st.clean_shutdown and not st.unfinished


def test_second_sigterm_forces_fast_shutdown_journal_recovers(
    env, tmp_path
):
    """SIGTERM twice = fast shutdown NOW: exit code 1, shutdown record
    not clean, and the open request survives into the next recovery."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d = _daemon(env, path)
    d.submit(Request(prompt=prompts[0], max_new_tokens=8,
                     request_id="r0"), dedupe_token="t0")
    d.tick()
    d.request_drain()
    d.request_drain()  # the second TERM
    rc = d.run(max_ticks=100)
    assert rc == EXIT_FORCED
    records, _ = read_journal(str(path))
    assert records[-1]["record"] == REC_SHUTDOWN
    assert not records[-1]["clean"]
    d2 = _daemon(env, path)
    for _ in range(40):
        if d2.result("r0")["status"] == "finished":
            break
        d2.tick()
    assert d2.result("r0")["tokens"] == refs[0]


def test_blown_grace_window_forces_shutdown(env, tmp_path):
    """A drain that cannot finish inside grace_seconds exits forced
    instead of hanging — the journal carries the remainder."""
    _, _, _, prompts, _ = env
    clk = FakeClock()
    d = _daemon(env, tmp_path / "j.jsonl", clock=clk, grace_seconds=5.0)
    d.submit(Request(prompt=prompts[0], max_new_tokens=8,
                     request_id="r0"))
    d.request_drain()
    d._begin_drain()
    clk.t += 10.0  # wall time blows straight through the grace window
    rc = d.run(max_ticks=3)
    assert rc == EXIT_FORCED
    st = load_state(str(tmp_path / "j.jsonl"))
    assert not st.clean_shutdown


# -- frontend journal hooks --------------------------------------------------


def test_frontend_journal_hook_fires_on_lifecycle_points(env, tmp_path):
    _, _, _, prompts, _ = env
    notes = []
    d = _daemon(env, tmp_path / "j.jsonl")
    d.frontend.set_journal(lambda kind, payload: notes.append(kind))
    d.frontend.submit(Request(prompt=prompts[0], max_new_tokens=2))
    assert "submit_accepted" in notes
    d.frontend.run(max_ticks=30)
    assert "terminal" in notes
    d.frontend.drain()
    assert "drain_begin" in notes


# -- HTTP + SSE face ---------------------------------------------------------


def test_http_endpoints_and_sse_stream(env, tmp_path):
    """The stdlib network face against a live wall-clock daemon: submit
    over HTTP (journal-durable), SSE stream to completion, healthz
    flip on drain, statez leak fields, cancel route."""
    import urllib.request

    _, _, _, prompts, refs = env
    d = ServingDaemon(
        _factory(env), str(tmp_path / "j.jsonl"),
        clock=WallClock(),
        config=DaemonConfig(fsync_batch=4, grace_seconds=30.0),
    )
    server = DaemonHTTPServer(d).start()
    rc_box = []
    pump = threading.Thread(
        target=lambda: rc_box.append(d.run()), daemon=True
    )
    pump.start()
    base = f"http://127.0.0.1:{server.port}"

    def call_port(port, method, path, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data, method=method
        )
        if data is not None:
            req.add_header("Content-Type", "application/json")
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as exc:
            return exc.code, json.loads(exc.read() or b"{}")

    def call(method, path, body=None):
        return call_port(server.port, method, path, body)

    try:
        code, health = call("GET", "/healthz")
        assert code == 200 and health["ok"]
        code, rec = call("POST", "/v1/submit", {
            "prompt": prompts[0], "max_new_tokens": 8,
            "dedupe_token": "http-0",
        })
        assert code == 200
        rid = rec["request_id"]
        # malformed body is a 400, not a daemon error
        code, _ = call("POST", "/v1/submit", {"prompt": "nope"})
        assert code == 400
        # SSE: tokens then the finished event
        with urllib.request.urlopen(
            base + f"/v1/stream/{rid}", timeout=60
        ) as resp:
            payload = resp.read()
        events = [
            json.loads(line[len(b"data: "):])
            for line in payload.split(b"\n")
            if line.startswith(b"data: ")
        ]
        toks = [e["token"] for e in events if "token" in e]
        assert toks == refs[0]
        assert events[-1]["finished"]
        assert events[-1]["finish_reason"] == "length"
        # cancel an unknown id 404s; a live one cancels
        code, _ = call("POST", "/v1/cancel/nope")
        assert code == 404
        code, rec2 = call("POST", "/v1/submit", {
            "prompt": prompts[1], "max_new_tokens": 8,
        })
        assert code == 200
        code, _ = call("POST", f"/v1/cancel/{rec2['request_id']}")
        assert code == 200
        code, state = call("GET", "/statez")
        assert code == 200
        assert "inflight_tokens" in state["cluster"]
        assert state["daemon"]["degraded_reason"] is None
        # the KV export route matches its path EXACTLY and refuses
        # nonsense parameters typed instead of passing them through
        code, err = call("GET", "/v1/kv/exportfoo")
        assert code == 404
        code, err = call("GET", "/v1/kv/export?max_blocks=-5")
        assert code == 400 and "max_blocks" in err["error"]
        code, err = call("GET", "/v1/kv/export?max_blocks=abc")
        assert code == 400
        # bounded body read: an oversized submit refuses 413 WITHOUT
        # buffering the payload (a second server on the same daemon,
        # with a tiny cap, proves the knob)
        small = DaemonHTTPServer(d, max_body_bytes=64).start()
        try:
            code, err = call_port(
                small.port, "POST", "/v1/submit",
                {"prompt": list(range(200)), "max_new_tokens": 4},
            )
            assert code == 413 and "limit" in err["error"]
            # under the cap the same server still accepts
            code, _ = call_port(
                small.port, "POST", "/v1/submit",
                {"prompt": [1, 2], "max_new_tokens": 4},
            )
            assert code == 200
        finally:
            small.stop()
        with pytest.raises(ValueError):
            DaemonHTTPServer(d, max_body_bytes=0)
        with pytest.raises(ValueError):
            DaemonHTTPServer(d, sse_keepalive_seconds=0)
        # drain: healthz flips 503 for the balancer, daemon exits 0
        d.request_drain()
        pump.join(timeout=60)
        assert rc_box == [EXIT_CLEAN]
        code, health = call("GET", "/healthz")
        assert code == 503
    finally:
        server.stop()


# -- the real-subprocess smoke (also scripts/check_all.py's gate) -----------


def test_daemon_smoke_subprocess():
    """start -> HTTP submit -> SSE replay -> SIGTERM -> exit 0 with a
    clean journal, as one REAL process receiving real signals.  This is
    exactly what ``check_all``'s ``check_daemon`` runtime gate runs."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import check_daemon
    finally:
        sys.path.pop(0)
    problems = check_daemon.check_paths()
    assert problems == [], "\n".join(problems)


def test_sighup_reload_journals_typed_decision(env, tmp_path):
    """SIGHUP's reload flows are journaled as typed DECISION records:
    no reload_path configured, unreadable spec, and a spec without a
    checkpoint_dir each refuse loudly instead of killing the pump."""
    # no reload_path
    d = _daemon(env, tmp_path / "a.jsonl")
    d.request_reload()
    d.run(max_ticks=1)
    recs, _ = read_journal(str(tmp_path / "a.jsonl"))
    decisions = [r for r in recs if r["record"] == "decision"]
    assert decisions and decisions[-1]["verdict"] == "no_reload_path"
    # unreadable spec file
    d2 = _daemon(env, tmp_path / "b.jsonl",
                 reload_path=str(tmp_path / "missing.json"))
    d2.request_reload()
    d2.run(max_ticks=1)
    recs, _ = read_journal(str(tmp_path / "b.jsonl"))
    assert [r for r in recs if r["record"] == "decision"][-1][
        "verdict"
    ] == "unreadable"
    # spec without a checkpoint_dir
    spec = tmp_path / "spec.json"
    spec.write_text("{}")
    d3 = _daemon(env, tmp_path / "c.jsonl", reload_path=str(spec))
    d3.request_reload()
    d3.run(max_ticks=1)
    recs, _ = read_journal(str(tmp_path / "c.jsonl"))
    assert [r for r in recs if r["record"] == "decision"][-1][
        "verdict"
    ] == "no_checkpoint_dir"
    assert int(
        d3.registry.counter("daemon_signals_total", signal="hup").value
    ) == 1


def test_torn_tail_truncated_before_reopen_double_restart(env, tmp_path):
    """A writer reopening after a torn write must TRUNCATE the fragment
    — appending onto it would weld the next record into mid-file
    garbage and brick the journal (JournalCorrupt) on the SECOND
    restart.  Two full crash+recover cycles over a torn tail must both
    succeed, with nothing durable lost."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d1 = _daemon(env, path)
    d1.submit(Request(prompt=prompts[0], max_new_tokens=8,
                      request_id="r0"), dedupe_token="t0")
    for _ in range(3):
        d1.tick()
    d1.journal.abort()
    with open(path, "a") as fh:  # the write the SIGKILL cut mid-record
        fh.write('{"record": "tokens", "request_id": "r0", "toke')
    # restart 1: fragment dropped BEFORE reading (the daemon truncates
    # ahead of load_state so recovery acts on exactly what stays
    # durable), recovery replays, MORE records append
    d2 = _daemon(env, path)
    # the fragment is GONE (not merely tolerated): the whole file —
    # including the records recovery just appended — parses torn-free
    assert read_journal(str(path))[1] == 0
    for _ in range(3):
        d2.tick()
    d2.journal.abort()  # crash again mid-stream
    # restart 2: the journal must still parse (no mid-file corruption)
    d3 = _daemon(env, path)
    for _ in range(40):
        if d3.result("r0")["status"] == "finished":
            break
        d3.tick()
    assert d3.result("r0")["tokens"] == refs[0]
    records, torn = read_journal(str(path))
    assert torn == 0  # every surviving record is parseable


def test_completed_retention_bounds_memory(env, tmp_path):
    """Terminal records past ``completed_retention`` evict oldest-first
    (with their dedupe tokens): daemon memory is bounded at any uptime,
    the open count stays exact, and an evicted token re-admits as a
    fresh request instead of replaying a record that no longer exists."""
    _, _, _, prompts, _ = env
    d = _daemon(env, tmp_path / "j.jsonl", completed_retention=2)
    rids = []
    for i in range(4):
        rec = d.submit(
            Request(prompt=prompts[i % len(prompts)], max_new_tokens=2,
                    request_id=f"r{i}"),
            dedupe_token=f"t{i}",
        )
        rids.append(rec["request_id"])
        for _ in range(20):
            if d.result(f"r{i}") is None or (
                d.result(f"r{i}")["status"] == "finished"
            ):
                break
            d.tick()
    assert len(d._requests) == 2  # bounded: only the newest two remain
    assert d.result("r0") is None and d.result("r3") is not None
    assert "t0" not in d._dedupe and "t3" in d._dedupe
    assert d._open_count == 0
    # an evicted dedupe token is a NEW admission now (fresh request id)
    again = d.submit(
        Request(prompt=prompts[0], max_new_tokens=2), dedupe_token="t0"
    )
    assert again["request_id"] != "r0"


# -- integrity: IO faults, CRC, the corruption matrix ------------------------


def test_iofault_plan_seeded_determinism():
    """Same rng state + ops + kinds => identical plan; bad inputs
    refuse loudly — the FaultPlan.from_seed contract, IO edition."""
    import random

    p1 = IOFaultPlan.from_seed(random.Random(9), ops=32)
    p2 = IOFaultPlan.from_seed(random.Random(9), ops=32)
    assert p1 == p2
    k1 = IOFaultPlan.from_seed(
        random.Random(4), ops=16, kinds=("fsync_eio", "bit_flip")
    )
    assert k1.fsync_eio_at is not None and k1.flip_read_at is not None
    assert k1.enospc_at_write is None and k1.short_write_at is None
    with pytest.raises(ValueError):
        IOFaultPlan.from_seed(random.Random(0), ops=2)
    with pytest.raises(ValueError):
        IOFaultPlan.from_seed(random.Random(0), kinds=("bogus",))


def test_iofault_injection_shapes(tmp_path):
    """Each injected fault has its contract shape: short write / ENOSPC
    leave a torn prefix AND raise; fsync raises EIO; a read bit flip
    changes exactly the payload (same length, different bytes)."""
    p = tmp_path / "f.txt"
    with iofaults.inject(IOFaultPlan(short_write_at=1)) as inj:
        with open(p, "w") as fh:
            iofaults.write_line(fh, "hello world\n")
            with pytest.raises(OSError):
                iofaults.write_line(fh, "second record here\n")
        text = p.read_text()
        assert text.startswith("hello world\n")
        assert "second record here" not in text
        assert len(text) > len("hello world\n")  # the torn prefix landed
        assert inj.injected["short_write"] == 1
    with iofaults.inject(IOFaultPlan(enospc_at_write=0)) as inj:
        with open(p, "w") as fh:
            with pytest.raises(OSError) as exc:
                iofaults.write_line(fh, "doomed record\n")
        assert "ENOSPC" in str(exc.value) or "full" in str(exc.value)
        assert inj.injected["enospc"] == 1
    with iofaults.inject(IOFaultPlan(fsync_eio_at=0)) as inj:
        with open(p, "a") as fh:
            with pytest.raises(OSError):
                iofaults.fsync_file(fh)
        assert inj.injected["fsync_eio"] == 1
    p.write_text("payload bytes\n")
    with iofaults.inject(IOFaultPlan(flip_read_at=0, flip_read_bit=9)):
        flipped = iofaults.read_text(str(p))
    clean = p.read_text()
    assert flipped != clean and len(flipped) == len(clean)
    # with no injector installed the wrappers are the raw ops
    assert iofaults.read_text(str(p)) == clean


def test_journal_crc_round_trip_under_seeded_bit_flips(tmp_path):
    """Every written record carries a verifying CRC; ONE flipped bit
    anywhere in a record's line is detected — tolerated (torn) at the
    tail, typed JournalCorrupt anywhere else.  Seeded sweep so the flip
    lands in keys, values, digits and the crc field itself."""
    import random

    path = str(tmp_path / "j.jsonl")
    w = JournalWriter(path, FakeClock())
    w.append({"record": REC_SUBMIT, "request_id": "a", "prompt": [1, 2],
              "max_new_tokens": 4, "dedupe_token": "da"})
    w.append({"record": REC_TOKENS, "request_id": "a", "index": 0,
              "tokens": [7, 8]})
    w.append({"record": REC_TERMINAL, "request_id": "a",
              "status": "finished", "finish_reason": "length"})
    w.close()
    records, torn = read_journal(path)
    assert torn == 0
    assert all(record_crc_ok(r) is True for r in records)
    clean = open(path, "rb").read()
    lines = clean.splitlines(keepends=True)
    rnd = random.Random(17)
    for trial in range(12):
        lineno = rnd.randrange(1, len(lines))  # never the meta record
        line = bytearray(lines[lineno])
        bit = rnd.randrange((len(line) - 1) * 8)  # never the newline
        line[bit // 8] ^= 1 << (bit % 8)
        with open(path, "wb") as fh:
            fh.write(b"".join(
                [bytes(line) if i == lineno else orig
                 for i, orig in enumerate(lines)]
            ))
        if lineno == len(lines) - 1:
            got, t = read_journal(path)
            # a flip that mints a "\n" splits the record into TWO bad
            # tail lines — still tolerated, still exactly one record lost
            assert 1 <= t <= 2, f"trial {trial}: tail flip not detected"
            assert len(got) == len(lines) - 1
        else:
            with pytest.raises(JournalCorrupt) as exc:
                read_journal(path)
            assert exc.value.reason in (CORRUPT_CRC, CORRUPT_GARBAGE)


def test_corruption_matrix_typed_distinctly(tmp_path):
    """Mid-file garbage, a CRC mismatch, and a sequence regression are
    DIFFERENT failures and each carries its own typed reason."""
    def write_lines(lines):
        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        return path

    rec_lines = []
    for seq, rec in enumerate([
        {"record": "journal_meta", "journal_version": 2},
        {"record": REC_SUBMIT, "request_id": "a", "prompt": [1]},
        {"record": REC_TOKENS, "request_id": "a", "index": 0,
         "tokens": [5]},
        {"record": REC_TERMINAL, "request_id": "a",
         "status": "finished", "finish_reason": "length"},
    ]):
        line, _ = encode_record({**rec, "seq": seq, "at": 0.0})
        rec_lines.append(line)
    # baseline parses clean
    assert read_journal(write_lines(rec_lines))[1] == 0
    # (a) unparseable bytes mid-file
    garbage = rec_lines[:2] + ["!!not json!!"] + rec_lines[2:]
    with pytest.raises(JournalCorrupt) as exc:
        read_journal(write_lines(garbage))
    assert exc.value.reason == CORRUPT_GARBAGE
    # (b) parseable record whose checksum disagrees: a one-digit edit
    # of the token value with the original crc left in place
    assert '"tokens": [5]' in rec_lines[2]
    tampered = rec_lines[2].replace('"tokens": [5]', '"tokens": [6]')
    with pytest.raises(JournalCorrupt) as exc:
        read_journal(write_lines(
            rec_lines[:2] + [tampered] + rec_lines[3:]
        ))
    assert exc.value.reason == CORRUPT_CRC
    # (c) valid records whose order lies
    back = [
        encode_record({"record": REC_TOKENS, "request_id": "a",
                       "index": 0, "tokens": [], "seq": s})[0]
        for s in (5, 3)
    ]
    with pytest.raises(JournalCorrupt) as exc:
        read_journal(write_lines(rec_lines[:1] + back))
    assert exc.value.reason == CORRUPT_SEQ


def test_crc_failed_tail_truncated_and_recovered_bitwise(env, tmp_path):
    """Post-fsync bit rot on the journal's LAST record (line intact,
    checksum wrong): the restart truncates exactly that record — same
    treatment as a torn write — and forced-prefix recovery regenerates
    whatever it held, bitwise."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d1 = _daemon(env, path)
    d1.submit(Request(prompt=prompts[0], max_new_tokens=8,
                      request_id="r0"), dedupe_token="t0")
    for _ in range(4):
        d1.tick()
    assert 0 < len(d1.result("r0")["tokens"]) < 8
    d1.journal.abort()
    data = open(path, "rb").read()
    assert data.endswith(b"\n")
    start = data.rfind(b"\n", 0, len(data) - 1) + 1
    flipped = bytearray(data)
    flipped[start + 10] ^= 0x10  # one bit inside the last record
    with open(path, "wb") as fh:
        fh.write(bytes(flipped))
    records_before, torn = read_journal(str(path))
    assert torn == 1  # reader: tolerated tail damage
    dropped = drop_torn_tail(str(path))
    assert dropped > 0  # truncater: the damaged record is GONE
    d2 = _daemon(env, path)
    assert read_journal(str(path))[1] == 0
    for _ in range(60):
        if d2.result("r0")["status"] == "finished":
            break
        d2.tick()
    assert d2.result("r0")["tokens"] == refs[0]
    assert d2.submit(
        Request(prompt=prompts[0], max_new_tokens=8), dedupe_token="t0"
    )["request_id"] == "r0"  # dedupe survived the damage


def test_pre_crc_journal_replays_unchanged(env, tmp_path):
    """A PR 14 journal (no crc fields) is still a valid recovery
    source: CRCs are verified WHEN PRESENT, so the old format replays
    — and finishes bitwise — without rewrite or refusal."""
    _, _, _, prompts, refs = env
    path = str(tmp_path / "old.jsonl")
    recs = [
        {"record": "journal_meta", "journal_version": 1, "seq": 0},
        {"record": REC_SUBMIT, "seq": 1, "at": 0.1, "request_id": "r0",
         "dedupe_token": "t0", "arrival": 0.1,
         "prompt": [int(t) for t in prompts[0]],
         "prompt_len": len(prompts[0]), "prefix_group": 0,
         "priority": 0, "deadline": None, "max_new_tokens": 8,
         "eos_token_id": None, "sampling": None},
        {"record": REC_TOKENS, "seq": 2, "request_id": "r0",
         "index": 0, "tokens": refs[0][:3]},
    ]
    with open(path, "w") as fh:
        for rec in recs:
            fh.write(json.dumps(rec) + "\n")
    d = _daemon(env, path)
    rec = d.result("r0")
    assert rec is not None and rec["tokens"][:3] == refs[0][:3]
    for _ in range(60):
        if d.result("r0")["status"] == "finished":
            break
        d.tick()
    assert d.result("r0")["tokens"] == refs[0]  # bitwise through formats


# -- rotation + compaction ---------------------------------------------------


def test_compaction_bounds_replay_records(env, tmp_path):
    """After a run with requests >> completed_retention, rotation keeps
    restart replay O(open + retained): the journal's record count stays
    bounded while the lifetime record count grows, recovery still
    dedupes retained tokens, and an OPEN request crosses a compaction
    with its stream continuing bitwise."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d = _daemon(
        env, path, completed_retention=2, compact_interval_records=20,
    )
    for i in range(8):
        d.submit(
            Request(prompt=prompts[i % len(prompts)], max_new_tokens=4,
                    request_id=f"r{i}"),
            dedupe_token=f"t{i}",
        )
        for _ in range(20):
            rec = d.result(f"r{i}")
            if rec is None or rec["status"] == "finished":
                break
            d.tick()
    lifetime = d.journal.records  # every record EVER appended
    assert d.journal.rotations >= 1, "interval never triggered a rotate"
    on_disk = len(read_journal(str(path))[0])
    # disk holds at most: the snapshot (<= 3 records per retained
    # request + meta) plus one interval's worth of fresh appends —
    # NOT the lifetime
    assert on_disk <= 20 + 3 * 3 + 2, (lifetime, on_disk)
    assert lifetime > on_disk
    # an OPEN request across a compaction: submit, stream partway,
    # force a rotation mid-stream, then crash — recovery must continue
    # bitwise from the compacted snapshot
    d.submit(Request(prompt=prompts[0], max_new_tokens=8,
                     request_id="open"), dedupe_token="topen")
    for _ in range(3):
        d.tick()
    assert 0 < len(d.result("open")["tokens"]) < 8
    d._compact()
    open_records = [
        r for r in read_journal(str(path))[0]
        if r.get("request_id") == "open"
    ]
    # exactly the snapshot pair: one submit + one tokens record
    assert [r["record"] for r in open_records] == [
        REC_SUBMIT, REC_TOKENS
    ]
    d.journal.abort()  # crash right after the rotate
    d2 = _daemon(env, path, completed_retention=2)
    for _ in range(60):
        if d2.result("open")["status"] == "finished":
            break
        d2.tick()
    assert d2.result("open")["tokens"] == refs[0]
    # retained dedupe survived compaction; evicted tokens re-admit
    assert d2.submit(
        Request(prompt=prompts[0], max_new_tokens=8),
        dedupe_token="topen",
    )["request_id"] == "open"


def test_double_crash_during_compaction_loses_nothing(env, tmp_path):
    """Both compaction crash windows: (a) crash AFTER the new segment
    (sidecar) is written but BEFORE the old one retires — the orphan
    sidecar is discarded and the old journal stays authoritative; (b)
    crash right after the atomic replace — the snapshot alone recovers.
    Neither loses an accepted request nor duplicates a completion."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    d = _daemon(env, path, completed_retention=4)
    d.submit(Request(prompt=prompts[0], max_new_tokens=8,
                     request_id="r0"), dedupe_token="t0")
    for _ in range(3):
        d.tick()
    # (a) a half-written new segment, then the crash
    with open(str(path) + ROTATE_SUFFIX, "w") as fh:
        fh.write('{"record": "journal_meta", "journal_ver')  # torn
    d.journal.abort()
    d2 = _daemon(env, path, completed_retention=4)
    assert not os.path.exists(str(path) + ROTATE_SUFFIX)  # discarded
    for _ in range(60):
        if d2.result("r0")["status"] == "finished":
            break
        d2.tick()
    assert d2.result("r0")["tokens"] == refs[0]
    # (b) a real rotate, then an immediate crash
    d2.submit(Request(prompt=prompts[1], max_new_tokens=8,
                      request_id="r1"), dedupe_token="t1")
    for _ in range(3):
        d2.tick()
    d2._compact()
    d2.journal.abort()
    d3 = _daemon(env, path, completed_retention=4)
    for _ in range(60):
        if d3.result("r1")["status"] == "finished":
            break
        d3.tick()
    assert d3.result("r1")["tokens"] == refs[1]
    # no duplicate admissions across all three lives
    state = load_state(str(path))
    assert sorted(state.dedupe) == ["t0", "t1"]
    assert not state.unfinished
    submits = [
        r for r in read_journal(str(path))[0]
        if r["record"] == REC_SUBMIT
    ]
    assert len(submits) == len({r["request_id"] for r in submits})


# -- degraded mode -----------------------------------------------------------


def test_degraded_mode_typed_rejects_drains_and_exits_clean(
    env, tmp_path
):
    """Persistent fsync EIO: the daemon counts the failures, enters
    DEGRADED (typed reason exposed), refuses new submissions with the
    typed ``degraded`` reason, finishes its in-flight work, and STILL
    drains exit 0 on SIGTERM — the process never dies mid-accept."""
    _, _, _, prompts, refs = env
    path = tmp_path / "j.jsonl"
    with iofaults.inject(IOFaultPlan(
        fsync_eio_at=2, fsync_eio_count=iofaults.PERSISTENT
    )) as inj:
        d = _daemon(env, path, degrade_after_io_errors=2)
        rec = d.submit(
            Request(prompt=prompts[0], max_new_tokens=8,
                    request_id="r0"),
            dedupe_token="t0",
        )
        assert rec["status"] == "queued"  # accepted before the EIOs
        for _ in range(30):
            d.tick()
            if d.degraded_reason is not None:
                break
        assert d.degraded_reason == "journal_io"
        assert inj.injected["fsync_eio"] >= 2
        assert int(
            d.registry.counter(
                "daemon_journal_integrity_io_errors_total"
            ).value
        ) >= 2
        # new submissions refuse TYPED (the HTTP layer maps it to 503)
        late = d.submit(Request(prompt=prompts[1], max_new_tokens=4))
        assert late["status"] == REJECTED
        assert late["finish_reason"] == REJECT_DEGRADED
        assert "journal_io" in late["detail"]
        # in-flight work drains to completion, bitwise
        for _ in range(60):
            if d.result("r0")["status"] == "finished":
                break
            d.tick()
        assert d.result("r0")["tokens"] == refs[0]
        assert d.status()["degraded_reason"] == "journal_io"
        # SIGTERM still drains exit 0 while degraded
        d.request_drain()
        assert d.run(max_ticks=100) == EXIT_CLEAN
    # the journal never bricked: a fresh scan tolerates at most tail
    # damage, and the accepted request's submit record is durable
    state = load_state(str(path))
    assert "t0" in state.dedupe


# -- the pump's phase clock and the lock, observed ---------------------------


class _SignalClock(FakeClock):
    """A FakeClock that says when somebody read it."""

    def __init__(self):
        super().__init__()
        self.read = threading.Semaphore(0)

    def __call__(self) -> float:
        self.read.release()
        return self.t


def _hist(daemon, name, **labels):
    (row,) = [
        r for r in daemon.registry.snapshot()["histograms"]
        if r["name"] == name and r["labels"] == labels
    ]
    return row


def test_submit_waits_for_the_pumps_lock_and_says_how_long(env, tmp_path):
    """A submit issued while another thread holds the daemon's lock
    records a `lock_wait` at least as long as the hold, apart from what
    it held itself."""
    _, _, _, prompts, _ = env
    clock = _SignalClock()
    d = _daemon(env, tmp_path / "j.jsonl", clock=clock)
    got = []
    d._lock.acquire()  # the pump, mid-tick
    try:
        t = threading.Thread(target=lambda: got.append(d.submit(
            Request(prompt=prompts[0], max_new_tokens=4, request_id="r0")
        )))
        while clock.read.acquire(blocking=False):
            pass  # reads made so far are not the submit's
        t.start()
        assert clock.read.acquire(timeout=30)  # it read the clock: waiting
        clock.t += 5.0  # ... through all of the hold
    finally:
        d._lock.release()
    t.join(timeout=30)
    assert not t.is_alive() and got[0]["status"] != REJECTED
    wait = _hist(d, "daemon_call_seconds", call="submit", phase="lock_wait")
    held = _hist(d, "daemon_call_seconds", call="submit", phase="held")
    assert wait["count"] == held["count"] == 1
    assert wait["sum"] >= 5.0 and held["sum"] == 0.0
    # the snapshot submit returns is taken under the same hold: no
    # `result` call of its own
    assert _hist(
        d, "daemon_call_seconds", call="result", phase="lock_wait"
    )["count"] == 0
    d.result("r0")
    d.cancel("r0")
    d.subscribe("r0")
    for call in ("result", "cancel", "subscribe"):
        assert _hist(
            d, "daemon_call_seconds", call=call, phase="held"
        )["count"] == 1


def test_pump_tick_observes_its_phases_and_its_own_lock_wait(env, tmp_path):
    _, _, _, prompts, _ = env
    clock = _SignalClock()
    d = _daemon(env, tmp_path / "j.jsonl", clock=clock)
    d.submit(Request(prompt=prompts[0], max_new_tokens=4, request_id="r0"))
    done = []
    d._lock.acquire()  # a handler thread, mid-submit
    try:
        t = threading.Thread(target=lambda: done.append(d.tick()))
        while clock.read.acquire(blocking=False):
            pass
        t.start()
        assert clock.read.acquire(timeout=30)
        clock.t += 2.0
    finally:
        d._lock.release()
    t.join(timeout=30)
    assert not t.is_alive() and done
    for _ in range(4):
        d.tick()
    for name in ("lock_wait", "step", "journal", "fsync", "housekeeping"):
        assert _hist(
            d, "daemon_tick_phase_seconds", phase=name
        )["count"] == 5, name
    waited = _hist(d, "daemon_tick_phase_seconds", phase="lock_wait")
    assert waited["sum"] == 2.0  # the one contended tick, nothing else


def test_callers_that_wait_go_before_the_pumps_next_tick(
    env, tmp_path, monkeypatch
):
    """`threading.RLock` is not fair, and the pump asks for it again
    microseconds after a tick's end: the callers that waited through a
    tick take the lock before the next tick does (`_callers_turn`)."""
    from tpu_parallel.daemon import daemon as daemon_mod

    # the bound is not what is timed here (a loaded machine wakes threads late)
    monkeypatch.setattr(daemon_mod, "_CALLERS_TURN_SECONDS", 30.0)
    _, _, _, prompts, _ = env
    d = _daemon(env, tmp_path / "j.jsonl")
    d.submit(Request(prompt=prompts[0], max_new_tokens=4, request_id="r0"))
    step, mid_tick, go = d.frontend.step, threading.Event(), threading.Event()

    def held_step():  # the pump, mid-tick, until every caller waits
        mid_tick.set()
        assert go.wait(30)
        return step()

    d.frontend.step = held_step
    seen = []

    def caller():
        d.result("r0")
        seen.append(d.ticks)

    pump = threading.Thread(target=lambda: [d.tick() for _ in range(3)])
    pump.start()
    assert mid_tick.wait(30)
    callers = [threading.Thread(target=caller) for _ in range(16)]
    for t in callers:
        t.start()
    deadline = time.monotonic() + 30
    while d._callers_waiting < len(callers) and time.monotonic() < deadline:
        time.sleep(0.001)
    assert d._callers_waiting == len(callers)
    go.set()
    for t in callers + [pump]:
        t.join(timeout=30)
        assert not t.is_alive()
    # every one of them ran between the first tick and the second
    assert seen == [1] * len(callers) and d.ticks == 3
    assert d._callers_waiting == 0


def test_a_caller_that_never_comes_does_not_hold_the_pump_off(
    env, tmp_path, monkeypatch
):
    from tpu_parallel.daemon import daemon as daemon_mod

    monkeypatch.setattr(daemon_mod, "_CALLERS_TURN_SECONDS", 0.05)
    d = _daemon(env, tmp_path / "j.jsonl")
    d._callers_waiting = 1  # counted, and never takes the lock
    t0 = time.monotonic()
    d.tick()
    assert 0.05 <= time.monotonic() - t0 < 20 and d.ticks == 1
    # the wait is the pump's `lock_wait` phase; the fake clock stood still
    assert _hist(d, "daemon_tick_phase_seconds", phase="lock_wait")["sum"] == 0.0


def test_phase_annotations_are_leaves_on_the_pump_thread(
    env, tmp_path, monkeypatch
):
    """Over 20 ticks with traffic, what reaches the profiler under
    `engine.tick.` / `daemon.tick.` is a sequence of leaves (never one
    inside another), all from the pump's thread, none from a handler
    thread's submit, and no enclosing span shares the prefixes."""
    from tpu_parallel.obs import phases as phase_module

    _, _, _, prompts, _ = env
    seen, open_now, nested = [], [], []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            if open_now:
                nested.append((list(open_now), self.name))
            open_now.append(self.name)
            seen.append((self.name, threading.get_ident()))

        def __exit__(self, *exc):
            open_now.remove(self.name)

    monkeypatch.setattr(phase_module, "TraceAnnotation", Recorder)
    d = _daemon(env, tmp_path / "j.jsonl")
    pump = threading.get_ident()

    def handler():
        for i, p in enumerate(prompts):
            d.submit(Request(prompt=p, max_new_tokens=6, request_id=f"h{i}"))
            d.result(f"h{i}")

    t = threading.Thread(target=handler)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive() and not seen  # calls annotate nothing
    for _ in range(20):
        d.tick()
    assert all(d.result(f"h{i}")["status"] == "finished" for i in range(4))
    names = {name for name, _ in seen}
    assert names == {
        "engine.tick.schedule", "engine.tick.prefill",
        "engine.tick.dispatch", "engine.tick.device_wait",
        "engine.tick.deliver", "engine.tick.record",
        "daemon.tick.lock_wait", "daemon.tick.journal",
        "daemon.tick.fsync", "daemon.tick.housekeeping",
    }
    assert not nested and not open_now
    assert {ident for _, ident in seen} == {pump}
    assert sum(n == "daemon.tick.journal" for n, _ in seen) == 20


def test_tracez_returns_every_span_once_and_the_tracer_stays_small(
    env, tmp_path
):
    """Tracing on for a long-lived daemon: what the spool has written is
    released from the tracer, and `/v1/tracez` still has each span once."""
    from tpu_parallel.obs import SpanSpool, Tracer

    cfg, model, params, prompts, _ = env
    clock = FakeClock()
    tracer = Tracer(clock=clock)

    def frontend_factory(clk):
        engine = ServingEngine(
            model, params, n_slots=2, clock=clk, tracer=tracer,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
        )
        return Frontend(
            [engine], router="least", config=FrontendConfig(restart=None),
            clock=clk, registry=MetricRegistry(), tracer=tracer,
        )

    spool = SpanSpool(str(tmp_path / "spans.jsonl"), "daemon:test")
    d = ServingDaemon(
        frontend_factory, str(tmp_path / "j.jsonl"), clock=clock,
        config=DaemonConfig(fsync_batch=4), span_spool=spool,
    )
    resident = 0
    for i in range(60):
        if i % 10 == 0:
            d.submit(Request(
                prompt=prompts[i % 4], max_new_tokens=6,
                request_id=f"r{i}",
            ))
            d.subscribe(f"r{i}")
        clock.t += 0.01
        d.tick()
        resident = max(resident, len(tracer.spans) + len(tracer.instants))
    assert resident <= 8  # only what is still open waits in the tracer
    assert tracer.dropped == 0
    records = [
        r for r in d.trace_payload()["records"] if r["kind"] == "span"
    ]
    ticks = [r for r in records if r["name"] == "tick"]
    assert len(ticks) == 60
    assert len({r["attrs"]["tick"] for r in ticks}) == 60  # each once
    for name in ("journal", "fsync", "housekeeping", "step", "lock_wait"):
        pump = [r for r in records if r["name"] == f"tick.{name}"]
        assert len(pump) == 60 and {r["track"] for r in pump} == {"daemon"}
    waits = [r for r in records if r["name"] == "lock_wait"]
    assert sorted(r["attrs"]["call"] for r in waits) == (
        ["submit"] * 6 + ["subscribe"] * 6
    )
    assert all("async_id" in r for r in waits)
    spool.close()
