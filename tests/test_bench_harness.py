"""The serve_bench workload-schedule helpers: trace record/replay exchange
format, priority/deadline distribution knobs, daemon journals replayed as
workloads.  (bench.py's own contract — one process, a TPU or a failure — is
pinned in tests/test_chip_compile.py.)
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _serve_bench():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import serve_bench
    finally:
        sys.path.pop(0)
    return serve_bench


def test_parse_dist():
    sb = _serve_bench()
    assert sb.parse_dist("0:6,1:3,2:1") == [
        (0.0, 6.0), (1.0, 3.0), (2.0, 1.0)
    ]
    assert sb.parse_dist("2.0:3,none:1") == [(2.0, 3.0), (None, 1.0)]
    assert sb.parse_dist("5") == [(5.0, 1.0)]  # weight defaults to 1
    for bad in ("", "x:y", "1:-2"):
        with pytest.raises(SystemExit):
            sb.parse_dist(bad)


def test_schedule_dists_deterministic_and_replayable(tmp_path):
    """--priority-dist / --deadline-dist satellite: the shaped schedule
    (a) leaves the arrival stream bit-identical to the unshaped one at
    the same seed (pre-existing records stay comparable), (b) is a pure
    function of (seed, dists), and (c) round-trips through the trace
    record/replay exchange format with every drawn field intact — a
    replayed overload trace exercises priority shedding as recorded."""
    sb = _serve_bench()
    prompts = [[1, 2, 3]] * 40
    groups = [0] * 40
    pdist = sb.parse_dist("0:6,1:3,2:1")
    ddist = sb.parse_dist("2.0:3,none:1")
    plain = sb.build_schedule(prompts, groups, 8.0, 5, 4)
    shaped = sb.build_schedule(
        prompts, groups, 8.0, 5, 4,
        priority_dist=pdist, deadline_dist=ddist,
    )
    assert [e["arrival"] for e in plain] == [e["arrival"] for e in shaped]
    assert all(
        e["priority"] == 0 and e["deadline"] is None for e in plain
    )
    assert {e["priority"] for e in shaped} == {0, 1, 2}
    assert any(e["deadline"] is None for e in shaped)
    assert any(e["deadline"] == 2.0 for e in shaped)
    again = sb.build_schedule(
        prompts, groups, 8.0, 5, 4,
        priority_dist=pdist, deadline_dist=ddist,
    )
    assert shaped == again
    path = str(tmp_path / "trace.jsonl")
    sb.write_trace(
        path, shaped,
        meta={"priority_dist": "0:6,1:3,2:1", "deadline_dist": "2.0:3,none:1"},
    )
    with open(path) as fh:
        header = json.loads(fh.readline())
    assert header["record"] == "trace_meta"
    assert header["priority_dist"] == "0:6,1:3,2:1"
    replayed = sb.load_trace(path)
    assert [
        (e["priority"], e["deadline"], e["prompt"]) for e in replayed
    ] == [
        (e["priority"], e["deadline"], e["prompt"]) for e in shaped
    ]


@pytest.mark.fast
def test_prompt_zipf_deterministic_and_replayable(tmp_path):
    """--prompt-zipf satellite: the Zipf multi-tenant mix (a) leaves the
    arrival stream bit-identical to unshaped schedules at the same seed
    (tenant/suffix draws run on child rngs), (b) is a pure function of
    (seed, S, tenants) with the head tenant genuinely hottest, and (c)
    round-trips through the trace exchange format with the tenant index
    riding ``prefix_group`` — a recorded Zipf workload replays exactly."""

    class _Cfg:
        vocab_size = 97
        seq_len = 64

    sb = _serve_bench()
    with pytest.raises(SystemExit):
        sb.parse_zipf("nope")
    with pytest.raises(SystemExit):
        sb.parse_zipf("0:4")
    assert sb.parse_zipf("1.2:16") == (1.2, 16)
    kw = dict(
        n_requests=60, prompt_min=1, prompt_max=6, prefix_len=8,
        seed=5, zipf_s=1.3, tenants=8,
    )
    p1, g1 = sb.make_zipf_prompts(_Cfg, **kw)
    p2, g2 = sb.make_zipf_prompts(_Cfg, **kw)
    assert p1 == p2 and g1 == g2  # pure function of (seed, shape)
    counts = [g1.count(t) for t in range(8)]
    assert counts[0] == max(counts) and counts[0] > sum(counts) / 8, (
        f"rank-1 tenant not hottest under Zipf: {counts}"
    )
    # same-tenant prompts share their header verbatim
    by_tenant = {}
    for p, g in zip(p1, g1):
        by_tenant.setdefault(g, p[:8])
        assert p[:8] == by_tenant[g]
    # arrivals come from build_schedule's OWN rng: bit-identical to the
    # unshaped workload at the same seed
    plain = sb.build_schedule([[1, 2, 3]] * 60, [0] * 60, 8.0, 5, 4)
    zipf = sb.build_schedule(p1, g1, 8.0, 5, 4)
    assert [e["arrival"] for e in plain] == [e["arrival"] for e in zipf]
    # trace round trip carries prompts AND tenant indices exactly
    path = str(tmp_path / "zipf.jsonl")
    sb.write_trace(path, zipf, meta=dict(prompt_zipf="1.3:8"))
    loaded = sb.load_trace(path)
    assert [e["prompt"] for e in loaded] == [e["prompt"] for e in zipf]
    assert [e["prefix_group"] for e in loaded] == g1


def test_daemon_journal_replays_as_workload(tmp_path):
    """One journal format, not two: serve_bench --trace-replay (alias
    --workload) loads a daemon write-ahead journal directly — submit
    records become the schedule (arrivals rebased to the first submit,
    bookkeeping records skipped, torn tail tolerated), and the loaded
    schedule round-trips through the plain trace format unchanged."""
    from tpu_parallel.daemon import JournalWriter

    sb = _serve_bench()

    class Clk:
        def __init__(self):
            self.t = 100.0

        def __call__(self):
            self.t += 1.0
            return self.t

    path = str(tmp_path / "journal.jsonl")
    w = JournalWriter(path, Clk())
    prompts = [[4, 5, 6], [7, 8], [9, 10, 11, 12]]
    for i, p in enumerate(prompts):
        w.append({
            "record": "submit", "request_id": f"r{i}",
            "dedupe_token": f"tok-{i}", "client_id": "c",
            "arrival": 100.0 + 2.0 * i,
            "prompt": p, "prompt_len": len(p), "prefix_group": 0,
            "priority": i, "deadline": 3.5 if i == 2 else None,
            "max_new_tokens": 8,
        })
        w.append({
            "record": "tokens", "request_id": f"r{i}",
            "index": 0, "tokens": [1, 2],
        })
    w.append({
        "record": "terminal", "request_id": "r0",
        "status": "finished", "finish_reason": "length", "n_tokens": 8,
    })
    w.close()
    with open(path, "a") as fh:
        fh.write('{"record": "tokens", "request_id": "r1", "tok')  # torn

    sched = sb.load_trace(path)
    assert [e["prompt"] for e in sched] == prompts
    assert [e["arrival"] for e in sched] == [0.0, 2.0, 4.0]  # rebased
    assert [e["priority"] for e in sched] == [0, 1, 2]
    assert sched[2]["deadline"] == 3.5
    assert all(e["max_new_tokens"] == 8 for e in sched)
    # time compression behaves exactly like trace replay
    fast = sb.load_trace(path, time_compress=2.0)
    assert [e["arrival"] for e in fast] == [0.0, 1.0, 2.0]
    # round trip through the PLAIN trace format: identical schedule
    trace = str(tmp_path / "trace.jsonl")
    sb.write_trace(trace, sched, meta=dict(source="journal"))
    assert sb.load_trace(trace) == sched
    # the requests build exactly like trace entries
    req = sb._schedule_request(sched[2])
    assert list(req.prompt) == prompts[2]
    assert req.priority == 2 and req.deadline == 3.5


def test_journal_workload_multi_lifetime_rebase_and_corruption(tmp_path):
    """Journal arrival stamps are process-monotonic, NOT comparable
    across restarts: a journal spanning a crash (second life's clock
    restarts near zero) must replay in FILE (= seq) order with monotone
    rebased arrivals — not scrambled by a min-rebase sort.  And garbage
    anywhere but the tail refuses loudly instead of silently replaying
    a smaller workload."""
    import json

    sb = _serve_bench()
    path = str(tmp_path / "journal.jsonl")

    def sub(seq, rid, arrival):
        return {"record": "submit", "seq": seq, "request_id": rid,
                "arrival": arrival, "prompt": [1, 2], "prompt_len": 2,
                "prefix_group": 0, "priority": 0, "deadline": None,
                "max_new_tokens": 4}

    records = [
        {"record": "journal_meta", "journal_version": 1, "seq": 0},
        sub(1, "a", 100.0),
        sub(2, "b", 103.0),
        # kill -9; restart: new process, clock restarts LOW
        {"record": "recovery", "seq": 3, "replayed": 1},
        sub(4, "c", 0.5),
        sub(5, "d", 2.5),
    ]
    with open(path, "w") as fh:
        for rec in records:
            fh.write(json.dumps(rec) + "\n")
    sched = sb.load_trace(path)
    # file order preserved — life 2 does NOT jump ahead of life 1
    assert [e["prompt_len"] for e in sched] == [2, 2, 2, 2]
    assert [e["arrival"] for e in sched] == [0.0, 3.0, 3.0, 5.0]
    arr = [e["arrival"] for e in sched]
    assert arr == sorted(arr)  # monotone across the lifetime seam
    # mid-file garbage: typed refusal, not a silently smaller workload
    lines = open(path).read().splitlines()
    lines.insert(2, '{"record": "submit", "request_id": "x", "arri')
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    with pytest.raises(SystemExit):
        sb.load_trace(path)


def test_journal_workload_rejects_crc_failed_records(tmp_path):
    """Workload replay and recovery share ONE verification helper
    (``journal.record_crc_ok``): a CRC-failed record is rejected by
    ``load_trace`` exactly as ``read_journal`` rejects it — tolerated
    once at the tail, typed refusal anywhere else.  Before this,
    replay trusted any PARSEABLE record and a bit-rotted journal could
    silently replay a workload recovery would never accept."""
    from tpu_parallel.daemon import JournalWriter, read_journal
    from tpu_parallel.daemon.journal import encode_record

    sb = _serve_bench()
    path = str(tmp_path / "journal.jsonl")

    def sub(seq, rid, arrival):
        line, _ = encode_record({
            "record": "submit", "seq": seq, "request_id": rid,
            "arrival": arrival, "prompt": [1, 2], "prompt_len": 2,
            "prefix_group": 0, "priority": 0, "deadline": None,
            "max_new_tokens": 4, "at": 0.0,
        })
        return line

    meta, _ = encode_record(
        {"record": "journal_meta", "journal_version": 2, "seq": 0}
    )
    lines = [meta, sub(1, "a", 1.0), sub(2, "b", 2.0), sub(3, "c", 3.0)]
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    assert len(sb.load_trace(path)) == 3  # clean journal replays whole
    # one corrupted digit in the TAIL record (crc left stale): both
    # surfaces tolerate it as tail damage — the workload just shrinks
    tail_rot = lines[:3] + [
        lines[3].replace('"arrival": 3.0', '"arrival": 9.0')
    ]
    with open(path, "w") as fh:
        fh.write("\n".join(tail_rot) + "\n")
    assert read_journal(path)[1] == 1
    assert [e["arrival"] for e in sb.load_trace(path)] == [0.0, 1.0]
    # the same rot MID-file: both surfaces refuse loudly
    mid_rot = [
        lines[0],
        lines[1].replace('"arrival": 1.0', '"arrival": 9.0'),
        lines[2], lines[3],
    ]
    with open(path, "w") as fh:
        fh.write("\n".join(mid_rot) + "\n")
    with pytest.raises(Exception):
        read_journal(path)
    with pytest.raises(SystemExit):
        sb.load_trace(path)
    # and a REAL writer's journal (crc on every record) replays whole
    real = str(tmp_path / "real.jsonl")
    w = JournalWriter(real, lambda: 0.0)
    w.append({"record": "submit", "request_id": "r", "arrival": 0.0,
              "prompt": [3], "prompt_len": 1, "prefix_group": 0,
              "priority": 0, "deadline": None, "max_new_tokens": 2})
    w.close()
    assert len(sb.load_trace(real)) == 1
