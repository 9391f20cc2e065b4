"""Daemon crash/drain soak: kill -9 the serving process mid-traffic,
restart it, and PROVE the journal-replay contract — and, under
``--disk-faults``, prove the INTEGRITY contract: seeded media
corruption (kill-torn tails, post-fsync bit rot, persistent fsync
``EIO``) is always either typed-detected or bitwise-recomputed — zero
silent wrong tokens, zero lost accepted requests.

Entry modes:

- (default) ``--soak``: the acceptance gate.  For each seeded trial:
  start the daemon as a real subprocess, feed it a seeded request
  schedule over HTTP (every request carries a client dedupe token),
  SIGKILL the process at a seeded point mid-traffic, restart it on the
  SAME journal, retry every submission idempotently (real clients retry
  on connection loss), run the remainder out, and assert:

  1. **zero lost accepted requests** — every journaled submit reaches
     exactly one ``finished`` terminal across the two process lives;
  2. **zero duplicate completions** — each dedupe token maps to exactly
     one journal submit and one terminal (retries after the crash
     dedupe instead of re-admitting);
  3. **bitwise token parity** — every completed stream equals the
     static greedy reference, so the crash+replay (journal prefix +
     forced-prefix recompute) changed NOTHING about the output;
  4. **zero leaked KV reservations** — ``/statez`` shows
     ``inflight_tokens == 0`` and every replica's slots/queues empty
     after quiesce;
  5. **graceful exit** — SIGTERM drains and exits 0 inside the grace
     window, with a clean shutdown record as the journal's last word.

  ``--record FILE`` writes the per-trial evidence.

- ``--disk-faults SEED``: the media-integrity soak.  Per seeded trial:
  (a) life 1 accepts traffic and is SIGKILLed mid-stream; (b) the
  harness flips ONE seeded bit inside the journal's last complete
  record — post-fsync bit rot, the damage the per-record CRC exists
  for; (c) life 2 restarts on the corrupted journal: the CRC-failed
  tail record must be TRUNCATED (typed detection, never silent
  replay), every surviving request recovers and finishes BITWISE
  against the greedy reference, and a request whose submit record was
  the corrupted one re-admits through the idempotent client retry;
  (d) a separate DEGRADED leg starts a child with an injected
  persistent-``EIO``-on-fsync plan
  (``tpu_parallel/daemon/iofaults.py``): after the error threshold
  the daemon must serve 503s with a typed ``degraded`` reason and a
  ``degraded_reason`` on ``/healthz``, finish its accepted in-flight
  work, and STILL drain exit 0 on SIGTERM.
  ``--record FILE`` writes the per-trial evidence.

- ``--smoke``: the fast CI gate (wired into ``scripts/check_all.py``
  and tier-1 via ``tests/test_daemon.py``): one subprocess — start,
  healthz, submit over HTTP, stream to completion, SIGTERM, assert a
  clean drained exit 0 and a clean journal.  No kill -9 (that is the
  soak's job); one model build is the whole cost.  ``--disk-smoke``
  is its integrity sibling (one reduced ``--disk-faults`` trial, no
  degraded leg) — ``check_daemon`` runs both.

- ``--kv-disk SEED``: the SSD-KV-tier acceptance bench
  (record: ``--record``, else ``kv_disk_bench.json``).  Life 1 builds a warm set of long shared
  headers through a tight radix+host hierarchy backed by a disk tier
  (``--kv-disk-dir``), forcing cold host evictions to SPILL block
  payloads to per-block-CRC'd files, then is SIGKILLed.  Three
  restart legs on the same schedule: **warm** (same disk directory —
  the manifest must seed prefix chains, every replayed header must
  hydrate through typed disk restores, zero failures, bitwise tokens,
  and TTFT p95 strictly below the **cold** leg, which restarts on an
  EMPTY disk directory with the identical engine shape) and **rot**
  (one seeded bit flipped in every spilled blob — every planted
  corruption must be typed-detected while the replay recomputes
  bitwise; silent wrong tokens are the only failure).  A fourth leg
  delegates the hit-rate comparison (disk-backed vs RAM-only
  hierarchy at a working set far above ``kv_host_blocks``) to
  ``serve_bench.run_kv_disk_bench``.  ``--kv-disk-smoke`` is the
  reduced warm-restart trial ``check_daemon`` runs.

- ``--serve``: INTERNAL child mode — build the tiny-model fleet, wrap
  it in :class:`~tpu_parallel.daemon.ServingDaemon` + HTTP server,
  write the ready file, install signals, pump until shut down, exit
  with ``daemon.run()``'s code.  ``--io-fsync-eio N`` arms the IO
  fault shim with a persistent fsync-``EIO`` plan starting at fsync
  index N.  ``--kv-disk-dir D`` attaches the radix + host + SSD KV
  hierarchy (one subdirectory per replica).  The parent modes spawn
  this.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# A CPU protocol gate (journal, signals, HTTP/SSE, recovery — tier-1 runs it
# through scripts/check_all.py), not a chip benchmark: the parent computes the
# generate() oracle with JAX AND starts serve children that import JAX, and a
# chip belongs to one process.  So the platform is set EXPLICITLY — here for
# this process before anything imports jax, below for every child — and every
# record is stamped with it.
BACKEND = "cpu"
os.environ["JAX_PLATFORMS"] = BACKEND

DEFAULT_NEW_TOKENS = 8
SOAK_NEW_TOKENS = 20  # long enough that a seeded kill lands mid-stream
READY_TIMEOUT = 300.0  # cold jax import + compile on a 1-core box

# --kv-disk geometry: the soak/crash modes keep the 32-token toy model
# (prefill there is pure dispatch), but the SSD tier's TTFT claim needs
# prefill COMPUTE to save — so its legs run a small-but-real model
# (serve_bench's hierarchy-bench trick) with 3-block shared headers and
# a hierarchy tight enough that the working set can only live on disk.
# d_model=512 puts a 96-token prefill at ~30 ms of CPU compute while a
# 3-blob chain restore is a few ms of IO — the warm/cold gap must be
# compute, not scheduler noise; disk capacity holds every soak chain
# (20 headers + warmup + flushers, 3 blocks each) with headroom so the
# warm leg never loses a chain to disk-tier eviction
KV_DISK_MODEL = dict(d_model=512, n_layers=4, n_heads=4, seq_len=128)
KV_DISK_ENGINE = dict(
    kv_block_tokens=32, kv_pool_blocks=24, prefix_cache_size=4,
    kv_radix_cache=True, kv_host_blocks=4, kv_disk_blocks=160,
)
KV_DISK_HEADER_TOKENS = 96  # 3 full blocks of reusable tenant header
KV_DISK_NEW_TOKENS = 6


# -- HTTP client helpers -----------------------------------------------------


def http_json(method, url, body=None, timeout=120.0):
    """One JSON request; returns (status_code, payload) and never
    raises on HTTP error codes (connection errors DO raise)."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"{}")


def wait_ready(ready_file, proc, timeout=READY_TIMEOUT):
    """Poll for the child's ready file; returns its payload dict."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"daemon child exited rc={proc.returncode} before ready"
            )
        if os.path.exists(ready_file):
            try:
                with open(ready_file) as fh:
                    info = json.load(fh)
                if "port" in info:
                    return info
            except (ValueError, OSError):
                pass  # mid-write
        time.sleep(0.05)
    raise RuntimeError(f"daemon child not ready within {timeout}s")


def spawn_daemon(args, journal, ready_file, extra=()):
    """Start the --serve child with this script's interpreter/env."""
    if os.path.exists(ready_file):
        os.remove(ready_file)
    cmd = [
        sys.executable, os.path.abspath(__file__), "--serve",
        "--journal", journal, "--ready-file", ready_file,
        "--replicas", str(args.replicas), "--slots", str(args.slots),
        "--grace", str(args.grace), "--fsync-batch", str(args.fsync_batch),
        *extra,
    ]
    env = dict(os.environ, JAX_PLATFORMS=BACKEND)
    return subprocess.Popen(cmd, env=env)


# -- schedule + references ---------------------------------------------------


def make_schedule(seed, n_requests, new_tokens):
    """Seeded prompts + dedupe tokens (pure function of seed)."""
    rnd = random.Random(seed)
    schedule = []
    for i in range(n_requests):
        length = rnd.randrange(3, 12)
        prompt = [rnd.randrange(1, 250) for _ in range(length)]
        schedule.append({
            "dedupe_token": f"soak-{seed}-{i}",
            "prompt": prompt,
            "max_new_tokens": new_tokens,
        })
    return schedule


def greedy_references(schedule, cfg_overrides=None):
    """Static-generate greedy continuation for every prompt — the
    parity oracle the daemon's crash+replay output must match.
    ``cfg_overrides`` must mirror what the ``--serve`` child builds
    (the ``--kv-disk`` legs use :data:`KV_DISK_MODEL`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_parallel.models import GPTLM, tiny_test
    from tpu_parallel.models.generate import generate

    cfg = tiny_test(remat=False, **(cfg_overrides or {}))
    model = GPTLM(cfg)
    probe = jnp.zeros((1, 16), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]
    refs = {}
    for entry in schedule:
        prompt = entry["prompt"]
        # generate() returns [batch, max_new_tokens] — continuation only
        cont = np.asarray(generate(
            model, params, jnp.asarray(prompt, jnp.int32)[None, :],
            max_new_tokens=entry["max_new_tokens"],
        ))[0]
        refs[entry["dedupe_token"]] = [int(t) for t in cont]
    return refs


# -- the serve child ---------------------------------------------------------


def serve(args):
    import jax

    from tpu_parallel.runtime import enable_compilation_cache

    enable_compilation_cache()
    from tpu_parallel.cluster import Frontend, FrontendConfig
    from tpu_parallel.daemon import (
        DaemonConfig,
        DaemonHTTPServer,
        ServingDaemon,
    )
    from tpu_parallel.daemon import iofaults

    if args.io_fsync_eio >= 0:
        # the dead-disk shape: every fsync from index N on fails EIO —
        # the child must DEGRADE (typed 503s, /healthz reason), not die
        iofaults.install(iofaults.IOFaultPlan(
            fsync_eio_at=args.io_fsync_eio,
            fsync_eio_count=iofaults.PERSISTENT,
        ))
    from tpu_parallel.models import GPTLM, tiny_test
    from tpu_parallel.obs.registry import MetricRegistry
    from tpu_parallel.serving import SchedulerConfig, ServingEngine

    cfg = tiny_test(
        remat=False, **(KV_DISK_MODEL if args.kv_disk_dir else {})
    )
    model = GPTLM(cfg)
    probe = jax.numpy.zeros((1, 16), jax.numpy.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]

    def frontend_factory(clock):
        engines = []
        for i in range(args.replicas):
            extra_kw = {}
            if args.kv_disk_dir:
                # one store per replica: the manifest journal is a
                # single-writer file, so replicas must not share a root
                extra_kw = dict(
                    KV_DISK_ENGINE,
                    kv_disk_dir=os.path.join(args.kv_disk_dir, f"r{i}"),
                )
            engines.append(ServingEngine(
                model, params, n_slots=args.slots,
                scheduler=SchedulerConfig(max_prefills_per_tick=2),
                **extra_kw,
            ))
        return Frontend(
            engines, router="least",
            config=FrontendConfig(restart=None),
            clock=clock, registry=MetricRegistry(),
        )

    daemon = ServingDaemon(
        frontend_factory, args.journal,
        config=DaemonConfig(
            grace_seconds=args.grace, fsync_batch=args.fsync_batch,
        ),
    )
    server = DaemonHTTPServer(daemon, port=args.port).start()
    daemon.install_signals()
    with open(args.ready_file + ".tmp", "w") as fh:
        json.dump({"port": server.port, "pid": os.getpid()}, fh)
    os.replace(args.ready_file + ".tmp", args.ready_file)
    rc = daemon.run()
    server.stop()
    return rc


# -- invariants --------------------------------------------------------------


def journal_invariants(journal_path, problems):
    """Scan the journal the way recovery does and check the no-loss /
    no-duplicate bookkeeping.  Returns the folded state."""
    from tpu_parallel.daemon import load_state

    state = load_state(journal_path)
    by_token = {}
    for rid in state.order:
        entry = state.entries[rid]
        tok = entry.dedupe_token
        if tok is not None:
            by_token.setdefault(tok, []).append(rid)
    for tok, rids in by_token.items():
        if len(rids) != 1:
            problems.append(
                f"dedupe token {tok} journaled {len(rids)} submits "
                f"({rids}) — duplicate admission"
            )
    for entry in state.unfinished:
        problems.append(
            f"request {entry.request_id} journaled accepted but never "
            "reached a terminal — lost accepted work"
        )
    return state


def state_leak_check(port, problems, label):
    code, payload = http_json(
        "GET", f"http://127.0.0.1:{port}/statez"
    )
    if code != 200:
        problems.append(f"{label}: /statez returned {code}")
        return
    cluster = payload["cluster"]
    if cluster["inflight_tokens"] != 0:
        problems.append(
            f"{label}: leaked token reservations: "
            f"{cluster['inflight_tokens']}"
        )
    for rep in cluster["replicas"]:
        if rep["active_slots"] or rep["queue_depth"]:
            problems.append(
                f"{label}: replica {rep['replica']} not quiesced: "
                f"slots={rep['active_slots']} queue={rep['queue_depth']}"
            )


def stop_gracefully(proc, grace, problems, label):
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=grace + 60)
    except subprocess.TimeoutExpired:
        proc.kill()
        problems.append(f"{label}: SIGTERM did not exit within grace")
        return
    if rc != 0:
        problems.append(f"{label}: drain exit code {rc} != 0")


# -- modes -------------------------------------------------------------------


def run_smoke(tmpdir=None, keep=False):
    """start -> submit -> stream -> SIGTERM drain -> clean exit.  The
    fast gate check_all and tier-1 run.  Returns a problem list."""
    import tempfile

    from tpu_parallel.daemon import REC_SHUTDOWN, read_journal

    problems = []
    tmpdir = tmpdir or tempfile.mkdtemp(prefix="daemon_smoke_")
    journal = os.path.join(tmpdir, "journal.jsonl")
    ready = os.path.join(tmpdir, "ready.json")
    args = argparse.Namespace(
        replicas=1, slots=2, grace=60.0, fsync_batch=8,
    )
    proc = spawn_daemon(args, journal, ready)
    try:
        info = wait_ready(ready, proc)
        port = info["port"]
        code, payload = http_json(
            "GET", f"http://127.0.0.1:{port}/healthz"
        )
        if code != 200 or not payload.get("ok"):
            problems.append(f"healthz {code}: {payload}")
        schedule = make_schedule(seed=7, n_requests=2,
                                 new_tokens=DEFAULT_NEW_TOKENS)
        rids = []
        for entry in schedule:
            code, rec = http_json(
                "POST", f"http://127.0.0.1:{port}/v1/submit", entry
            )
            if code != 200:
                problems.append(f"submit {code}: {rec}")
                continue
            rids.append(rec["request_id"])
        # idempotence: resubmitting the first token dedupes
        code, rec = http_json(
            "POST", f"http://127.0.0.1:{port}/v1/submit", schedule[0]
        )
        if code != 200 or rec["request_id"] != rids[0]:
            problems.append(f"dedupe resubmit mismatched: {code} {rec}")
        deadline = time.monotonic() + 120
        for rid in rids:
            while time.monotonic() < deadline:
                code, rec = http_json(
                    "GET", f"http://127.0.0.1:{port}/v1/result/{rid}"
                )
                if code == 200 and rec["status"] == "finished":
                    if len(rec["tokens"]) != DEFAULT_NEW_TOKENS:
                        problems.append(
                            f"{rid}: {len(rec['tokens'])} tokens != "
                            f"{DEFAULT_NEW_TOKENS}"
                        )
                    break
                time.sleep(0.05)
            else:
                problems.append(f"{rid}: never finished")
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metricsz", timeout=30
        ) as resp:
            metrics_text = resp.read().decode()
        if "daemon_journal_records_total" not in metrics_text:
            problems.append("metricsz missing daemon_* series")
        if rids:
            # SSE replay of a finished stream: N token events + a
            # finished event with the typed reason
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/stream/{rids[0]}"
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                events = [
                    json.loads(line[len(b"data: "):])
                    for line in resp.read().split(b"\n")
                    if line.startswith(b"data: ")
                ]
            toks = [e["token"] for e in events if "token" in e]
            if len(toks) != DEFAULT_NEW_TOKENS or not events[-1].get(
                "finished"
            ):
                problems.append(
                    f"stream replay malformed: {len(toks)} tokens, "
                    f"tail {events[-1] if events else None}"
                )
        state_leak_check(port, problems, "smoke")
        stop_gracefully(proc, args.grace, problems, "smoke")
        records, torn = read_journal(journal)
        if torn:
            problems.append(f"{torn} torn record(s) after a clean exit")
        last = records[-1] if records else {}
        if last.get("record") != REC_SHUTDOWN or not last.get("clean"):
            problems.append(
                f"journal's last word is {last} — expected a clean "
                "shutdown record"
            )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        if not keep and not problems:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def corrupt_tail_record(journal_path, rnd):
    """Flip ONE seeded bit inside the journal's last COMPLETE record —
    the post-fsync bit-rot shape the per-record CRC exists to catch.
    (A SIGKILL may also have left an unterminated fragment after it;
    recovery must truncate both.)  Returns ``(record_kind,
    dedupe_token)`` of the corrupted record so the caller knows which
    damage class it planted (a submit's loss re-admits via client
    retry; a tokens/terminal loss regenerates bitwise)."""
    import json as _json

    with open(journal_path, "rb") as fh:
        data = fh.read()
    end = len(data)
    if not data.endswith(b"\n"):
        end = data.rfind(b"\n") + 1  # skip the torn fragment
    start = data.rfind(b"\n", 0, end - 1) + 1
    line = data[start:end - 1]  # the last complete record's bytes
    try:
        rec = _json.loads(line)
    except ValueError:
        rec = {}
    bit = rnd.randrange(len(line) * 8)
    flipped = bytearray(line)
    flipped[bit // 8] ^= 1 << (bit % 8)
    with open(journal_path, "wb") as fh:
        fh.write(data[:start] + bytes(flipped) + data[end - 1:])
    return rec.get("record", "unparseable"), rec.get("dedupe_token")


def run_disk_trial(args, seed, refs, degraded_leg=True):
    """One seeded disk-fault trial (see the module docstring's
    ``--disk-faults`` contract).  Returns (trial_record, problems)."""
    from tpu_parallel.daemon import load_state, read_journal

    rnd = random.Random(seed ^ 0x10FA)
    problems = []
    tmpdir = os.path.join(
        args.workdir or "/tmp", f"daemon_disk_{os.getpid()}_{seed}"
    )
    os.makedirs(tmpdir, exist_ok=True)
    journal = os.path.join(tmpdir, "journal.jsonl")
    ready = os.path.join(tmpdir, "ready.json")
    if os.path.exists(journal):
        os.remove(journal)
    schedule = make_schedule(seed, args.requests, args.new)

    # ---- life 1: accept traffic, SIGKILL mid-stream
    proc = spawn_daemon(args, journal, ready)
    info = wait_ready(ready, proc)
    port = info["port"]
    kill_after = rnd.randrange(2, max(3, args.requests))
    accepted = {}
    for i, entry in enumerate(schedule):
        try:
            code, rec = http_json(
                "POST", f"http://127.0.0.1:{port}/v1/submit", entry
            )
        except (urllib.error.URLError, OSError):
            break
        if code == 200:
            accepted[entry["dedupe_token"]] = rec["request_id"]
        if i + 1 == kill_after:
            time.sleep(rnd.uniform(0.2, 0.6))  # let tokens stream
            break
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)

    # ---- seeded media corruption: one bit of the last durable record
    kind, corrupted_token = corrupt_tail_record(journal, rnd)
    pre_records = None
    try:
        pre_records, pre_torn = read_journal(journal)
    except Exception as exc:
        # the flip landed in the LAST record, so a typed torn-tail read
        # must still succeed — anything else is a detection bug
        problems.append(
            f"read_journal refused a tail-corrupted journal: {exc!r}"
        )
        pre_torn = -1
    if pre_torn == 0:
        problems.append(
            "planted bit flip was not detected as tail damage "
            f"(corrupted a {kind} record)"
        )

    # ---- life 2: restart on the corrupted journal; idempotent retries
    proc = spawn_daemon(args, journal, ready)
    info = wait_ready(ready, proc)
    port = info["port"]
    # the CRC-failed record must be GONE (truncated), not tolerated
    # forever: the restarted journal parses torn-free end to end
    records, torn = read_journal(journal)
    if torn:
        problems.append(
            f"life2: {torn} damaged record(s) survived the restart "
            "truncation"
        )
    dedupe_hits = 0
    readmitted = 0
    all_rids = {}
    for entry in schedule:
        code, rec = http_json(
            "POST", f"http://127.0.0.1:{port}/v1/submit", entry
        )
        if code != 200:
            problems.append(f"life2 submit rejected {code}: {rec}")
            continue
        tok = entry["dedupe_token"]
        all_rids[tok] = rec["request_id"]
        if tok in accepted:
            if rec["request_id"] == accepted[tok]:
                dedupe_hits += 1
            elif tok == corrupted_token:
                # the corrupted record WAS this submit: its durability
                # was lost with the bit, so the retry legitimately
                # re-admits fresh — the typed, counted fallback
                readmitted += 1
            else:
                problems.append(
                    f"life2: dedupe {tok} re-admitted as "
                    f"{rec['request_id']} != {accepted[tok]} (corrupted "
                    f"record was {kind})"
                )
    deadline = time.monotonic() + 240
    finished = {}
    pending = dict(all_rids)
    while pending and time.monotonic() < deadline:
        for tok, rid in list(pending.items()):
            code, rec = http_json(
                "GET", f"http://127.0.0.1:{port}/v1/result/{rid}"
            )
            if code == 200 and rec["status"] in (
                "finished", "failed", "cancelled", "rejected", "expired",
            ):
                finished[tok] = rec
                del pending[tok]
        time.sleep(0.05)
    for tok, rid in pending.items():
        problems.append(f"{tok} ({rid}): never terminal")
    for tok, rec in finished.items():
        if rec["status"] != "finished":
            problems.append(
                f"{tok}: status {rec['status']} ({rec['finish_reason']})"
                " — lost accepted work"
            )
        elif rec["tokens"] != refs[tok]:
            problems.append(
                f"{tok}: tokens diverge from the greedy reference "
                "through crash + media corruption (SILENT WRONG TOKENS)"
            )
    state_leak_check(port, problems, f"disk{seed}")
    stop_gracefully(proc, args.grace, problems, f"disk{seed}")
    state = journal_invariants(journal, problems)
    trial = {
        "seed": seed,
        "kill_after": kill_after,
        "corrupted_record": kind,
        "corrupted_submit_readmitted": readmitted,
        "dedupe_hits_on_retry": dedupe_hits,
        "recoveries": state.recoveries,
        "finished": sum(
            1 for r in finished.values() if r["status"] == "finished"
        ),
        "requests": args.requests,
    }

    # ---- degraded leg: persistent fsync EIO -> typed 503s, clean drain
    if degraded_leg:
        dj = os.path.join(tmpdir, "degraded.jsonl")
        if os.path.exists(dj):
            os.remove(dj)
        proc = spawn_daemon(
            args, dj, ready, extra=("--io-fsync-eio", "3")
        )
        info = wait_ready(ready, proc)
        port = info["port"]
        deg_accepted = []
        saw_degraded = False
        for i, entry in enumerate(make_schedule(
            seed ^ 0xDE6, args.requests, args.new
        )):
            code, rec = http_json(
                "POST", f"http://127.0.0.1:{port}/v1/submit", entry
            )
            if code == 200:
                deg_accepted.append(rec["request_id"])
            elif code == 503 and rec.get("finish_reason") in (
                "degraded", "journal_error"
            ):
                if rec.get("finish_reason") == "degraded":
                    saw_degraded = True
            else:
                problems.append(
                    f"degraded leg: submit {i} -> {code} {rec} (want "
                    "200 or typed 503)"
                )
            time.sleep(0.05)
        deadline = time.monotonic() + 60
        reason = None
        while time.monotonic() < deadline:
            code, health = http_json(
                "GET", f"http://127.0.0.1:{port}/healthz"
            )
            reason = health.get("degraded_reason")
            if code == 503 and reason:
                break
            time.sleep(0.1)
        if not reason:
            problems.append(
                "degraded leg: /healthz never exposed degraded_reason "
                "under persistent fsync EIO"
            )
        if not saw_degraded:
            problems.append(
                "degraded leg: no submission was refused with the "
                "typed 'degraded' reason"
            )
        # accepted-before-degrade work still finishes (drains), and
        # SIGTERM still exits 0 while degraded
        deadline = time.monotonic() + 120
        for rid in deg_accepted:
            while time.monotonic() < deadline:
                code, rec = http_json(
                    "GET", f"http://127.0.0.1:{port}/v1/result/{rid}"
                )
                if code == 200 and rec["status"] == "finished":
                    break
                time.sleep(0.05)
            else:
                problems.append(
                    f"degraded leg: accepted {rid} never finished "
                    "draining"
                )
        stop_gracefully(
            proc, args.grace, problems, f"degraded{seed}"
        )
        trial["degraded"] = {
            "accepted_before_degrade": len(deg_accepted),
            "degraded_reason": reason,
            "typed_degraded_rejects": saw_degraded,
        }
        # the degraded journal is NOT required to be clean (its disk
        # was dying) — but it must never brick: a fresh scan tolerates
        # at most tail damage
        try:
            load_state(dj)
        except Exception as exc:
            problems.append(
                f"degraded leg: journal bricked after EIO storm: "
                f"{exc!r}"
            )
    if not problems:
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)
    return trial, problems


def run_disk_soak(args):
    """The seeded media-corruption acceptance soak (>= 3 seeds)."""
    record = {"bench": "daemon_disk_faults", "backend": BACKEND, "trials": []}
    problems = []
    refs_cache = {}
    for trial in range(args.trials):
        seed = args.disk_faults + trial
        schedule = make_schedule(seed, args.requests, args.new)
        if seed not in refs_cache:
            refs_cache[seed] = greedy_references(schedule)
        trial_rec, trial_problems = run_disk_trial(
            args, seed, refs_cache[seed]
        )
        trial_rec["problems"] = list(trial_problems)
        record["trials"].append(trial_rec)
        problems.extend(trial_problems)
        print(
            f"disk trial {trial} (seed {seed}): "
            f"corrupted={trial_rec['corrupted_record']} "
            f"dedupe_hits={trial_rec['dedupe_hits_on_retry']} "
            f"finished={trial_rec['finished']}/{args.requests} "
            f"degraded_reason="
            f"{trial_rec.get('degraded', {}).get('degraded_reason')} "
            f"problems={len(trial_problems)}"
        )
    record["ok"] = not problems
    if args.record:
        with open(args.record, "w") as fh:
            json.dump(record, fh, indent=2)
            fh.write("\n")
        print(f"record: {args.record}")
    return problems


def run_disk_smoke():
    """One reduced disk-fault trial (no degraded leg): the integrity
    half of the ``check_daemon`` runtime gate."""
    args = argparse.Namespace(
        replicas=1, slots=2, grace=60.0, fsync_batch=4,
        requests=3, new=8, workdir="", record="",
    )
    seed = 5
    schedule = make_schedule(seed, args.requests, args.new)
    refs = greedy_references(schedule)
    _, problems = run_disk_trial(args, seed, refs, degraded_leg=False)
    return problems


# -- SSD KV tier legs (--kv-disk) --------------------------------------------


def make_kv_disk_schedule(seed, n_headers, life,
                          new_tokens=KV_DISK_NEW_TOKENS):
    """Seeded long-header replay schedule.  Prompts are a pure function
    of ``(seed, i)`` — identical across process lives — while the
    dedupe token carries the ``life`` tag, so a restarted daemon
    re-admits the replay as FRESH work (restore or recompute, never a
    journal dedupe hit that would hide the KV path entirely)."""
    rnd = random.Random(seed ^ 0x55D)
    schedule = []
    for i in range(n_headers):
        header = [
            rnd.randrange(1, 250) for _ in range(KV_DISK_HEADER_TOKENS)
        ]
        suffix = [rnd.randrange(1, 250) for _ in range(2)]
        schedule.append({
            "dedupe_token": f"kvd-{seed}-{i}-{life}",
            "prompt": header + suffix,
            "max_new_tokens": new_tokens,
        })
    return schedule


def kv_disk_references(seed, n_headers):
    """Greedy reference continuations indexed by header number (the
    prompts are life-invariant, so one oracle serves every leg)."""
    sched = make_kv_disk_schedule(seed, n_headers, "ref")
    refs = greedy_references(sched, cfg_overrides=KV_DISK_MODEL)
    return [refs[entry["dedupe_token"]] for entry in sched]


def timed_submit(port, entry):
    """Submit one request and ride its LIVE SSE stream to the end:
    returns ``(ttft_seconds, tokens, status)`` where TTFT is measured
    from just before the submit POST to the first streamed token — the
    client-observed latency the warm/cold legs compare.  The stream is
    drained to the terminal event on purpose: hanging up mid-stream
    would CANCEL the request."""
    t0 = time.monotonic()
    code, rec = http_json(
        "POST", f"http://127.0.0.1:{port}/v1/submit", entry
    )
    if code != 200:
        raise RuntimeError(f"submit {code}: {rec}")
    rid = rec["request_id"]
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/stream/{rid}"
    )
    ttft, tokens = None, []
    with urllib.request.urlopen(req, timeout=180) as resp:
        for raw in resp:
            if not raw.startswith(b"data: "):
                continue
            ev = json.loads(raw[len(b"data: "):])
            if "token" in ev:
                if ttft is None:
                    ttft = time.monotonic() - t0
                tokens.append(ev["token"])
            if ev.get("finished"):
                return ttft, tokens, ev.get("status")
    raise RuntimeError(f"stream for {rid} closed before the terminal")


def healthz_kv(port):
    code, payload = http_json("GET", f"http://127.0.0.1:{port}/healthz")
    return (payload.get("kv") or {}) if isinstance(payload, dict) else {}


def p95(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(0.95 * (len(xs) - 1))))]


def corrupt_blob_files(disk_root, rnd):
    """Flip one seeded bit inside the payload region of EVERY spilled
    ``.kvw`` blob under ``disk_root`` — post-fsync SSD rot.  The frame
    CRC + manifest cross-check must type every one; returns the count
    planted."""
    flipped = 0
    for root, _, names in os.walk(disk_root):
        for name in sorted(names):
            if not name.endswith(".kvw"):
                continue
            path = os.path.join(root, name)
            with open(path, "rb") as fh:
                data = bytearray(fh.read())
            if len(data) < 8:
                continue
            pos = rnd.randrange(len(data) // 4, 3 * len(data) // 4)
            data[pos] ^= 1 << rnd.randrange(8)
            with open(path, "wb") as fh:
                fh.write(bytes(data))
            flipped += 1
    return flipped


def run_kv_disk_trial(args, seed, refs, *, timing=True, rot_leg=True):
    """One SSD-tier restart trial (see the module docstring's
    ``--kv-disk`` contract).  Returns ``(trial_record, problems)``."""
    import shutil

    n_headers = len(refs)
    problems = []
    tmpdir = os.path.join(
        args.workdir or "/tmp", f"daemon_kvdisk_{os.getpid()}_{seed}"
    )
    if os.path.exists(tmpdir):
        shutil.rmtree(tmpdir)
    os.makedirs(tmpdir)
    journal = os.path.join(tmpdir, "journal.jsonl")
    ready = os.path.join(tmpdir, "ready.json")
    warm_disk = os.path.join(tmpdir, "disk")
    warm_extra = ("--kv-disk-dir", warm_disk)

    def replay(port, life):
        # compile warm-up OUTSIDE the timed window, both paths: the
        # first dummy submit compiles the full-length prefill bucket
        # (the cold path), the immediate second submit HITS the
        # still-resident chain and compiles the short-tail
        # prefix-hit prefill (the warm path) — so no timed request in
        # either leg pays jit, and the legs compare compute, not
        # compilation
        for rep in range(2):
            timed_submit(port, {
                "dedupe_token": f"kvd-{seed}-warmup-{life}-{rep}",
                "prompt": [3] * (KV_DISK_HEADER_TOKENS + 2),
                "max_new_tokens": KV_DISK_NEW_TOKENS,
            })
        ttfts = []
        for i, entry in enumerate(
            make_kv_disk_schedule(seed, n_headers, life)
        ):
            ttft, tokens, status = timed_submit(port, entry)
            if status != "finished":
                problems.append(f"{life}: header {i} status {status}")
            elif tokens != refs[i]:
                problems.append(
                    f"{life}: header {i} tokens diverge from the "
                    "greedy reference (SILENT WRONG TOKENS)"
                )
            ttfts.append(ttft)
        return ttfts

    # ---- life 1: build the warm set through the spill path, kill -9.
    # Each header is submitted TWICE back to back: the second submission
    # hits the still-resident chain, which is what marks its blocks WARM
    # — only evicted-but-warm blocks spill (a cold one-off drops
    # outright), so without the double-take nothing would ever reach
    # disk.  Then a train of warm FLUSHER prompts (disjoint token space)
    # cycles the device and host tiers, pushing every header block
    # through the cold-host-eviction path — whose prefix-closure spill
    # persists each header's whole chain — before the kill lands.
    proc = spawn_daemon(args, journal, ready, extra=warm_extra)
    info = wait_ready(ready, proc)
    port = info["port"]
    # the warmup header is submitted twice so its blocks go WARM and
    # ride the flusher cascade to disk with everything else — the warm
    # leg's (untimed) warmup submits then exercise the disk-restore
    # machinery's first-use costs OUTSIDE the timed window, exactly as
    # they pre-pay compile for the prefill buckets
    for rep in range(2):
        timed_submit(port, {
            "dedupe_token": f"kvd-{seed}-warmup-a-{rep}",
            "prompt": [3] * (KV_DISK_HEADER_TOKENS + 2),
            "max_new_tokens": KV_DISK_NEW_TOKENS,
        })
    build = [
        make_kv_disk_schedule(seed, n_headers, life)
        for life in ("a0", "a1")
    ]
    for i in range(n_headers):
        for sched in build:  # back to back: the second take must HIT
            _, tokens, status = timed_submit(port, sched[i])
            if status != "finished":
                problems.append(f"life1: header {i} status {status}")
            elif tokens != refs[i]:
                problems.append(
                    f"life1: header {i} tokens diverge from the greedy "
                    "reference"
                )
    frnd = random.Random(seed ^ 0xF1)
    for i in range(4):
        flusher = [250] + [
            frnd.randrange(1, 250)
            for _ in range(KV_DISK_HEADER_TOKENS + 1)
        ]
        for rep in range(2):
            timed_submit(port, {
                "dedupe_token": f"kvd-{seed}-flush-{i}-{rep}",
                "prompt": flusher,
                "max_new_tokens": KV_DISK_NEW_TOKENS,
            })
    kv_life1 = healthz_kv(port)
    if kv_life1.get("disk_blocks_used", 0) < n_headers:
        problems.append(
            f"life1: {kv_life1.get('disk_blocks_used', 0)} disk blocks "
            f"< {n_headers} headers — the warm set never reached the "
            f"disk tier (healthz kv: {kv_life1})"
        )
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)

    if rot_leg:
        # snapshot the on-disk tier BEFORE the warm leg mutates it
        rot_disk = os.path.join(tmpdir, "disk_rot")
        shutil.copytree(warm_disk, rot_disk)

    # ---- warm leg: restart on the SAME journal + SAME disk directory
    proc = spawn_daemon(args, journal, ready, extra=warm_extra)
    info = wait_ready(ready, proc)
    port = info["port"]
    kv_seeded = healthz_kv(port)
    if not kv_seeded.get("disk_seeded_chains"):
        problems.append(
            "warm: restart seeded no prefix chains from the manifest "
            f"(healthz kv: {kv_seeded})"
        )
    warm_ttfts = replay(port, "w")
    kv_warm = healthz_kv(port)
    if kv_warm.get("disk_restores", 0) < n_headers:
        problems.append(
            f"warm: {kv_warm.get('disk_restores', 0)} disk restores < "
            f"{n_headers} replayed warm chains — warm hits recomputed"
        )
    if kv_warm.get("disk_restore_failures", 0):
        problems.append(
            f"warm: {kv_warm['disk_restore_failures']} restore "
            "failures on an uncorrupted disk"
        )
    stop_gracefully(proc, args.grace, problems, f"kvdisk-warm{seed}")

    trial = {
        "seed": seed,
        "headers": n_headers,
        "header_tokens": KV_DISK_HEADER_TOKENS,
        "engine": dict(KV_DISK_ENGINE),
        "life1_kv": kv_life1,
        "warm": {
            "kv": kv_warm,
            "seeded_chains": kv_seeded.get("disk_seeded_chains", 0),
            "ttft_ms": [round(t * 1000, 2) for t in warm_ttfts],
        },
    }

    # ---- cold leg: identical engine shape, EMPTY disk directory —
    # the restart-TTFT baseline the warm leg must beat
    if timing:
        cold_journal = os.path.join(tmpdir, "journal_cold.jsonl")
        cold_disk = os.path.join(tmpdir, "disk_cold")
        proc = spawn_daemon(
            args, cold_journal, ready,
            extra=("--kv-disk-dir", cold_disk),
        )
        info = wait_ready(ready, proc)
        port = info["port"]
        cold_ttfts = replay(port, "c")
        stop_gracefully(
            proc, args.grace, problems, f"kvdisk-cold{seed}"
        )
        warm_p95, cold_p95 = p95(warm_ttfts), p95(cold_ttfts)
        if warm_p95 >= cold_p95:
            problems.append(
                f"warm-restart TTFT p95 {warm_p95 * 1000:.1f}ms is not "
                f"below the cold restart's {cold_p95 * 1000:.1f}ms"
            )
        trial["warm"]["ttft_ms_p95"] = round(warm_p95 * 1000, 2)
        trial["cold"] = {
            "ttft_ms": [round(t * 1000, 2) for t in cold_ttfts],
            "ttft_ms_p95": round(cold_p95 * 1000, 2),
        }

    # ---- rot leg: one seeded bit in every spilled blob; every planted
    # corruption must surface as a TYPED restore failure while the
    # replay recomputes bitwise — never as served wrong tokens
    if rot_leg:
        rnd = random.Random(seed ^ 0xB07)
        n_flipped = corrupt_blob_files(rot_disk, rnd)
        rot_journal = os.path.join(tmpdir, "journal_rot.jsonl")
        proc = spawn_daemon(
            args, rot_journal, ready, extra=("--kv-disk-dir", rot_disk),
        )
        info = wait_ready(ready, proc)
        port = info["port"]
        replay(port, "r")
        kv_rot = healthz_kv(port)
        if n_flipped and not kv_rot.get("disk_restore_failures"):
            problems.append(
                f"rot: {n_flipped} planted blob corruptions, none "
                "typed-detected"
            )
        stop_gracefully(proc, args.grace, problems, f"kvdisk-rot{seed}")
        trial["rot"] = {"flipped_blobs": n_flipped, "kv": kv_rot}

    if not problems:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return trial, problems


def run_kv_disk_soak(args):
    """The SSD-tier acceptance bench: restart-TTFT warm vs cold on the
    same disk, seeded blob rot, plus serve_bench's disk-vs-RAM-only
    hit-rate leg — one record."""
    import importlib.util
    import types

    record = {"bench": "kv_disk", "backend": BACKEND, "trials": []}
    problems = []
    # 20 timed samples per leg: p95 is the second-worst sample, so one
    # scheduler hiccup cannot decide the warm-vs-cold verdict
    n_headers = 20
    for trial in range(args.trials):
        seed = args.kv_disk + trial
        refs = kv_disk_references(seed, n_headers)
        trial_rec, trial_problems = run_kv_disk_trial(args, seed, refs)
        trial_rec["problems"] = list(trial_problems)
        record["trials"].append(trial_rec)
        problems.extend(trial_problems)
        print(
            f"kv-disk trial {trial} (seed {seed}): "
            f"seeded_chains={trial_rec['warm']['seeded_chains']} "
            f"warm_p95={trial_rec['warm'].get('ttft_ms_p95')}ms "
            f"cold_p95={trial_rec.get('cold', {}).get('ttft_ms_p95')}ms "
            f"rot_flipped={trial_rec.get('rot', {}).get('flipped_blobs')} "
            f"problems={len(trial_problems)}"
        )

    # ---- hit-rate leg: in-process engines, disk-backed hierarchy vs
    # RAM-only at a working set far above kv_host_blocks (serve_bench
    # owns the workload; loaded by path, same trick as check_daemon)
    spec = importlib.util.spec_from_file_location(
        "serve_bench",
        os.path.join(REPO_ROOT, "scripts", "serve_bench.py"),
    )
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    hit_rec, hit_violations = sb.run_kv_disk_bench(
        None, None, None, seed=args.kv_disk,
        logger=types.SimpleNamespace(log_record=lambda rec: None),
    )
    record["hit_rate_leg"] = hit_rec
    problems.extend(f"hit-rate leg: {v}" for v in hit_violations)

    record["ok"] = not problems
    out = args.record or os.path.join(REPO_ROOT, "kv_disk_bench.json")
    with open(out, "w") as fh:
        json.dump(record, fh, indent=2)
        fh.write("\n")
    print(f"record: {out}")
    return problems


def run_kv_disk_smoke():
    """One reduced warm-restart trial — no TTFT gate (CI boxes are too
    noisy for a latency comparison), no rot leg: spill, kill -9,
    manifest warm-start, typed restores, bitwise replay.  The SSD-tier
    third of the ``check_daemon`` runtime gate."""
    args = argparse.Namespace(
        replicas=1, slots=2, grace=60.0, fsync_batch=4, workdir="",
    )
    seed = 11
    refs = kv_disk_references(seed, n_headers=5)
    _, problems = run_kv_disk_trial(
        args, seed, refs, timing=False, rot_leg=False,
    )
    return problems


def run_soak(args):
    """The seeded kill-9 / restart / drain acceptance soak."""
    from tpu_parallel.daemon import load_state

    record = {"bench": "daemon_soak", "backend": BACKEND, "trials": []}
    problems = []
    refs_cache = {}
    for trial in range(args.trials):
        seed = args.seed + trial
        rnd = random.Random(seed ^ 0xD43)
        tmpdir = os.path.join(
            args.workdir or "/tmp", f"daemon_soak_{os.getpid()}_{seed}"
        )
        os.makedirs(tmpdir, exist_ok=True)
        journal = os.path.join(tmpdir, "journal.jsonl")
        ready = os.path.join(tmpdir, "ready.json")
        if os.path.exists(journal):
            os.remove(journal)
        schedule = make_schedule(seed, args.requests, args.new)
        if seed not in refs_cache:
            refs_cache[seed] = greedy_references(schedule)
        refs = refs_cache[seed]
        trial_problems = []

        # ---- life 1: accept traffic, SIGKILL at a seeded point
        proc = spawn_daemon(args, journal, ready)
        info = wait_ready(ready, proc)
        port = info["port"]
        kill_after = rnd.randrange(2, max(3, args.requests - 2))
        accepted = {}
        killed = False
        for i, entry in enumerate(schedule):
            try:
                code, rec = http_json(
                    "POST", f"http://127.0.0.1:{port}/v1/submit", entry
                )
            except (urllib.error.URLError, OSError):
                break  # the daemon is gone (we killed it)
            if code == 200:
                accepted[entry["dedupe_token"]] = rec["request_id"]
            else:
                trial_problems.append(
                    f"life1 submit {i} rejected {code}: {rec}"
                )
            if i + 1 == kill_after:
                # let some tokens stream so the kill lands mid-request
                time.sleep(rnd.uniform(0.2, 0.6))
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=30)
                killed = True
                break
        if not killed:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        durable = load_state(journal)
        life1 = {
            "accepted": len(accepted),
            "kill_after": kill_after,
            "durable_submits": len(durable.order),
            "durable_unfinished": len(durable.unfinished),
            "torn_records": durable.torn_records,
        }
        if len(durable.order) < len(accepted):
            trial_problems.append(
                f"life1: {len(accepted)} accepts acknowledged but only "
                f"{len(durable.order)} journaled — the WAL lied"
            )

        # ---- life 2: restart on the same journal, idempotent retries
        proc = spawn_daemon(args, journal, ready)
        info = wait_ready(ready, proc)
        port = info["port"]
        dedupe_hits = 0
        all_rids = {}
        for entry in schedule:
            code, rec = http_json(
                "POST", f"http://127.0.0.1:{port}/v1/submit", entry
            )
            if code != 200:
                trial_problems.append(
                    f"life2 submit rejected {code}: {rec}"
                )
                continue
            tok = entry["dedupe_token"]
            all_rids[tok] = rec["request_id"]
            if tok in accepted:
                if rec["request_id"] != accepted[tok]:
                    trial_problems.append(
                        f"life2: dedupe {tok} re-admitted as "
                        f"{rec['request_id']} != {accepted[tok]}"
                    )
                else:
                    dedupe_hits += 1
        deadline = time.monotonic() + 240
        finished = {}
        pending = dict(all_rids)
        while pending and time.monotonic() < deadline:
            for tok, rid in list(pending.items()):
                code, rec = http_json(
                    "GET", f"http://127.0.0.1:{port}/v1/result/{rid}"
                )
                if code == 200 and rec["status"] in (
                    "finished", "failed", "cancelled", "rejected",
                    "expired",
                ):
                    finished[tok] = rec
                    del pending[tok]
            time.sleep(0.05)
        for tok, rid in pending.items():
            trial_problems.append(f"{tok} ({rid}): never terminal")

        # ---- invariants
        for tok, rec in finished.items():
            if rec["status"] != "finished":
                trial_problems.append(
                    f"{tok}: status {rec['status']} "
                    f"({rec['finish_reason']}) — lost accepted work"
                )
                continue
            if rec["tokens"] != refs[tok]:
                trial_problems.append(
                    f"{tok}: tokens diverge from the greedy reference "
                    "through crash+replay"
                )
        state_leak_check(port, trial_problems, f"trial{trial}")
        stop_gracefully(
            proc, args.grace, trial_problems, f"trial{trial}"
        )
        state = journal_invariants(journal, trial_problems)
        trial_rec = {
            "seed": seed,
            "life1": life1,
            "dedupe_hits_on_retry": dedupe_hits,
            "recoveries": state.recoveries,
            "journal_records": state.next_seq,
            "finished": sum(
                1 for r in finished.values()
                if r["status"] == "finished"
            ),
            "requests": args.requests,
            "problems": list(trial_problems),
        }
        record["trials"].append(trial_rec)
        problems.extend(trial_problems)
        if not trial_problems:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
        print(
            f"trial {trial} (seed {seed}): accepted={len(accepted)} "
            f"kill_after={kill_after} dedupe_hits={dedupe_hits} "
            f"finished={trial_rec['finished']}/{args.requests} "
            f"problems={len(trial_problems)}"
        )
    caught = sum(
        t["life1"]["durable_unfinished"] for t in record["trials"]
    )
    if caught == 0:
        problems.append(
            "no trial caught accepted-but-unfinished work at the kill "
            "point — the soak proved nothing about recovery; lengthen "
            "--new or add trials"
        )
    record["unfinished_at_kill_total"] = caught
    record["ok"] = not problems
    if args.record:
        with open(args.record, "w") as fh:
            json.dump(record, fh, indent=2)
            fh.write("\n")
        print(f"record: {args.record}")
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--serve", action="store_true",
                    help="INTERNAL: run the daemon child process")
    ap.add_argument("--smoke", action="store_true",
                    help="fast gate: start, submit, SIGTERM drain, "
                         "assert clean exit (no kill -9)")
    ap.add_argument("--disk-smoke", action="store_true",
                    help="fast integrity gate: one reduced disk-fault "
                         "trial (kill + seeded tail bit flip + bitwise "
                         "recovery), no degraded leg")
    ap.add_argument("--disk-faults", type=int, default=None,
                    metavar="SEED",
                    help="seeded media-corruption soak: kill-torn "
                         "tails, one-bit journal rot, persistent "
                         "fsync-EIO degraded mode — trials use seeds "
                         "SEED..SEED+trials-1")
    ap.add_argument("--kv-disk", type=int, default=None, metavar="SEED",
                    help="SSD-KV-tier acceptance bench: warm vs cold "
                         "restart TTFT on the same disk, seeded blob "
                         "rot, and the serve_bench hit-rate leg; "
                         "writes kv_disk_bench.json by default")
    ap.add_argument("--kv-disk-smoke", action="store_true",
                    help="fast SSD-tier gate: one reduced warm-restart "
                         "trial (spill, kill -9, manifest warm-start, "
                         "typed restores, bitwise replay)")
    ap.add_argument("--kv-disk-dir", type=str, default="",
                    help="INTERNAL (--serve): attach the radix + host "
                         "+ SSD KV hierarchy, one subdirectory per "
                         "replica")
    ap.add_argument("--io-fsync-eio", type=int, default=-1,
                    help="INTERNAL (--serve): arm the IO fault shim "
                         "with persistent fsync EIO from this fsync "
                         "index on")
    ap.add_argument("--soak", action="store_true",
                    help="seeded kill-9/restart soak (the default)")
    ap.add_argument("--journal", type=str, default="")
    ap.add_argument("--ready-file", type=str, default="")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--grace", type=float, default=60.0)
    ap.add_argument("--fsync-batch", type=int, default=8)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--new", type=int, default=SOAK_NEW_TOKENS)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", type=str, default="")
    ap.add_argument("--record", type=str, default="")
    args = ap.parse_args()

    if args.serve:
        if not args.journal or not args.ready_file:
            ap.error("--serve needs --journal and --ready-file")
        sys.exit(serve(args))
    if args.smoke:
        problems = run_smoke()
    elif args.disk_smoke:
        problems = run_disk_smoke()
    elif args.kv_disk_smoke:
        problems = run_kv_disk_smoke()
    elif args.kv_disk is not None:
        problems = run_kv_disk_soak(args)
    elif args.disk_faults is not None:
        problems = run_disk_soak(args)
    else:
        problems = run_soak(args)
    for problem in problems:
        print(problem, file=sys.stderr)
    if problems:
        print(
            f"daemon_bench: {len(problems)} INVARIANT VIOLATION(S)",
            file=sys.stderr,
        )
        sys.exit(1)
    print("daemon_bench: OK")


if __name__ == "__main__":
    main()
