"""Fleet soak: one wire-level router over N daemon PROCESSES, under
seeded host kills — the cross-host acceptance gate (docs/14_fleet.md).

``daemon_bench`` proves one process survives its own death through the
journal.  This bench proves the FLEET survives a host's death through
the router: clients talk only to the router (the daemon's exact
HTTP/SSE contract re-served by ``tpu_parallel/fleet/http.py``), daemons
are killed -9 at seeded points mid-traffic, and the invariants are
judged fleet-wide:

1. **zero lost accepted requests** — every submission the router
   acknowledged reaches exactly one ``finished`` terminal, even when
   its backing daemon was SIGKILLed mid-stream (cross-host handoff:
   prompt + delivered tokens replayed onto a survivor as a forced
   prefix);
2. **zero duplicate completions** — the router's dedupe ledger answers
   client retries with the original request id across host deaths
   (the dead host's journal is unreachable; the ledger is the
   fleet-wide authority);
3. **bitwise token parity** — every completed stream, including every
   handed-off one, equals the static greedy reference: the host death
   changed NOTHING about the output;
4. **remote KV migration lands** — a killed daemon restarted on its
   port is warm-started by the router from a healthy donor over the
   ``kv_wire`` codec, with at least one typed ``imported`` verdict;
   and the corrupt-injection leg (one seeded bit flipped in an
   exported wire blob) is refused TYPED by the importer — corrupt
   bytes never land, recompute covers the miss;
5. **graceful exits** — SIGTERM drains the router and every daemon to
   exit 0.

Entry modes:

- ``--smoke``: the fast CI gate (``scripts/check_fleet.py`` and tier-1
  via ``tests/test_fleet.py``): router + 2 daemons on loopback ports,
  one SIGKILL mid-stream, one recovery warm start, one corrupt-import
  refusal, and a disagg leg (a second, role-pinned router over the
  same daemons: bitwise prefill->decode handoff, then a dead decode
  peer resolving as a typed fallback).  Bounded wall time; one model
  build in the parent (the greedy reference) plus one per child.
- ``--soak SEED``: the acceptance soak — per seeded trial: router + 3
  daemons, a seeded request schedule, a seeded victim SIGKILLed at a
  seeded point, full invariant sweep, restart + warm start, corrupt
  leg, graceful stop.  ``--record FILE`` writes the
  per-trial evidence.
- ``--disagg SEED``: the disaggregation bench — 1-prefill/2-decode vs
  3-mixed at equal hardware on one seeded schedule (a long-prefill
  burst contending with decode-heavy probes); records decode ITL p95,
  TTFT, and handoff bytes/latency per leg into ``fleet_disagg_bench.json``,
  failing on any lost/duplicated/non-bitwise stream.
- ``--serve``: INTERNAL daemon child — the ``daemon_bench`` child with
  radix-cached engines (``kv_block_tokens=4`` + ``kv_radix_cache``) so
  peer KV export/import has chains to ship.
- ``--route``: INTERNAL router child — a :class:`FleetRouter` on the
  WallClock + urllib transport, its probe pump on the main thread,
  SIGTERM -> stop -> exit 0.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
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
# long enough that a seeded kill lands mid-stream, while prompt +
# budget stays inside the tiny_test model's seq_len of 32
HANDOFF_NEW_TOKENS = 20
READY_TIMEOUT = 300.0  # cold jax import + compile on a 1-core box
BLOCK_TOKENS = 4  # the children's paged-KV block size
TERMINAL = ("finished", "failed", "cancelled", "rejected", "expired")


# -- small plumbing ----------------------------------------------------------


def pick_ports(n):
    """Reserve n distinct loopback ports (portpicker when available,
    else bind-to-0 probing — daemons need FIXED ports so a restarted
    victim comes back at the address the router knows it by)."""
    try:
        import portpicker

        return [portpicker.pick_unused_port() for _ in range(n)]
    except ImportError:
        socks, ports = [], []
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            socks.append(s)
        for s in socks:
            s.close()
        return ports


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


def http_bytes(method, url, data=None, timeout=120.0):
    """Binary-bodied sibling: returns (status_code, raw_bytes)."""
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/octet-stream")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def wait_ready(ready_file, proc, timeout=READY_TIMEOUT):
    """Poll for a child's ready file; returns its payload dict."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"child exited rc={proc.returncode} before ready"
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
    raise RuntimeError(f"child not ready within {timeout}s")


class Peer:
    """One daemon child the parent manages: fixed port, its journal,
    its ready file, and the live Popen handle (replaced on restart)."""

    def __init__(self, tmpdir, name, port, role="mixed", tick_sleep=0.0,
                 trace_log=None):
        self.name = name
        self.port = port
        self.role = role
        self.tick_sleep = tick_sleep
        self.addr = f"127.0.0.1:{port}"
        self.journal = os.path.join(tmpdir, f"{name}.jsonl")
        self.ready = os.path.join(tmpdir, f"{name}.ready.json")
        self.trace_log = trace_log
        self.proc = None
        self.pid = None

    def spawn(self, grace=60.0):
        if os.path.exists(self.ready):
            os.remove(self.ready)
        cmd = [
            sys.executable, os.path.abspath(__file__), "--serve",
            "--journal", self.journal, "--ready-file", self.ready,
            "--port", str(self.port), "--grace", str(grace),
            "--role", self.role, "--tick-sleep", str(self.tick_sleep),
        ]
        if self.trace_log:
            cmd += ["--trace-log", self.trace_log]
        env = dict(os.environ, JAX_PLATFORMS=BACKEND)
        self.proc = subprocess.Popen(cmd, env=env)
        return self

    def wait_ready(self):
        info = wait_ready(self.ready, self.proc)
        self.pid = info["pid"]
        return info

    def sigkill(self):
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)


def spawn_router(tmpdir, peer_addrs, warm_blocks=64, roles=None,
                 name="router", trace_log=None):
    ready = os.path.join(tmpdir, f"{name}.ready.json")
    if os.path.exists(ready):
        os.remove(ready)
    cmd = [
        sys.executable, os.path.abspath(__file__), "--route",
        "--peers", ",".join(peer_addrs), "--ready-file", ready,
        "--warm-blocks", str(warm_blocks),
    ]
    if roles:
        cmd += ["--roles", ",".join(roles)]
    if trace_log:
        cmd += ["--trace-log", trace_log]
    env = dict(os.environ, JAX_PLATFORMS=BACKEND)
    return subprocess.Popen(cmd, env=env), ready


def stop_gracefully(proc, problems, label, grace=120.0):
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        problems.append(f"{label}: SIGTERM did not exit within grace")
        return
    if rc != 0:
        problems.append(f"{label}: drain exit code {rc} != 0")


# -- schedule + references ---------------------------------------------------


def make_schedule(seed, n_requests, new_tokens, prefix=()):
    """Seeded prompts + dedupe tokens.  ``prefix`` makes a group of
    prompts share a block-aligned head — the hot chains the radix
    caches build and the KV migration legs ship."""
    rnd = random.Random(seed)
    schedule = []
    for i in range(n_requests):
        tail = rnd.randrange(3, 10)
        prompt = list(prefix) + [
            rnd.randrange(1, 250) for _ in range(tail)
        ]
        schedule.append({
            "dedupe_token": f"fleet-{seed}-{i}",
            "prompt": prompt,
            "max_new_tokens": new_tokens,
        })
    return schedule


def shared_prefix(seed, blocks=2):
    rnd = random.Random(seed ^ 0x9E1F)
    return [
        rnd.randrange(1, 250) for _ in range(blocks * BLOCK_TOKENS)
    ]


def greedy_references(schedule):
    """Static-generate the greedy continuation for every prompt — the
    parity oracle every fleet stream must match bitwise, through any
    number of host deaths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_parallel.models import GPTLM, tiny_test
    from tpu_parallel.models.generate import generate

    cfg = tiny_test(remat=False)
    model = GPTLM(cfg)
    probe = jnp.zeros((1, 16), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]
    refs = {}
    for entry in schedule:
        cont = np.asarray(generate(
            model, params,
            jnp.asarray(entry["prompt"], jnp.int32)[None, :],
            max_new_tokens=entry["max_new_tokens"],
        ))[0]
        refs[entry["dedupe_token"]] = [int(t) for t in cont]
    return refs


# -- the children ------------------------------------------------------------


def serve(args):
    """Daemon child: daemon_bench's serve with radix-cached engines so
    ``/v1/kv/export`` has hot chains to ship."""
    import jax

    from tpu_parallel.runtime import enable_compilation_cache

    enable_compilation_cache()
    from tpu_parallel.cluster import Frontend, FrontendConfig
    from tpu_parallel.daemon import (
        DaemonConfig,
        DaemonHTTPServer,
        ServingDaemon,
    )
    from tpu_parallel.models import GPTLM, tiny_test
    from tpu_parallel.obs.registry import MetricRegistry
    from tpu_parallel.obs.spool import SpanSpool
    from tpu_parallel.obs.tracer import Tracer
    from tpu_parallel.serving import SchedulerConfig, ServingEngine

    from tpu_parallel.daemon.wallclock import WallClock

    # --trace-log arms distributed tracing: ONE tracer shared by the
    # engines, the frontend and the daemon (so every layer's spans land
    # in one list), spooled to the named JSONL by the daemon's tick.
    # The tracer runs on the daemon's OWN clock — span timestamps and
    # the ``ts`` this process reports on the wire must share a base or
    # the stitcher's clock-offset math rebases against the wrong zero.
    wallclock = WallClock()
    tracer = Tracer(wallclock) if args.trace_log else None
    spool = (
        SpanSpool(args.trace_log, proc=f"daemon:{args.role}")
        if args.trace_log else None
    )

    cfg = tiny_test(remat=False)
    model = GPTLM(cfg)
    probe = jax.numpy.zeros((1, 16), jax.numpy.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]

    def frontend_factory(clock):
        engines = [
            ServingEngine(
                model, params, n_slots=args.slots,
                scheduler=SchedulerConfig(max_prefills_per_tick=2),
                kv_block_tokens=BLOCK_TOKENS, prefix_cache_size=64,
                kv_radix_cache=True, tracer=tracer,
                # one decode token per paced tick: the fused-scan
                # default drains a whole budget in ~3 ticks, which no
                # tick pacing can stretch — and mid-flight legs (kills,
                # disagg migrations) need requests that LIVE a while
                decode_steps_per_tick=1 if args.tick_sleep > 0 else "auto",
            )
            for _ in range(args.replicas)
        ]
        fe = Frontend(
            engines, router="least",
            config=FrontendConfig(restart=None),
            clock=clock, registry=MetricRegistry(), tracer=tracer,
        )
        if args.tick_sleep > 0:
            # pace each pump tick like a realistically-sized model's
            # decode step: the tiny CPU model otherwise drains a whole
            # token budget faster than one KV-handoff round-trip, which
            # makes mid-flight legs (kills, disagg migrations) a race
            orig_step = fe.step

            def paced_step(*a, **kw):
                out = orig_step(*a, **kw)
                time.sleep(args.tick_sleep)
                return out

            fe.step = paced_step
        return fe

    daemon = ServingDaemon(
        frontend_factory, args.journal,
        config=DaemonConfig(
            grace_seconds=args.grace, fsync_batch=args.fsync_batch,
            role=args.role,
        ),
        clock=wallclock,
        span_spool=spool,
    )
    server = DaemonHTTPServer(daemon, port=args.port).start()
    daemon.install_signals()
    with open(args.ready_file + ".tmp", "w") as fh:
        json.dump({"port": server.port, "pid": os.getpid()}, fh)
    os.replace(args.ready_file + ".tmp", args.ready_file)
    rc = daemon.run()
    server.stop()
    return rc


def route(args):
    """Router child: FleetRouter + FleetHTTPServer; the probe pump owns
    the main thread; SIGTERM stops it for a clean exit 0."""
    from tpu_parallel.daemon.wallclock import WallClock
    from tpu_parallel.fleet import (
        FleetHTTPServer,
        FleetRouter,
        HTTPFleetTransport,
        PeerPolicy,
    )
    from tpu_parallel.obs.registry import MetricRegistry
    from tpu_parallel.obs.spool import SpanSpool
    from tpu_parallel.obs.tracer import Tracer

    wallclock = WallClock()
    # same-clock rule as serve(): the router's clock_sync attrs
    # (t_send/t_recv on self.clock) and its span timestamps must share
    # a base for the stitcher's rebasing to be exact
    tracer = Tracer(wallclock) if args.trace_log else None
    spool = (
        SpanSpool(args.trace_log, proc="router")
        if args.trace_log else None
    )
    peers = [p for p in args.peers.split(",") if p]
    roles = None
    if args.roles:
        parts = [r for r in args.roles.split(",") if r]
        if len(parts) != len(peers):
            raise SystemExit("--roles must align 1:1 with --peers")
        roles = dict(zip(peers, parts))
    router = FleetRouter(
        peers,
        clock=wallclock,
        transport=HTTPFleetTransport(),
        roles=roles,
        # key placement on the shared-prefix head (2 KV blocks = 8
        # tokens): every request of a shared_prefix() group lands on
        # the same daemon, which is what makes its radix chains hot
        # and the kill leg's filler backlog actually pin one host
        buckets=(2 * BLOCK_TOKENS, 4 * BLOCK_TOKENS),
        # bench-paced breaker: detect a dead host in ~1s of probes and
        # readmit a rebooted one within 2s of it answering
        policy=PeerPolicy(
            probe_interval_seconds=0.5,
            degraded_after=1,
            dead_after=2,
            reprobe_backoff_seconds=0.5,
            reprobe_backoff_factor=2.0,
            reprobe_backoff_max=2.0,
            connect_timeout_seconds=5.0,
            request_timeout_seconds=120.0,
            stream_idle_timeout_seconds=15.0,
        ),
        registry=MetricRegistry(),
        warm_start_blocks=args.warm_blocks,
        tracer=tracer,
        span_spool=spool,
    )
    server = FleetHTTPServer(router, port=args.port).start()
    signal.signal(signal.SIGTERM, lambda *_: router.stop())
    with open(args.ready_file + ".tmp", "w") as fh:
        json.dump({"port": server.port, "pid": os.getpid()}, fh)
    os.replace(args.ready_file + ".tmp", args.ready_file)
    router.run(poll_seconds=0.1)
    server.stop()
    return 0


# -- fleet-side helpers ------------------------------------------------------


class StreamReader(threading.Thread):
    """Consume one router SSE stream to its terminal event."""

    def __init__(self, base, rid):
        super().__init__(daemon=True)
        self.url = f"{base}/v1/stream/{rid}"
        self.rid = rid
        self.events = []
        self.times = []  # wall-clock arrival per event (TTFT / ITL)
        self.t0 = None
        self.error = None

    def run(self):
        self.t0 = time.monotonic()
        try:
            req = urllib.request.Request(self.url)
            # generous per-read timeout: the router does not forward
            # keepalives, and a handoff can sit out a probe interval
            with urllib.request.urlopen(req, timeout=600) as resp:
                for raw in resp:
                    line = raw.strip()
                    if not line.startswith(b"data:"):
                        continue
                    ev = json.loads(line[len(b"data:"):].strip())
                    self.times.append(time.monotonic())
                    self.events.append(ev)
                    if ev.get("finished"):
                        return
        except Exception as exc:  # judged by the parent, not raised
            self.error = repr(exc)

    def tokens(self):
        return [e["token"] for e in self.events if "token" in e]

    def indices(self):
        return [e["index"] for e in self.events if "token" in e]

    def ttft(self):
        """Stream-open to first relayed token, or None."""
        for ev, at in zip(self.events, self.times):
            if "token" in ev:
                return at - self.t0
        return None

    def itls(self):
        """Inter-token gaps over the relayed stream (decode latency as
        the client experiences it, handoff stalls included)."""
        arrivals = [
            at for ev, at in zip(self.events, self.times) if "token" in ev
        ]
        return [b - a for a, b in zip(arrivals, arrivals[1:])]


def wait_finished(base, rids, refs, problems, timeout=240.0, label=""):
    """Poll router results until every rid is terminal; judge lost
    work and bitwise parity.  Returns token -> final record."""
    deadline = time.monotonic() + timeout
    pending = dict(rids)
    finished = {}
    while pending and time.monotonic() < deadline:
        for tok, rid in list(pending.items()):
            code, rec = http_json("GET", f"{base}/v1/result/{rid}")
            if code == 200 and rec.get("status") in TERMINAL:
                finished[tok] = rec
                del pending[tok]
        time.sleep(0.05)
    for tok, rid in pending.items():
        problems.append(f"{label}{tok} ({rid}): never terminal")
    for tok, rec in finished.items():
        if rec["status"] != "finished":
            problems.append(
                f"{label}{tok}: status {rec['status']} "
                f"({rec['finish_reason']}) — lost accepted work"
            )
        elif refs is not None and rec["tokens"] != refs[tok]:
            problems.append(
                f"{label}{tok}: tokens diverge from the greedy "
                "reference through the fleet (SILENT WRONG TOKENS)"
            )
    return finished


def kill_when_mid_flight(reader, victim, problems, timeout=120.0):
    """Spin on the target's OWN relayed SSE events until its first
    token arrives, then SIGKILL the backing daemon — the only trigger
    fast enough when the tiny model decodes a whole slot's budget in
    milliseconds (a fixed sleep overshoots the stream; an HTTP result
    poll's router roundtrip can be slower than the stream itself).
    Returns True when the kill landed mid-flight."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        events = reader.events
        if events:
            if events[-1].get("finished"):
                break  # drained before the kill could land
            victim.sigkill()
            return True
        if not reader.is_alive():
            break
        time.sleep(0.0005)
    victim.sigkill()
    problems.append(
        "kill leg: target never observed mid-flight before the kill "
        f"(events={len(reader.events)}, alive={reader.is_alive()})"
    )
    return False


def read_metric(base, line_prefix):
    """Read one series value from the router's /metricsz text."""
    with urllib.request.urlopen(f"{base}/metricsz", timeout=30) as resp:
        text = resp.read().decode()
    for line in text.splitlines():
        if line.startswith(line_prefix + " "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def read_metric_sum(base, name):
    """Sum every series of a labelled metric family (e.g. all
    ``reason=`` legs of ``fleet_handoff_fallbacks_total``)."""
    with urllib.request.urlopen(f"{base}/metricsz", timeout=30) as resp:
        text = resp.read().decode()
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def p95(samples):
    if not samples:
        return None
    ordered = sorted(samples)
    rank = max(1, -(-95 * len(ordered) // 100))  # ceil, 1-based
    return ordered[rank - 1]


def wait_metric(base, line_prefix, minimum, timeout=90.0):
    deadline = time.monotonic() + timeout
    value = 0.0
    while time.monotonic() < deadline:
        value = read_metric(base, line_prefix)
        if value >= minimum:
            return value
        time.sleep(0.25)
    return value


def corrupt_import_leg(donor_addr, target_addr, seed, problems):
    """Export real KV from ``donor``, flip ONE seeded bit, import into
    ``target``: the importer must refuse TYPED (a ``kv_wire`` reason),
    never land garbage.  Returns the typed reason (or None)."""
    from tpu_parallel.serving.kv_wire import WIRE_REASONS

    code, blob = http_bytes(
        "GET", f"http://{donor_addr}/v1/kv/export?max_blocks=16"
    )
    if code != 200:
        problems.append(f"corrupt leg: donor export -> {code}")
        return None
    if not blob:
        problems.append(
            "corrupt leg: donor exported no hot KV — nothing proved"
        )
        return None
    rnd = random.Random(seed ^ 0xB17)
    bit = rnd.randrange(len(blob) * 8)
    flipped = bytearray(blob)
    flipped[bit // 8] ^= 1 << (bit % 8)
    code, body = http_bytes(
        "POST", f"http://{target_addr}/v1/kv/import", bytes(flipped)
    )
    try:
        payload = json.loads(body or b"{}")
    except ValueError:
        payload = {}
    reason = payload.get("reason")
    if code != 400 or reason not in WIRE_REASONS:
        problems.append(
            f"corrupt leg: flipped-bit import answered {code} "
            f"{payload} — want a typed 400 refusal"
        )
        return None
    # the INTACT blob lands (or typed-falls-back) — the refusal above
    # was about the damage, not the transfer
    code, body = http_bytes(
        "POST", f"http://{target_addr}/v1/kv/import", blob
    )
    if code != 200:
        problems.append(f"corrupt leg: intact import -> {code} {body!r}")
    return reason


def direct_import_leg(donor_addrs, victim_addr, problems):
    """Deterministic warm-start freight: export hot chains from a
    daemon that served traffic while the victim was dead — chains the
    victim's own journal replay cannot have recovered — and land them
    directly.  Returns the count of typed ``imported`` verdicts."""
    for addr in sorted(donor_addrs):
        code, blob = http_bytes(
            "GET", f"http://{addr}/v1/kv/export?max_blocks=64"
        )
        if code != 200 or not blob:
            continue
        code, body = http_bytes(
            "POST", f"http://{victim_addr}/v1/kv/import", blob
        )
        if code != 200:
            problems.append(
                f"direct import into the recovered victim -> {code}"
            )
            continue
        try:
            payload = json.loads(body or b"{}")
        except ValueError:
            payload = {}
        if payload.get("imported", 0) >= 1:
            return payload["imported"]
    problems.append(
        "no remote KV import landed a typed `imported` verdict, even "
        "shipping downtime chains the victim provably never saw"
    )
    return 0


# -- the trace leg's stitch + verdict ----------------------------------------


def stitch_and_judge(trace_out, router_log, peers, rids, evidence):
    """Run ``scripts/trace_stitch.py`` over the router's and every
    peer's span log, then judge the stitched forest: each request in
    ``rids`` must map (via the router's ``route`` span) to a trace that
    is single-rooted, touches >= 2 pids and carries a cross-process
    parent link.  Fills ``evidence`` (the trace record) and returns
    a problem list."""
    from tpu_parallel.obs.spool import read_span_log

    problems = []
    cmd = [
        sys.executable,
        os.path.join(REPO_ROOT, "scripts", "trace_stitch.py"),
        trace_out, router_log,
    ] + [
        f"{p.trace_log}={p.addr}" for p in peers if p.trace_log
    ] + ["--summary"]
    res = subprocess.run(cmd, capture_output=True, text=True)
    if res.returncode != 0:
        problems.append(
            f"trace leg: stitch failed rc={res.returncode}: "
            f"{res.stderr.strip()}"
        )
        return problems
    try:
        summary = json.loads(res.stdout)
    except ValueError:
        problems.append(
            f"trace leg: stitch summary unparseable: {res.stdout!r}"
        )
        return problems
    with open(trace_out) as fh:
        stitched = json.load(fh)

    # rid -> trace id, from the router's root spans
    records, _skipped = read_span_log(router_log)
    trace_of_rid = {}
    span_counts = {"router": len(records)}
    for rec in records:
        if rec.get("kind") == "span" and rec.get("name") == "route":
            rid = (rec.get("attrs") or {}).get("rid")
            if rid and rec.get("trace_id"):
                trace_of_rid[rid] = rec["trace_id"]
    for p in peers:
        if p.trace_log:
            peer_records, _ = read_span_log(p.trace_log)
            span_counts[p.name] = len(peer_records)

    connected = 0
    for tok, rid in sorted(rids.items()):
        trace_id = trace_of_rid.get(rid)
        verdict = summary.get(trace_id) if trace_id else None
        if verdict is None:
            problems.append(
                f"trace leg: {tok} ({rid}) has no stitched trace"
            )
            continue
        broken = []
        if not verdict.get("single_rooted"):
            broken.append(f"roots={verdict.get('roots')}")
        if len(verdict.get("pids", [])) < 2:
            broken.append(f"pids={verdict.get('pids')}")
        if verdict.get("cross_process_links", 0) < 1:
            broken.append("no cross-process link")
        if broken:
            problems.append(
                f"trace leg: {tok} trace not connected: "
                + ", ".join(broken)
            )
        else:
            connected += 1
    flow_arrows = stitched.get("metadata", {}).get("flow_arrows", 0)
    if flow_arrows < 1:
        problems.append(
            "trace leg: stitched file carries no flow arrows"
        )
    evidence.update({
        "trace_file": trace_out,
        "requests": len(rids),
        "connected_traces": connected,
        "completeness": (
            round(connected / len(rids), 4) if rids else None
        ),
        "span_counts": span_counts,
        "stitched_traces": len(summary),
        "flow_arrows": flow_arrows,
        "trace_events": len(stitched.get("traceEvents", [])),
    })
    return problems


# -- modes -------------------------------------------------------------------


def run_smoke(tmpdir=None, keep=False, trace_out="", record=""):
    """router + 2 daemons -> traffic -> one SIGKILL mid-stream (bitwise
    handoff) -> victim restart (remote KV warm start) -> corrupt-import
    refusal -> graceful stop.  The gate check_fleet and tier-1 run.
    Returns a problem list.

    The TRACE leg rides the disagg leg: the daemons spool spans from
    boot, the disagg router runs traced, and after it drains the three
    span logs are stitched (``scripts/trace_stitch.py``) into ONE
    Perfetto file — every disagg request must form a single-rooted
    trace crossing >= 2 pids with a cross-process parent link.
    ``trace_out`` names the stitched file (default: inside tmpdir);
    ``record`` writes that evidence as JSON."""
    import tempfile

    problems = []
    tmpdir = tmpdir or tempfile.mkdtemp(prefix="fleet_smoke_")
    trace_out = trace_out or os.path.join(tmpdir, "stitched_trace.json")
    ports = pick_ports(2)
    # paced ticks: the tiny model must not outrun the mid-flight legs
    # (the kill and the disagg migration both race one HTTP round-trip)
    peers = [
        Peer(tmpdir, f"d{i}", p, tick_sleep=0.01,
             trace_log=os.path.join(tmpdir, f"d{i}.trace.jsonl"))
        for i, p in enumerate(ports)
    ]
    trace_evidence = {}
    by_addr = {p.addr: p for p in peers}
    router_proc = None
    try:
        for p in peers:
            p.spawn()
        for p in peers:
            p.wait_ready()
        router_proc, rready = spawn_router(
            tmpdir, [p.addr for p in peers]
        )
        rport = wait_ready(rready, router_proc)["port"]
        base = f"http://127.0.0.1:{rport}"

        code, payload = http_json("GET", f"{base}/healthz")
        if code != 200 or not payload.get("ok"):
            problems.append(f"router healthz {code}: {payload}")

        # ---- warm traffic: shared-prefix group A, plus the kill-leg
        # entries (fillers pin the victim's slots so the target request
        # is guaranteed still mid-flight when the host dies)
        prefix_a = shared_prefix(31)
        sched = make_schedule(
            41, 2, DEFAULT_NEW_TOKENS, prefix=prefix_a
        )
        # the kill leg runs on a FRESH prefix: warm-cached chains would
        # make every prefill a radix hit and the whole backlog drains
        # in milliseconds — too fast to ever catch the target mid-flight
        rnd = random.Random(43)
        prefix_k = shared_prefix(33)
        fillers = [
            {
                "dedupe_token": f"fleet-fill-{i}",
                "prompt": prefix_k + [
                    rnd.randrange(1, 250) for _ in range(3)
                ],
                "max_new_tokens": HANDOFF_NEW_TOKENS,
            }
            for i in range(6)
        ]
        long_entry = {
            "dedupe_token": "fleet-long-0",
            "prompt": prefix_k + [7, 11, 13],
            "max_new_tokens": HANDOFF_NEW_TOKENS,
        }
        refs = greedy_references(sched + fillers + [long_entry])
        rids = {}
        for entry in sched:
            code, rec = http_json(
                "POST", f"{base}/v1/submit", entry
            )
            if code != 200:
                problems.append(f"submit {code}: {rec}")
                continue
            rids[entry["dedupe_token"]] = rec["request_id"]
        # fleet-wide idempotence: a retry answers the original record
        if rids:
            code, rec = http_json("POST", f"{base}/v1/submit", sched[0])
            first = rids[sched[0]["dedupe_token"]]
            if code != 200 or rec["request_id"] != first:
                problems.append(
                    f"fleet dedupe resubmit mismatched: {code} {rec}"
                )
        wait_finished(base, rids, refs, problems, label="warm: ")

        # ---- the kill leg: pin the victim's slots with filler work,
        # then SIGKILL the daemon backing the live target stream
        fill_rids = {}
        for entry in fillers:
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code != 200:
                problems.append(f"filler submit {code}: {rec}")
                continue
            fill_rids[entry["dedupe_token"]] = rec["request_id"]
        code, rec = http_json("POST", f"{base}/v1/submit", long_entry)
        if code != 200:
            problems.append(f"long submit {code}: {rec}")
            return problems
        rid_long = rec["request_id"]
        victim = by_addr[rec["peer"]]
        reader = StreamReader(base, rid_long)
        reader.start()
        if not kill_when_mid_flight(reader, victim, problems):
            return problems
        reader.join(timeout=420)
        if reader.is_alive():
            problems.append("kill leg: relay stream never terminated")
        elif reader.error:
            problems.append(f"kill leg: relay stream tore: {reader.error}")
        else:
            idxs = reader.indices()
            if idxs != list(range(len(idxs))):
                problems.append(
                    f"kill leg: client indices not contiguous: {idxs}"
                )
            if reader.tokens() != refs["fleet-long-0"]:
                problems.append(
                    "kill leg: handed-off stream diverges from the "
                    "greedy reference (NOT BITWISE)"
                )
            tail = reader.events[-1] if reader.events else {}
            if not tail.get("finished") or tail.get("status") != "finished":
                problems.append(f"kill leg: bad terminal event {tail}")
        code, rec = http_json("GET", f"{base}/v1/result/{rid_long}")
        if code != 200 or rec.get("handoffs", 0) < 1:
            problems.append(
                f"kill leg: no handoff recorded on the request: {rec}"
            )
        # the fillers shared the victim's slots: they hand off too, and
        # must finish bitwise on the survivor like any accepted work
        wait_finished(base, fill_rids, refs, problems, label="filler: ")
        survivor = next(p for p in peers if p is not victim)

        # ---- hot chains the victim never saw, then the corrupt leg
        prefix_b = shared_prefix(32)
        sched_b = make_schedule(
            42, 2, DEFAULT_NEW_TOKENS, prefix=prefix_b
        )
        refs_b = greedy_references(sched_b)
        rids_b = {}
        for entry in sched_b:
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code == 200:
                rids_b[entry["dedupe_token"]] = rec["request_id"]
            else:
                problems.append(f"post-kill submit {code}: {rec}")
        wait_finished(base, rids_b, refs_b, problems, label="post-kill: ")
        corrupt_import_leg(survivor.addr, survivor.addr, 5, problems)

        # ---- restart the victim: the router must warm-start it from
        # the survivor over the wire (>= 1 typed `imported` verdict)
        victim.spawn()
        victim.wait_ready()
        imported = wait_metric(
            base, 'fleet_kv_imports_total{status="imported"}', 1
        )
        if imported < 1:
            problems.append(
                "no remote KV import landed after the victim recovered "
                f"(imported={imported})"
            )
        # the recovered peer serves through the router again
        code, payload = http_json("GET", f"{base}/healthz")
        if code != 200:
            problems.append(f"post-recovery healthz {code}: {payload}")

        # ---- disagg leg: a SECOND router over the same two daemons,
        # roles pinned prefill/decode router-side.  Fresh prompts place
        # on the prefill peer, migrate to the decode peer at first
        # token, and the client stream must stay bitwise through the
        # move; a dead decode peer must resolve as a TYPED fallback to
        # colocated decode, never lost tokens.  (Router children are
        # cheap — no model build — so this reuses the warm daemons.)
        # DISTINCT cold prompts (a shared warm prefix makes every
        # prefill a radix hit and the whole batch drains before the
        # export round-trip can land), enough of them that the later
        # requests queue behind the prefill peer's slots: a queued
        # request's relay is attached before admission, so its first
        # token fires the export with most of the budget still pending
        rnd_d = random.Random(44)
        d_entries = [
            {
                "dedupe_token": f"fleet-dg-{i}",
                "prompt": [
                    rnd_d.randrange(1, 250) for _ in range(11)
                ],
                "max_new_tokens": HANDOFF_NEW_TOKENS,
            }
            for i in range(8)
        ]
        kill_entry = {
            "dedupe_token": "fleet-dg-kill",
            "prompt": [rnd_d.randrange(1, 250) for _ in range(11)],
            "max_new_tokens": HANDOFF_NEW_TOKENS,
        }
        refs_d = greedy_references(d_entries + [kill_entry])
        router2_log = os.path.join(tmpdir, "router2.trace.jsonl")
        router2, r2ready = spawn_router(
            tmpdir, [p.addr for p in peers],
            roles=["prefill", "decode"], name="router2",
            trace_log=router2_log,
        )
        try:
            r2port = wait_ready(r2ready, router2)["port"]
            base2 = f"http://127.0.0.1:{r2port}"
            rids_d, readers_d = {}, {}
            for entry in d_entries:
                code, rec = http_json("POST", f"{base2}/v1/submit", entry)
                if code != 200:
                    problems.append(f"disagg submit {code}: {rec}")
                    continue
                tok = entry["dedupe_token"]
                rids_d[tok] = rec["request_id"]
                if rec.get("peer") != peers[0].addr:
                    problems.append(
                        f"disagg: {tok} placed on {rec.get('peer')}, "
                        "not the prefill peer"
                    )
                readers_d[tok] = StreamReader(base2, rec["request_id"])
                readers_d[tok].start()
            for tok, reader in readers_d.items():
                reader.join(timeout=420)
                if reader.is_alive():
                    problems.append(
                        f"disagg: {tok} stream never terminated"
                    )
                elif reader.error:
                    problems.append(
                        f"disagg: {tok} stream tore: {reader.error}"
                    )
                else:
                    if reader.tokens() != refs_d[tok]:
                        problems.append(
                            f"disagg: {tok} NOT BITWISE through the "
                            "prefill->decode handoff"
                        )
                    idxs = reader.indices()
                    if idxs != list(range(len(idxs))):
                        problems.append(
                            f"disagg: {tok} client indices not "
                            f"contiguous: {idxs}"
                        )
            wait_finished(base2, rids_d, refs_d, problems, label="disagg: ")
            migrated = read_metric(base2, "fleet_handoff_disagg_total")
            if migrated < 1:
                problems.append(
                    "disagg: no prefill->decode migration landed "
                    f"(disagg={migrated}, fallbacks="
                    f"{read_metric_sum(base2, 'fleet_handoff_fallbacks_total')})"
                )

            # ---- trace leg, live surfaces: per-request attribution
            # (/v1/requestz), the raw span feed (/v1/tracez), and the
            # aggregated fleet exposition (peer-labelled /metricsz)
            probe_rid = next(iter(rids_d.values()), None)
            if probe_rid is not None:
                code, tl = http_json(
                    "GET", f"{base2}/v1/requestz/{probe_rid}"
                )
                if code != 200 or not tl.get("trace_id"):
                    problems.append(
                        f"trace leg: requestz {code}: {tl}"
                    )
                elif not tl.get("phases"):
                    problems.append(
                        f"trace leg: requestz has no phase "
                        f"attribution: {tl}"
                    )
                elif len(tl.get("processes", [])) < 2:
                    problems.append(
                        "trace leg: requestz stitched fewer than 2 "
                        f"processes: {tl.get('processes')}"
                    )
            code, tz = http_json("GET", f"{base2}/v1/tracez")
            if code != 200 or not tz.get("records"):
                problems.append(
                    f"trace leg: router tracez empty: {code}"
                )
            with urllib.request.urlopen(
                f"{base2}/metricsz", timeout=30
            ) as resp:
                fleet_text = resp.read().decode()
            if not any(
                line.startswith("daemon_") and 'peer="' in line
                for line in fleet_text.splitlines()
            ):
                problems.append(
                    "trace leg: fleet /metricsz re-exports no "
                    "peer-labelled daemon_* series"
                )
            if "fleet:" not in fleet_text:
                problems.append(
                    "trace leg: fleet /metricsz carries no fleet-level "
                    "sum series"
                )
            if "fleet_phase_seconds" not in fleet_text:
                problems.append(
                    "trace leg: no fleet_phase_seconds histogram "
                    "observed"
                )

            # ---- overhead leg: the same cold schedule through an
            # UNTRACED role-pinned router vs the traced one — tracing
            # must not tax the serve path measurably
            rnd_o = random.Random(45)

            def oh_batch(tag):
                return [
                    {
                        "dedupe_token": f"fleet-oh-{tag}-{i}",
                        "prompt": [
                            rnd_o.randrange(1, 250) for _ in range(11)
                        ],
                        "max_new_tokens": DEFAULT_NEW_TOKENS,
                    }
                    for i in range(4)
                ]

            batch_plain, batch_traced = oh_batch("p"), oh_batch("t")
            refs_oh = greedy_references(batch_plain + batch_traced)
            router2b, r2bready = spawn_router(
                tmpdir, [p.addr for p in peers],
                roles=["prefill", "decode"], name="router2b",
            )
            try:
                r2bport = wait_ready(r2bready, router2b)["port"]
                base2b = f"http://127.0.0.1:{r2bport}"

                def timed_batch(base_url, batch):
                    t0 = time.monotonic()
                    rids = {}
                    for entry in batch:
                        code, rec = http_json(
                            "POST", f"{base_url}/v1/submit", entry
                        )
                        if code == 200:
                            rids[entry["dedupe_token"]] = (
                                rec["request_id"]
                            )
                        else:
                            problems.append(
                                f"overhead submit {code}: {rec}"
                            )
                    wait_finished(
                        base_url, rids, refs_oh, problems,
                        label="overhead: ",
                    )
                    return time.monotonic() - t0

                t_plain = timed_batch(base2b, batch_plain)
                t_traced = timed_batch(base2, batch_traced)
                overhead = max(0.0, t_traced / max(t_plain, 1e-9) - 1.0)
                trace_evidence["overhead"] = {
                    "untraced_seconds": round(t_plain, 3),
                    "traced_seconds": round(t_traced, 3),
                    "ratio": round(overhead, 4),
                }
                # generous gate bound: batches this small are noisy on
                # a 1-core box; the recorded artifact carries the
                # measured ratio for the <=5% acceptance judgment
                if overhead > 0.25:
                    problems.append(
                        "trace leg: traced serve path "
                        f"{overhead:.1%} slower than untraced"
                    )
                stop_gracefully(router2b, problems, "router2b")
                router2b = None
            finally:
                if router2b is not None and router2b.poll() is None:
                    router2b.kill()
                    router2b.wait(timeout=30)

            # kill the decode peer; fresh work falls back TYPED
            peers[1].sigkill()
            code, rec = http_json("POST", f"{base2}/v1/submit", kill_entry)
            if code != 200:
                problems.append(f"disagg kill submit {code}: {rec}")
            else:
                reader = StreamReader(base2, rec["request_id"])
                reader.start()
                reader.join(timeout=420)
                if reader.is_alive() or reader.error:
                    problems.append(
                        "disagg kill: stream did not survive the dead "
                        f"decode peer (error={reader.error})"
                    )
                elif reader.tokens() != refs_d["fleet-dg-kill"]:
                    problems.append(
                        "disagg kill: colocated fallback NOT BITWISE"
                    )
            fallbacks = read_metric_sum(
                base2, "fleet_handoff_fallbacks_total"
            )
            if fallbacks < 1:
                problems.append(
                    "disagg kill: dead decode peer produced no typed "
                    f"fallback (fallbacks_total={fallbacks})"
                )
            stop_gracefully(router2, problems, "router2")
            router2 = None
        finally:
            if router2 is not None and router2.poll() is None:
                router2.kill()
                router2.wait(timeout=30)
        # bring the decode daemon back so the fleet drains gracefully
        peers[1].spawn()
        peers[1].wait_ready()

        # ---- trace leg, stitching: the three span logs -> ONE
        # Perfetto file via the CLI, then judge connectivity — every
        # disagg request must be a single-rooted trace crossing >= 2
        # pids with a cross-process parent link (the flow arrow)
        trace_problems = stitch_and_judge(
            trace_out, router2_log, peers, rids_d, trace_evidence
        )
        problems.extend(trace_problems)
        if record:
            with open(record, "w") as fh:
                json.dump(trace_evidence, fh, indent=2)
                fh.write("\n")

        # ---- graceful stop: router first, then the daemons
        stop_gracefully(router_proc, problems, "router")
        router_proc = None
        for p in peers:
            stop_gracefully(p.proc, problems, p.name)
    finally:
        for proc in [router_proc] + [p.proc for p in peers]:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        if not keep and not problems:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def read_metric_family(base, name):
    """All series of one metric family -> {label_suffix: value}."""
    with urllib.request.urlopen(f"{base}/metricsz", timeout=30) as resp:
        text = resp.read().decode()
    family = {}
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            series, value = line.rsplit(" ", 1)
            family[series[len(name):].strip() or "_"] = float(value)
    return family


def _disagg_leg(tmpdir, label, roles, refs, burst, measured):
    """One disagg-bench leg: N daemons under the given roles, the
    seeded long-prefill burst + measured decode-heavy probes, decode
    ITL/TTFT from the probes' own relayed streams.  Returns
    (stats, problems)."""
    problems = []
    ports = pick_ports(len(roles))
    peers = [
        Peer(tmpdir, f"{label}{i}", port, role=role, tick_sleep=0.01)
        for i, (port, role) in enumerate(zip(ports, roles))
    ]
    router_proc = None
    stats = {"label": label, "roles": list(roles)}
    try:
        for p in peers:
            p.spawn()
        for p in peers:
            p.wait_ready()
        router_proc, rready = spawn_router(
            tmpdir, [p.addr for p in peers],
            roles=list(roles), name=f"router_{label}",
        )
        rport = wait_ready(rready, router_proc)["port"]
        base = f"http://127.0.0.1:{rport}"

        # the burst first (it is the prefill contention), then the
        # measured probes whose decode ITL the record judges
        rids, readers = {}, {}
        for entry in burst + measured:
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code != 200:
                problems.append(f"{label}: submit {code}: {rec}")
                continue
            tok = entry["dedupe_token"]
            rids[tok] = rec["request_id"]
            readers[tok] = StreamReader(base, rec["request_id"])
            readers[tok].start()

        measured_toks = {e["dedupe_token"] for e in measured}
        ttfts, gaps_all, gaps_steady = [], [], []
        for tok, reader in readers.items():
            reader.join(timeout=420)
            if reader.is_alive():
                problems.append(f"{label}: {tok} stream never terminated")
                continue
            if reader.error:
                problems.append(
                    f"{label}: {tok} stream tore: {reader.error}"
                )
                continue
            if reader.tokens() != refs[tok]:
                problems.append(
                    f"{label}: {tok} diverges from the greedy "
                    "reference (NOT BITWISE)"
                )
            idxs = reader.indices()
            if idxs != list(range(len(idxs))):
                problems.append(
                    f"{label}: {tok} client indices not contiguous"
                )
            if tok in measured_toks:
                if reader.ttft() is not None:
                    ttfts.append(reader.ttft())
                gaps = reader.itls()
                gaps_all.extend(gaps)
                # steady-state view: drop each stream's single largest
                # gap (the disagg leg's one-time migration stall; the
                # same trim applies to BOTH legs so the comparison
                # stays symmetric).  The stall itself is reported via
                # fleet_handoff_seconds_total.
                if gaps:
                    trimmed = sorted(gaps)[:-1]
                    gaps_steady.extend(trimmed)

        # every accepted request terminal + bitwise; retries answer the
        # original record (zero lost, zero duplicated)
        wait_finished(base, rids, refs, problems, label=f"{label}: ")
        for entry in burst + measured:
            tok = entry["dedupe_token"]
            if tok not in rids:
                continue
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code != 200 or rec.get("request_id") != rids[tok]:
                problems.append(
                    f"{label}: {tok} retry re-admitted — duplicate "
                    f"work path ({code} {rec})"
                )

        stats.update(
            requests=len(rids),
            measured=len(measured_toks),
            ttft_p95_seconds=p95(ttfts),
            decode_itl_p95_seconds=p95(gaps_steady),
            decode_itl_p95_all_gaps_seconds=p95(gaps_all),
            decode_itl_samples=len(gaps_steady),
            handoff_disagg=read_metric(
                base, "fleet_handoff_disagg_total"
            ),
            handoff_bytes=read_metric(base, "fleet_handoff_bytes_total"),
            handoff_seconds=read_metric(
                base, "fleet_handoff_seconds_total"
            ),
            handoff_fallbacks=read_metric_family(
                base, "fleet_handoff_fallbacks_total"
            ),
        )
        stop_gracefully(router_proc, problems, f"{label}-router")
        router_proc = None
        for p in peers:
            stop_gracefully(p.proc, problems, p.name)
    finally:
        for proc in [router_proc] + [p.proc for p in peers]:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
    return stats, problems


def run_disagg(args):
    """1-prefill/2-decode vs 3-mixed at equal hardware, same seeded
    schedule: a long-prefill burst contends with decode-heavy probes;
    the record captures decode ITL p95 / TTFT per leg
    plus the handoff byte/latency cost, and any correctness problem
    (lost, duplicated, or non-bitwise stream) fails the bench."""
    import tempfile

    seed = args.disagg
    tmpdir = args.workdir or tempfile.mkdtemp(prefix="fleet_disagg_")
    rnd = random.Random(seed ^ 0xD15A)
    # long prompts near the tiny model's seq_len: prefill compute is
    # the contention the decode pool escapes
    burst = [
        {
            "dedupe_token": f"burst-{seed}-{i}",
            "prompt": [rnd.randrange(1, 250) for _ in range(24)],
            "max_new_tokens": 4,
        }
        for i in range(8)
    ]
    measured = [
        {
            "dedupe_token": f"probe-{seed}-{i}",
            "prompt": [rnd.randrange(1, 250) for _ in range(8)],
            "max_new_tokens": HANDOFF_NEW_TOKENS,
        }
        for i in range(6)
    ]
    refs = greedy_references(burst + measured)
    baseline, problems = _disagg_leg(
        tmpdir, "mixed", ("mixed", "mixed", "mixed"),
        refs, burst, measured,
    )
    disagg, problems_b = _disagg_leg(
        tmpdir, "disagg", ("prefill", "decode", "decode"),
        refs, burst, measured,
    )
    problems += problems_b
    if disagg.get("handoff_disagg", 0) < 1:
        problems.append(
            "disagg leg: no prefill->decode migration fired "
            f"(fallbacks={disagg.get('handoff_fallbacks')})"
        )
    record = {
        "bench": "fleet_disagg",
        "backend": BACKEND,
        "seed": seed,
        "config": {
            "daemons": 3,
            "burst_requests": len(burst),
            "measured_requests": len(measured),
            "burst_prompt_tokens": 24,
            "probe_new_tokens": HANDOFF_NEW_TOKENS,
            "baseline_roles": list(baseline["roles"]),
            "disagg_roles": list(disagg["roles"]),
            "itl_note": (
                "decode_itl_p95_seconds drops each stream's single "
                "largest gap (applied to both legs); the untrimmed "
                "view is decode_itl_p95_all_gaps_seconds"
            ),
        },
        "baseline": baseline,
        "disagg": disagg,
    }
    b = baseline.get("decode_itl_p95_seconds")
    d = disagg.get("decode_itl_p95_seconds")
    if b and d:
        record["itl_p95_ratio_disagg_over_baseline"] = round(d / b, 4)
    record["problems"] = problems
    record["ok"] = not problems
    path = args.record or "fleet_disagg_bench.json"
    with open(path, "w") as fh:
        json.dump(record, fh, indent=2)
        fh.write("\n")
    print(f"record: {path}")
    for label, leg in (("baseline", baseline), ("disagg", disagg)):
        print(
            f"  {label}: decode ITL p95 "
            f"{leg.get('decode_itl_p95_seconds')}s, TTFT p95 "
            f"{leg.get('ttft_p95_seconds')}s, migrations "
            f"{leg.get('handoff_disagg')}, handoff bytes "
            f"{leg.get('handoff_bytes')}"
        )
    if not problems:
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)
    return problems


def run_trial(args, seed):
    """One seeded soak trial (see the module docstring).  Returns
    (trial_record, problems)."""
    rnd = random.Random(seed ^ 0xF1EE7)
    problems = []
    tmpdir = os.path.join(
        args.workdir or "/tmp", f"fleet_soak_{os.getpid()}_{seed}"
    )
    os.makedirs(tmpdir, exist_ok=True)
    ports = pick_ports(args.daemons)
    peers = [Peer(tmpdir, f"d{i}", p) for i, p in enumerate(ports)]
    by_addr = {p.addr: p for p in peers}
    router_proc = None
    try:
        for p in peers:
            if os.path.exists(p.journal):
                os.remove(p.journal)
            p.spawn(grace=args.grace)
        for p in peers:
            p.wait_ready()
        router_proc, rready = spawn_router(
            tmpdir, [p.addr for p in peers],
            warm_blocks=args.warm_blocks,
        )
        rport = wait_ready(rready, router_proc)["port"]
        base = f"http://127.0.0.1:{rport}"

        # every schedule this trial runs, referenced in one pass: two
        # shared-prefix traffic groups, the kill leg (fillers pin the
        # victim's slots behind one long target), and a downtime group
        # served while the victim is dead (warm-start freight its own
        # journal replay provably cannot recover)
        prefix_a = shared_prefix(seed)
        prefix_b = shared_prefix(seed + 1)
        prefix_c = shared_prefix(seed + 2)
        prefix_d = shared_prefix(seed + 3)
        half = args.requests // 2
        schedule = (
            make_schedule(seed, half, args.new, prefix=prefix_a)
            + make_schedule(
                seed + 1000, args.requests - half, args.new,
                prefix=prefix_b,
            )
        )
        fillers = [
            {
                "dedupe_token": f"fleet-{seed}-fill-{i}",
                "prompt": prefix_c + [
                    rnd.randrange(1, 250) for _ in range(2)
                ],
                "max_new_tokens": HANDOFF_NEW_TOKENS,
            }
            for i in range(5)
        ]
        target = {
            "dedupe_token": f"fleet-{seed}-target",
            "prompt": prefix_c + [
                rnd.randrange(1, 250) for _ in range(2)
            ],
            "max_new_tokens": HANDOFF_NEW_TOKENS,
        }
        downtime = make_schedule(
            seed + 2000, 2, args.new, prefix=prefix_d
        )
        refs = greedy_references(
            schedule + fillers + [target] + downtime
        )

        # ---- phase 1: streamed traffic through a healthy fleet
        rids = {}
        readers = {}
        for entry in schedule:
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code != 200:
                problems.append(f"submit {code}: {rec}")
                continue
            tok = entry["dedupe_token"]
            rids[tok] = rec["request_id"]
            readers[tok] = StreamReader(base, rec["request_id"])
            readers[tok].start()
        accepted = len(rids)
        for tok, reader in readers.items():
            reader.join(timeout=420)
            if reader.is_alive():
                problems.append(f"{tok}: relay stream never terminated")
            elif reader.error:
                problems.append(f"{tok}: relay tore: {reader.error}")
            elif reader.tokens() != refs[tok]:
                problems.append(
                    f"{tok}: stream diverges from the greedy reference"
                )
        wait_finished(base, rids, refs, problems)

        # ---- the seeded kill: the fillers share the target's prefix,
        # so the ring packs them onto one daemon and keeps the target
        # mid-flight behind them; that daemon is the victim
        fill_rids = {}
        for entry in fillers:
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code != 200:
                problems.append(f"filler submit {code}: {rec}")
                continue
            fill_rids[entry["dedupe_token"]] = rec["request_id"]
        code, rec = http_json("POST", f"{base}/v1/submit", target)
        if code != 200:
            problems.append(f"target submit {code}: {rec}")
            raise RuntimeError("kill-leg target never admitted")
        rid_target = rec["request_id"]
        accepted += len(fill_rids) + 1
        victim = by_addr[rec["peer"]]
        reader = StreamReader(base, rid_target)
        reader.start()
        kill_when_mid_flight(reader, victim, problems)
        kill_at = time.monotonic()
        reader.join(timeout=420)
        if reader.is_alive():
            problems.append("kill leg: relay stream never terminated")
        elif reader.error:
            problems.append(f"kill leg: relay tore: {reader.error}")
        else:
            idxs = reader.indices()
            if idxs != list(range(len(idxs))):
                problems.append(
                    f"kill leg: client indices not contiguous: {idxs}"
                )
            if reader.tokens() != refs[target["dedupe_token"]]:
                problems.append(
                    "kill leg: handed-off stream diverges from the "
                    "greedy reference (NOT BITWISE)"
                )
        code, target_rec = http_json(
            "GET", f"{base}/v1/result/{rid_target}"
        )
        if code != 200 or target_rec.get("handoffs", 0) < 1:
            problems.append(
                f"kill leg: no handoff recorded on the target: "
                f"{target_rec}"
            )
        kill_finished = wait_finished(
            base, fill_rids, refs, problems, label="filler: "
        )
        handoffs = sum(
            r.get("handoffs", 0)
            for r in [target_rec] + list(kill_finished.values())
            if isinstance(r, dict)
        )
        kill_to_done = round(time.monotonic() - kill_at, 3)

        # ---- fleet-wide idempotency: a full client retry sweep maps
        # every dedupe token back to its original request id, across
        # the host death
        all_rids = dict(rids)
        all_rids.update(fill_rids)
        all_rids[target["dedupe_token"]] = rid_target
        dedupe_hits = 0
        for entry in schedule + fillers + [target]:
            tok = entry["dedupe_token"]
            if tok not in all_rids:
                continue
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code == 200 and rec["request_id"] == all_rids[tok]:
                dedupe_hits += 1
            else:
                problems.append(
                    f"{tok}: retry re-admitted as {rec.get('request_id')}"
                    f" != {all_rids[tok]} — duplicate completion path"
                )

        # ---- downtime traffic: hot chains the dead victim never saw
        d_rids = {}
        d_peers = set()
        for entry in downtime:
            code, rec = http_json("POST", f"{base}/v1/submit", entry)
            if code != 200:
                problems.append(f"downtime submit {code}: {rec}")
                continue
            d_rids[entry["dedupe_token"]] = rec["request_id"]
            d_peers.add(rec["peer"])
        wait_finished(base, d_rids, refs, problems, label="downtime: ")
        accepted += len(d_rids)

        # ---- corrupt-injection leg against a survivor
        survivor = next(p for p in peers if p is not victim)
        wire_reason = corrupt_import_leg(
            survivor.addr, survivor.addr, seed, problems
        )

        # ---- restart the victim; the router warm-starts it remotely.
        # The router's donor pick (the newcomer's ring successor) may
        # hold only chains the victim's own journal replay already
        # recovered — then every verdict is `already_cached` and the
        # deterministic fallback ships the downtime peer's chains
        # directly instead.
        victim.spawn(grace=args.grace)
        victim.wait_ready()
        imported = wait_metric(
            base, 'fleet_kv_imports_total{status="imported"}', 1,
            timeout=20,
        )
        if imported < 1:
            imported = direct_import_leg(
                d_peers, victim.addr, problems
            )

        # ---- graceful stop
        stop_gracefully(router_proc, problems, "router")
        router_proc = None
        for p in peers:
            stop_gracefully(p.proc, problems, p.name, grace=args.grace + 60)
        trial = {
            "seed": seed,
            "victim": victim.addr,
            "accepted": accepted,
            "requests": args.requests,
            "finished": len(all_rids) + len(d_rids) - sum(
                1 for p in problems if "lost accepted work" in p
            ),
            "handoffs": handoffs,
            "dedupe_hits_on_retry": dedupe_hits,
            "kv_imported": imported,
            "corrupt_refusal_reason": wire_reason,
            "kill_to_done_seconds": kill_to_done,
        }
    finally:
        for proc in [router_proc] + [p.proc for p in peers]:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        if not problems:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
    return trial, problems


def run_soak(args):
    """The seeded host-kill acceptance soak (>= 3 seeds)."""
    record = {"bench": "fleet_soak", "backend": BACKEND, "trials": []}
    problems = []
    total_handoffs = 0
    for trial in range(args.trials):
        seed = args.soak + trial
        trial_rec, trial_problems = run_trial(args, seed)
        trial_rec["problems"] = list(trial_problems)
        record["trials"].append(trial_rec)
        problems.extend(trial_problems)
        total_handoffs += trial_rec.get("handoffs", 0)
        print(
            f"trial {trial} (seed {seed}): victim={trial_rec['victim']} "
            f"finished={trial_rec['finished']}/{trial_rec['accepted']} "
            f"handoffs={trial_rec['handoffs']} "
            f"kv_imported={trial_rec['kv_imported']} "
            f"corrupt_refusal={trial_rec['corrupt_refusal_reason']} "
            f"problems={len(trial_problems)}"
        )
    if total_handoffs == 0:
        problems.append(
            "no trial handed a request across hosts — the soak proved "
            "nothing about cross-host continuation; lengthen --new or "
            "add trials"
        )
    record["handoffs_total"] = total_handoffs
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
                    help="INTERNAL: run one daemon child")
    ap.add_argument("--route", action="store_true",
                    help="INTERNAL: run the fleet router child")
    ap.add_argument("--smoke", action="store_true",
                    help="fast gate: router + 2 daemons, one SIGKILL, "
                         "bitwise handoff, one warm start, one corrupt "
                         "refusal")
    ap.add_argument("--soak", type=int, default=None, metavar="SEED",
                    help="seeded host-kill soak: trials use seeds "
                         "SEED..SEED+trials-1")
    ap.add_argument("--disagg", type=int, default=None, metavar="SEED",
                    help="prefill/decode disaggregation bench: "
                         "1-prefill/2-decode vs 3-mixed at equal "
                         "hardware, records fleet_disagg_bench.json")
    ap.add_argument("--peers", type=str, default="")
    ap.add_argument("--role", type=str, default="mixed",
                    help="INTERNAL (--serve): this daemon's fleet role")
    ap.add_argument("--tick-sleep", type=float, default=0.0,
                    help="INTERNAL (--serve): seconds slept per pump "
                         "tick — paces the tiny model like a real one")
    ap.add_argument("--roles", type=str, default="",
                    help="INTERNAL (--route): comma roles aligned "
                         "with --peers")
    ap.add_argument("--journal", type=str, default="")
    ap.add_argument("--ready-file", type=str, default="")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--grace", type=float, default=60.0)
    ap.add_argument("--fsync-batch", type=int, default=8)
    ap.add_argument("--warm-blocks", type=int, default=64)
    ap.add_argument("--daemons", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new", type=int, default=12)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--workdir", type=str, default="")
    ap.add_argument("--record", type=str, default="")
    ap.add_argument("--trace-log", type=str, default="",
                    help="arm tracing in a child (--serve/--route): "
                         "spool spans to this JSONL, served at "
                         "/v1/tracez")
    ap.add_argument("--trace-out", type=str, default="",
                    help="smoke/disagg: write the stitched Perfetto "
                         "trace here (also enables the trace leg)")
    args = ap.parse_args()

    if args.serve:
        if not args.journal or not args.ready_file:
            ap.error("--serve needs --journal and --ready-file")
        sys.exit(serve(args))
    if args.route:
        if not args.peers or not args.ready_file:
            ap.error("--route needs --peers and --ready-file")
        sys.exit(route(args))
    if args.smoke:
        problems = run_smoke(
            trace_out=args.trace_out, record=args.record,
        )
    elif args.soak is not None:
        problems = run_soak(args)
    elif args.disagg is not None:
        problems = run_disagg(args)
    else:
        ap.error("pick a mode: --smoke, --soak SEED, or --disagg SEED")
        return
    for problem in problems:
        print(problem, file=sys.stderr)
    if problems:
        print(
            f"fleet_bench: {len(problems)} INVARIANT VIOLATION(S)",
            file=sys.stderr,
        )
        sys.exit(1)
    print("fleet_bench: OK")


if __name__ == "__main__":
    main()
