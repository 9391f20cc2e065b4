"""Serving-engine throughput vs. request arrival rate.

Drives the continuous-batching engine (``tpu_parallel.serving``) with a
Poisson arrival stream of random-length prompts and emits ONE JSON record
per (rate, config) point — throughput, TTFT p50/p95, inter-token latency,
slot occupancy, queue depth, prefill compile/call counts, prefix hit rate
— in the same style as the ``DECODE_r*.json`` static-decode records, so
rounds can track serving perf side by side with static decode.  Not part
of the driver contract.

Usage:
  python scripts/serve_bench.py [--requests N] [--rate R[,R2,...]]
      [--slots S] [--new T] [--prompt-min P] [--prompt-max P]
      [--prompt-dist] [--prefix-len P] [--buckets auto|off|B1,B2,...]
      [--chunk C] [--prefix-cache N] [--spec K] [--compare] [--smoke]
      [--unified-bench --unified-record FILE]
      [--replicas N] [--router rr|least|prefix[,...]] [--fault]
      [--prefix-groups G] [--trace-out FILE] [--metrics-out FILE]
      [--trace-record FILE] [--trace-replay FILE --time-compress X]
      [--swap-bench --swap-at T --swap-record FILE]
      [--autopilot --autopilot-record FILE]
      [--kv-radix] [--kv-host-blocks N] [--prompt-zipf S:TENANTS]
      [--kv-bench --kv-record FILE]
      [--kv-disk --kv-disk-record FILE]
      [--priority-dist SPEC] [--deadline-dist SPEC]
      [--seed K] [--out FILE]

``--prompt-zipf S:TENANTS`` generates a Zipf multi-tenant prompt mix
(tenant headers drawn with weight 1/rank^S) on CHILD rngs, so the
arrival stream is bit-identical to unshaped schedules at the same seed;
the tenant rides traces as ``prefix_group`` and replays exactly.
``--kv-radix`` / ``--kv-host-blocks`` arm the hierarchical KV memory
(radix prefix tree + host-RAM offload tier, serving/kv_hierarchy.py)
for the measured points, and ``--kv-bench`` is its acceptance bench:
radix+host vs aligned-LRU at equal HBM pool bytes on the
Zipf mix, plus a KV-migration relocation leg asserting a relocated
request continues from shipped blocks bitwise-identically.
``--kv-disk`` is the SSD tier's in-process hit-rate bench: the same hierarchy with and without a disk tier under
it, at equal RAM budgets, on a working set far above
``kv_host_blocks`` — the restart-TTFT legs live in ``daemon_bench
--kv-disk``, where process death makes the comparison honest.

Workload record/replay: ``--trace-record PATH`` dumps the generated
request schedule (arrival, prompt, prefix group, priority, deadline)
as JSONL; ``--trace-replay PATH`` (alias ``--workload PATH``) re-feeds
a recorded schedule through the same runners — single-engine or
cluster — with ``--time-compress X`` dividing every arrival gap (a
day-in-the-life at 10-100x).  The loader also accepts a serving
daemon's write-ahead journal directly (``tpu_parallel/daemon/``): the
journal's ``submit`` records carry the SAME workload field names as
trace entries — one exchange format, not two — so yesterday's
production traffic replays against today's configuration with zero
conversion steps (arrivals rebase to the first submit).
``--priority-dist`` / ``--deadline-dist`` (``VALUE:WEIGHT,...``;
deadlines accept ``none``) shape the generated schedule's priority
classes and per-request deadlines from weighted draws on a child rng —
recorded traces carry the drawn values, so a replayed overload trace
exercises priority shedding exactly as recorded.

``--autopilot`` is the SLO-autopilot acceptance bench
(docs/12): deterministic fake-clock legs over one seeded 2x-overload
schedule — a no-autopilot leg whose queue age diverges and deadlines
miss en masse, then the same schedule with the autopilot shedding a
bounded lowest-priority slice and scaling the fleet through the
probation gate.  Exits nonzero unless non-shed deadline misses stay
under 5%, queue-age p95 stays bounded, the shed fraction respects the
policy bound, every finished request is bitwise identical to the
single-engine baseline, and the typed action log replays bit-for-bit;
``--autopilot-record`` writes the record.  The same gate runs (without
the determinism re-run) as part of ``--smoke``.

``--swap-bench`` is the rolling weight hot-swap acceptance bench
(docs/12): three deterministic fake-clock legs over one schedule —
baseline, a real rolling swap at tick ``--swap-at`` (zero failed
requests, in-flight-at-swap streams bitwise identical to baseline,
fleet ends 100% on the new version), and an injected regression whose
stalled canary must trigger automatic rollback (fleet ends 100% on the
OLD version).  Exits nonzero on any invariant violation;
``--swap-record`` writes the record.

``--replicas N`` (N > 1) switches to CLUSTER mode: N engine replicas
behind the ``tpu_parallel.cluster`` Frontend, one record per (rate,
router policy) — ``--router`` takes a comma list (rr, least, prefix) so
one run compares policies on identical workloads (TTFT p95, aggregate
prefix hit rate, retries).  ``--prefix-groups G`` shapes the workload as
G distinct shared system-headers assigned randomly across requests — the
repeated-prefix stream prefix-affinity routing exists for.
``--fault-spec`` arms per-replica FaultPlans from a comma list of
``RID:KIND@ARG`` entries — ``0:crash@8`` (crash replica 0 at its tick
8), ``1:stall@4+6`` (6 no-op ticks from tick 4), ``2:flap@10``
(crash-loop every 10th incarnation tick), ``0:reject@3+5`` (admission
refusals); entries for the same replica merge.  ``--fault`` stays as an
alias for the original ``0:crash@8``.  When any spec includes a flap —
or with ``--chaos SEED``, which draws the whole per-replica schedule
from ``FaultPlan.from_seed`` — replicas get engine factories and the
frontend's RestartPolicy circuit breaker, so the record carries the
full fault-storm story: deaths, watchdog trips, restarts, probation
promotions (every request still completes, replayed via forced-prefix
re-prefill).

``--trace-out`` records every measured point's request lifecycles
(queue -> prefill[/chunk] -> decode/verify -> finish, one Perfetto track
per slot plus the scheduler track) and writes ONE Chrome trace-event
JSON at exit; ``--metrics-out`` writes the LAST point's metric-registry
snapshot as Prometheus text exposition (docs/11_observability.md).

Defaults exercise 32 requests at rates 8 and 0 (0 = all-at-once) on the
tiny test model; ``--model gpt2_125m`` serves the full-width model (for a
chip).  The caller picks the model, never the backend, and every record
line names where it ran (``platform``, ``device_kind``, ``device_count``).

``--prompt-dist`` switches to the prefix-shared workload: every prompt
starts with the same ``--prefix-len`` system header followed by a random
suffix in [prompt-min, prompt-max] — the shape the prefill fast path
(bucketing + batched prefill + prefix reuse) is built for.  ``--compare``
emits each point twice: the legacy exact batch-1 prefill engine
("prefill_mode": "exact") and the fast path
("bucketed"), so a single file records the improvement.

``--smoke`` runs a small greedy parity gate first — every fast-path mode
(bucketed, chunked, prefix-reuse, the FUSED multi-step tick both alone
and composed with chunked prefill, the per-step T=1 engine, and the
SPECULATIVE engine with both the n-gram drafter and an adversarial
all-wrong drafter) must produce token-identical output to static
``generate()`` — and exits nonzero on any mismatch, so bench numbers can
never come from a silently-wrong fast path.  ``--fused-tick T`` pins
``decode_steps_per_tick`` for the measured points (1 = the per-step
engine, the pre-fused baseline).  ``--spec K`` turns speculative decoding on for the measured
points; the record then reports ``spec_acceptance_rate`` and
``tokens_per_decode_tick`` from the engine metrics.

Records append to ``--out`` (default serve_bench.jsonl next to this
script's cwd) via the shared MetricLogger JSONL sink.
"""

import argparse
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tpu_parallel.utils.profiling import run_identity


def make_prompts(cfg, *, n_requests, prompt_min, prompt_max, prefix_len,
                 seed, prefix_groups=1):
    """Random prompts; with ``prefix_len`` > 0 every prompt opens with one
    of ``prefix_groups`` random system-headers (assigned randomly, so
    routing policy — not submission order — decides placement) and
    [prompt_min, prompt_max] sizes the SUFFIX.  Returns ``(prompts,
    group_indices)`` — the group index feeds the trace recorder's
    ``prefix_group`` field (0 when prefixes are off)."""
    rnd = random.Random(seed)
    headers = [
        [rnd.randrange(1, cfg.vocab_size) for _ in range(prefix_len)]
        for _ in range(max(1, prefix_groups))
    ]
    prompts, groups = [], []
    for _ in range(n_requests):
        n = rnd.randint(prompt_min, prompt_max)
        # single-group draws NO group index, preserving the exact RNG
        # stream (and therefore the workload) of single-engine runs at
        # the same --seed
        g = 0 if len(headers) == 1 else rnd.randrange(len(headers))
        prompts.append(
            headers[g]
            + [rnd.randrange(1, cfg.vocab_size) for _ in range(n)]
        )
        groups.append(g)
    return prompts, groups


def make_zipf_prompts(cfg, *, n_requests, prompt_min, prompt_max,
                      prefix_len, seed, zipf_s, tenants):
    """Zipf-distributed MULTI-TENANT prompt mix: ``tenants`` distinct
    system headers of ``prefix_len`` tokens, each request's tenant drawn
    with weight ``1 / rank**zipf_s`` (rank 1 hottest), suffix lengths in
    [prompt_min, prompt_max].  Every draw runs on CHILD rngs
    (``seed ^ const``), so the ARRIVAL stream — :func:`build_schedule`'s
    own ``Random(seed)`` — is bit-identical to unshaped schedules at the
    same seed: the knob reshapes prompts, never timing.  Returns
    ``(prompts, tenant_indices)``; the tenant index rides traces as
    ``prefix_group``, so a recorded Zipf workload replays exactly.

    This is the workload the KV-hierarchy acceptance bench runs on: a
    hot head of tenants an LRU cache would keep anyway, and a long Zipf
    tail whose one-shot headers evict the head under pure LRU — the
    radix tree's frequency-aware eviction plus the host offload tier
    exist to win exactly here."""
    hdr_rnd = random.Random(seed ^ 0x7E4A47)
    pick_rnd = random.Random(seed ^ 0x21BF03)
    suf_rnd = random.Random(seed ^ 0x5FF1C5)
    headers = [
        [hdr_rnd.randrange(1, cfg.vocab_size) for _ in range(prefix_len)]
        for _ in range(max(1, tenants))
    ]
    weights = [1.0 / (rank + 1) ** zipf_s for rank in range(len(headers))]
    prompts, groups = [], []
    for _ in range(n_requests):
        g = pick_rnd.choices(range(len(headers)), weights=weights)[0]
        n = suf_rnd.randint(prompt_min, prompt_max)
        prompts.append(
            headers[g]
            + [suf_rnd.randrange(1, cfg.vocab_size) for _ in range(n)]
        )
        groups.append(g)
    return prompts, groups


def parse_zipf(spec):
    """``S:TENANTS`` -> ``(s, tenants)`` (e.g. ``1.2:16``)."""
    try:
        s_s, _, t_s = spec.partition(":")
        s, tenants = float(s_s), int(t_s)
    except ValueError:
        raise SystemExit(f"bad --prompt-zipf {spec!r} (want S:TENANTS)")
    if s <= 0 or tenants < 1:
        raise SystemExit(
            f"--prompt-zipf {spec!r}: S must be > 0, TENANTS >= 1"
        )
    return s, tenants


def parse_dist(spec):
    """``VALUE:WEIGHT,...`` -> ``[(value, weight), ...]`` — the
    ``--priority-dist`` / ``--deadline-dist`` exchange format.  Values
    parse as numbers; ``none`` (deadlines: no deadline) stays None.
    Weights are relative (they need not sum to 1)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            val_s, _, w_s = part.partition(":")
            value = None if val_s.lower() == "none" else float(val_s)
            weight = float(w_s) if w_s else 1.0
        except ValueError:
            raise SystemExit(f"bad dist entry {part!r} (want VALUE:WEIGHT)")
        if weight <= 0:
            raise SystemExit(f"dist entry {part!r}: weight must be > 0")
        out.append((value, weight))
    if not out:
        raise SystemExit(f"empty dist spec {spec!r}")
    return out


def build_schedule(prompts, groups, rate, seed, new_tokens,
                   priority_dist=None, deadline_dist=None):
    """The bench's request schedule as data: one dict per request with
    arrival (seconds from t0, same Poisson draw the runners always
    made), the prompt itself, and the workload-shape fields the cluster
    frontend consumes (priority, deadline).  This is the unit
    ``--trace-record`` dumps and ``--trace-replay`` re-feeds.

    ``priority_dist`` / ``deadline_dist`` (:func:`parse_dist` output)
    draw each request's priority class and deadline from weighted
    distributions on a CHILD rng, so the arrival stream — and therefore
    every pre-existing record at the same seed — is bit-identical with
    the knobs off, and the shaped schedule is still a pure function of
    (seed, dists)."""
    rnd = random.Random(seed)
    arrivals, t = [], 0.0
    for _ in prompts:
        arrivals.append(t)
        if rate > 0:
            t += rnd.expovariate(rate)
    shape = random.Random(seed ^ 0x5EED0D15)
    def draw(dist, cast):
        if dist is None:
            return None
        vals = [v for v, _ in dist]
        weights = [w for _, w in dist]
        v = shape.choices(vals, weights=weights)[0]
        return None if v is None else cast(v)
    return [
        {
            "arrival": round(a, 6),
            "prompt": list(p),
            "prompt_len": len(p),
            "prefix_group": g,
            "priority": draw(priority_dist, int) or 0,
            "deadline": draw(deadline_dist, float),
            "max_new_tokens": new_tokens,
        }
        for a, p, g in zip(arrivals, prompts, groups)
    ]


def write_trace(path, schedule, meta=None):
    """Dump a schedule as JSONL: a ``trace_meta`` header line then one
    request per line — the workload-replay harness's exchange format."""
    import json

    with open(path, "w") as fh:
        fh.write(json.dumps({"record": "trace_meta", **(meta or {})}))
        fh.write("\n")
        for entry in schedule:
            fh.write(json.dumps(entry))
            fh.write("\n")
    return path


def load_trace(path, time_compress=1.0):
    """Load a recorded schedule; ``time_compress`` divides every arrival
    (10 = a day-in-the-life replayed in 1/10th the time — same order,
    same prompts, compressed gaps).

    Accepts BOTH exchange surfaces that share the workload schema: a
    ``--trace-record`` file (``trace_meta`` header + request lines) and
    a serving daemon's write-ahead journal (``journal_meta`` header —
    only its ``submit`` records are requests; their ``arrival`` stamps
    are process-monotonic clock readings, so they rebase to the first
    submit = 0).

    Integrity: records are verified with the SAME helper recovery uses
    (``tpu_parallel.daemon.journal.record_crc_ok`` — CRC checked when
    present, legacy records pass), so a corrupted journal replays
    exactly the workload a restart would recover: one damaged tail
    record tolerated, damage anywhere else refuses loudly.  Before
    this, replay trusted any PARSEABLE record — a bit-rotted journal
    could silently replay a different workload than recovery saw."""
    import json

    from tpu_parallel.daemon.journal import (
        MAX_TORN_TAIL_LINES,
        record_crc_ok,
    )

    if time_compress <= 0:
        raise SystemExit(f"--time-compress {time_compress} must be > 0")
    schedule = []
    journal = False
    # a trailing run of damaged lines is legal exactly as recovery
    # tolerates it (one torn/rotted record, which a flipped-in newline
    # can split in two); damage followed by good records refuses
    bad_run = []  # line numbers of the current trailing damaged run
    # journal arrival stamps are process-monotonic and NOT comparable
    # across restarts: each lifetime (delimited by recovery/shutdown
    # records, or a clock regression) rebases so the replayed arrivals
    # stay monotone in FILE (= seq) order — the order traffic actually
    # happened
    new_life = True
    base = life_t0 = 0.0
    prev_raw = None
    workload_keys = (
        "arrival", "prompt", "prompt_len", "prefix_group", "priority",
        "deadline", "max_new_tokens",
    )
    with open(path) as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                bad_run.append(lineno)
                if len(bad_run) > MAX_TORN_TAIL_LINES:
                    raise SystemExit(
                        f"{path}:{bad_run[0]}: damage spans more than a "
                        "torn tail — refusing to replay a corrupt "
                        "workload"
                    )
                continue
            if record_crc_ok(rec) is False:
                # CRC-failed records are rejected exactly like
                # unparseable ones — read_journal and load_trace must
                # never diverge on what counts as a valid record
                bad_run.append(lineno)
                if len(bad_run) > MAX_TORN_TAIL_LINES:
                    raise SystemExit(
                        f"{path}:{bad_run[0]}: checksum damage spans "
                        "more than a torn tail — refusing to replay a "
                        "corrupt workload"
                    )
                continue
            if bad_run:
                raise SystemExit(
                    f"{path}:{bad_run[0]}: unparseable or checksum-"
                    "failed record is not a torn tail — refusing to "
                    "replay a corrupt workload"
                )
            kind = rec.get("record")
            if kind == "trace_meta":
                continue
            if kind == "journal_meta":
                journal = True
                new_life = True
                continue
            if journal or kind is not None:
                # journal mode: only submit records are workload;
                # tokens/terminal/decision records are bookkeeping
                if kind in ("recovery", "shutdown"):
                    new_life = True  # a restarted process's clock follows
                    continue
                if kind != "submit":
                    continue
                raw = float(rec["arrival"])
                if new_life or (prev_raw is not None and raw < prev_raw):
                    base = schedule[-1]["arrival"] if schedule else 0.0
                    life_t0 = raw
                    new_life = False
                prev_raw = raw
                rec = {k: rec.get(k) for k in workload_keys}
                rec["arrival"] = base + (raw - life_t0)
                schedule.append(rec)
                continue
            rec["arrival"] = float(rec["arrival"]) / time_compress
            schedule.append(rec)
    if not schedule:
        raise SystemExit(f"trace {path} holds no requests")
    if journal:
        for rec in schedule:
            rec["arrival"] = round(rec["arrival"] / time_compress, 6)
        return schedule  # file order IS seq order IS the true order
    return sorted(schedule, key=lambda r: r["arrival"])


def _schedule_request(entry, on_token=None):
    from tpu_parallel.serving import Request

    return Request(
        prompt=list(entry["prompt"]),
        max_new_tokens=int(entry["max_new_tokens"]),
        priority=int(entry.get("priority") or 0),
        deadline=entry.get("deadline"),
        on_token=on_token,
    )


def run_point(model, params, cfg, prompts, *, rate, n_slots, new_tokens,
              seed, engine_kwargs, label, tracer=None, schedule=None,
              priority_dist=None, deadline_dist=None):
    from tpu_parallel.serving import (
        Request,
        SchedulerConfig,
        ServingEngine,
    )

    # Poisson process: exponential inter-arrival gaps at `rate` req/s
    # (rate <= 0 or huge => everything arrives at t=0); a replayed trace
    # supplies the whole schedule instead
    if schedule is None:
        schedule = build_schedule(
            prompts, [0] * len(prompts), rate, seed, new_tokens,
            priority_dist=priority_dist, deadline_dist=deadline_dist,
        )
    prompts = [e["prompt"] for e in schedule]
    arrivals = [e["arrival"] for e in schedule]
    n_requests = len(schedule)
    # a replayed trace's budgets win over the CLI default — the record
    # and the throughput denominator must describe what actually ran
    new_tokens = max(int(e["max_new_tokens"]) for e in schedule)
    total_new = sum(int(e["max_new_tokens"]) for e in schedule)

    eng = ServingEngine(
        model, params, n_slots=n_slots,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        rng=jax.random.PRNGKey(seed),
        **engine_kwargs,
    )
    # warm the compiles outside the measured window (exact mode compiles
    # per DISTINCT prompt length; bucketed mode per bucket) + the decode
    # program — ONE drained run over every prompt, not a run per prompt
    # (batched prefill pads to prefill_batch, so singleton and grouped
    # admissions share a compile shape); then start metrics — and
    # prefix-hit tallies — from a clean slate.  The prefix cache itself
    # stays warm, as a long-lived server's would.
    for p in prompts:
        eng.add_request(Request(prompt=p, max_new_tokens=2))
    eng.run()
    eng.reset_metrics()
    if eng._prefix is not None:
        eng._prefix.reset_counters()
    if tracer is not None:
        # trace only the measured window (the warmup's spans would bury
        # the burst under compile-length rectangles)
        eng.tracer = tracer

    t0 = time.perf_counter()
    outs, submitted = [], 0
    while submitted < n_requests or eng.has_work():
        now = time.perf_counter() - t0
        while submitted < n_requests and arrivals[submitted] <= now:
            outs.append(eng.add_request(_schedule_request(
                schedule[submitted]
            )))
            submitted += 1
        if eng.has_work():
            eng.step()
        else:
            # idle until the next arrival
            time.sleep(
                max(0.0, arrivals[submitted] - (time.perf_counter() - t0))
            )
    wall = time.perf_counter() - t0
    assert all(out.status == "finished" for out in outs)

    summary = eng.metrics.summary()
    lengths = [len(p) for p in prompts]
    return eng, {
        "bench": "serve",
        **run_identity(cfg),
        "prefill_mode": label,
        "n_requests": n_requests,
        "arrival_mode": "poisson" if rate > 0 else "burst",
        # numeric or null ALWAYS (the burst sentinel used to be the
        # string "all_at_once" in this float field — schema fix)
        "arrival_rate_per_sec": rate if rate > 0 else None,
        "n_slots": n_slots,
        "prompt_len": [min(lengths), max(lengths)],
        "distinct_prompt_lens": len(set(lengths)),
        "new_tokens": new_tokens,
        "kv_cache": cfg.kv_cache_dtype,
        "prefill_buckets": list(eng._buckets) if eng._buckets else None,
        "prefill_chunk_tokens": eng._chunk_tokens,
        # block-paged KV cache (0 = fixed-slot layout); occupancy / COW /
        # shared-block counters ride in via the metrics summary below
        "kv_block_tokens": getattr(eng.pool, "block_tokens", 0),
        "kv_pool_blocks": getattr(eng.pool, "n_blocks", None),
        "prefix_cache_size": (
            0 if eng._prefix is None
            else getattr(eng._prefix, "max_entries", None)
            or getattr(eng._prefix, "max_device_blocks", 0)
        ),
        # hierarchical KV memory (0/None = aligned-LRU or no cache)
        "kv_radix_cache": eng._radix is not None,
        "kv_host_blocks": (
            eng._radix.host_capacity if eng._radix is not None else 0
        ),
        # speculative decode config (0 = off); acceptance rate, wasted
        # verify positions, and tokens_per_decode_tick ride in via the
        # metrics summary below
        "draft_tokens": eng._spec_width,
        "decode_steps_per_tick": eng.decode_steps_per_tick,
        # distinct prefill/extend call shapes == jit compiles of the
        # prefill path (exact mode: one per distinct length; bucketed:
        # bounded by the bucket set)
        "prefill_compiles": eng.prefill_compiles,
        "wall_s": round(wall, 3),
        "request_tokens_per_sec": round(total_new / wall, 1),
        **summary,
    }


def parse_fault_spec(spec: str):
    """``RID:KIND@ARG`` comma list -> per-replica FaultPlan dict.
    Kinds: ``crash@T`` (one-shot crash at tick T), ``stall@T+N`` (N
    no-op ticks from T; N defaults 4), ``flap@K`` (crash-loop: every
    incarnation dies on its K-th step), ``reject@T+N`` (admission-reject
    window).  Entries for one replica merge into a single plan."""
    import dataclasses

    from tpu_parallel.cluster import FaultPlan

    plans = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            rid_s, rest = part.split(":", 1)
            kind, _, arg = rest.partition("@")
            rid = int(rid_s)
            kw = {}
            if kind == "crash":
                kw["crash_at_tick"] = int(arg)
            elif kind == "stall":
                at, _, n = arg.partition("+")
                kw["stall_at_tick"] = int(at)
                kw["stall_ticks"] = int(n) if n else 4
            elif kind == "flap":
                kw["crash_every"] = int(arg)
            elif kind == "reject":
                at, _, n = arg.partition("+")
                kw["reject_at_tick"] = int(at)
                kw["reject_ticks"] = int(n) if n else 4
            else:
                raise SystemExit(
                    f"bad --fault-spec kind {kind!r} "
                    "(want crash | stall | flap | reject)"
                )
        except ValueError:
            raise SystemExit(f"bad --fault-spec entry {part!r}")
        plans[rid] = dataclasses.replace(
            plans.get(rid, FaultPlan()), **kw
        )
    return plans


def run_cluster_point(model, params, cfg, prompts, *, rate, n_replicas,
                      router, n_slots, new_tokens, seed, engine_kwargs,
                      fault_plans=None, chaos_seed=None, warm=True,
                      tracer=None, schedule=None, priority_dist=None,
                      deadline_dist=None):
    """One cluster-mode measurement: ``n_replicas`` engines behind the
    Frontend under the given router policy, same Poisson arrival stream
    as :func:`run_point`.  ``fault_plans`` (replica id -> FaultPlan, see
    :func:`parse_fault_spec`) injects deterministic faults mid-run;
    ``chaos_seed`` instead draws every replica's schedule from
    ``FaultPlan.from_seed``.  Whenever faults can kill replicas
    repeatedly (any flap, or chaos mode) the replicas get engine
    factories so the frontend's RestartPolicy circuit breaker can
    heal the fleet mid-run — the record carries the storm counters.
    Engine jits are shared per model, so ``warm`` drives one throwaway
    frontend to compile everything outside the measured window."""
    from tpu_parallel.cluster import (
        FaultPlan,
        Frontend,
        FrontendConfig,
        ReplicaHandle,
        RestartPolicy,
    )
    from tpu_parallel.serving import Request, SchedulerConfig, ServingEngine

    def make_engine(i):
        return ServingEngine(
            model, params, n_slots=n_slots,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            rng=jax.random.PRNGKey(seed + 1000 * i),
            **engine_kwargs,
        )

    def make_engines():
        return [make_engine(i) for i in range(n_replicas)]

    if schedule is None:
        schedule = build_schedule(
            prompts, [0] * len(prompts), rate, seed, new_tokens,
            priority_dist=priority_dist, deadline_dist=deadline_dist,
        )
    prompts = [e["prompt"] for e in schedule]
    arrivals = [e["arrival"] for e in schedule]
    # a replayed trace's budgets win over the CLI default (see run_point)
    new_tokens = max(int(e["max_new_tokens"]) for e in schedule)
    total_new = sum(int(e["max_new_tokens"]) for e in schedule)

    if warm:
        fe = Frontend(make_engines(), router=router)
        for p in prompts:
            fe.submit(Request(prompt=p, max_new_tokens=2))
        fe.run()

    if chaos_seed is not None:
        crnd = random.Random(chaos_seed)
        fault_plans = {
            i: FaultPlan.from_seed(
                random.Random(crnd.randrange(2 ** 31)), 64
            )
            for i in range(n_replicas)
        }
    fault_plans = fault_plans or {}
    # self-healing matters once a replica can die more than once (flap /
    # chaos); one-shot crash specs keep the historical no-restart shape
    # so --fault records stay comparable with earlier ones
    selfheal = chaos_seed is not None or any(
        p.crash_every is not None for p in fault_plans.values()
    )
    handles = []
    for i, eng in enumerate(make_engines()):
        handles.append(
            ReplicaHandle(
                i, eng, fault_plan=fault_plans.get(i),
                engine_factory=(
                    (lambda i=i: make_engine(i)) if selfheal else None
                ),
            )
        )
    config = FrontendConfig(
        retry_limit=16 if selfheal else 3,
        watchdog_ticks=5, watchdog_kill_ticks=20,
        restart=RestartPolicy(
            backoff_seconds=0.05, probation_ticks=4, probation_requests=2
        ),
    )
    fe = Frontend(handles, router=router, tracer=tracer, config=config)

    t0 = time.perf_counter()
    outs, submitted = [], 0
    n_requests = len(prompts)
    while submitted < n_requests or fe.has_work():
        now = time.perf_counter() - t0
        while submitted < n_requests and arrivals[submitted] <= now:
            outs.append(fe.submit(_schedule_request(schedule[submitted])))
            submitted += 1
        if fe.has_work():
            fe.step()
        else:
            time.sleep(
                max(0.0, arrivals[submitted] - (time.perf_counter() - t0))
            )
    wall = time.perf_counter() - t0
    assert all(out.status == "finished" for out in outs), (
        [out.status for out in outs]
    )

    s = fe.summary()
    lengths = [len(p) for p in prompts]
    tokens_out = sum(
        h.engine.metrics.tokens_out for h in fe.replicas
    )
    return fe, {
        "bench": "serve_cluster",
        **run_identity(cfg),
        "router": s["router"],
        "replicas": n_replicas,
        "fault": bool(fault_plans),
        "chaos_seed": chaos_seed,
        "n_requests": n_requests,
        "arrival_mode": "poisson" if rate > 0 else "burst",
        # numeric or null ALWAYS (the burst sentinel used to be the
        # string "all_at_once" in this float field — schema fix)
        "arrival_rate_per_sec": rate if rate > 0 else None,
        "n_slots": n_slots,
        "prompt_len": [min(lengths), max(lengths)],
        "new_tokens": new_tokens,
        "prefix_cache_size": engine_kwargs.get("prefix_cache_size", 0),
        "draft_tokens": engine_kwargs.get("draft_tokens", 0),
        "wall_s": round(wall, 3),
        "tokens_out": tokens_out,
        "request_tokens_per_sec": round(total_new / wall, 1),
        "finished": s["finished"],
        "retries": s["retries"],
        "requeued": s["requeued"],
        "replica_deaths": s["replica_deaths"],
        "watchdog_degraded": s["watchdog_degraded"],
        "watchdog_kills": s["watchdog_kills"],
        "restarts": s["restarts"],
        "probation_promotions": s["probation_promotions"],
        "prefix_hit_rate": s["prefix_hit_rate"],
        "ttft_ms_p50": s["ttft_ms_p50"],
        "ttft_ms_p95": s["ttft_ms_p95"],
        "e2e_ms_p95": s["e2e_ms_p95"],
    }


def run_swap_bench(model, params, cfg, schedule, *, n_replicas, n_slots,
                   router, seed, dt, swap_at_tick, logger):
    """The rolling weight hot-swap acceptance bench: three
    legs over ONE replayed schedule on a FAKE clock (dt per cluster
    tick), so every trajectory is a pure function of (schedule, seed).

    1. ``baseline`` — no swap; per-request greedy tokens recorded.
    2. ``swap`` — a REAL new weight set (different init) rolls across
       the fleet at tick ``swap_at_tick``.  Invariants: the rollout
       completes, the fleet ends 100% on the new version, ZERO failed
       requests, and every request that was mid-stream at the trigger
       finishes bitwise identical to the baseline (it completes on the
       old weights).
    3. ``regression`` — a null-value weight set (same numbers, new
       version id, so bitwise comparisons stay valid) whose canary is
       stalled by a FaultPlan: the watchdog kills it, the SwapPolicy
       rolls back automatically, and the fleet ends 100% on the OLD
       version with — again — zero failed requests.

    Returns ``(record, violations)``; an empty violations list is the
    acceptance criterion.
    """
    import jax.numpy as jnp
    import numpy as np

    from tpu_parallel.cluster import (
        FaultPlan,
        Frontend,
        FrontendConfig,
        ReplicaHandle,
        RestartPolicy,
        SwapPolicy,
    )
    from tpu_parallel.models.generate import generate
    from tpu_parallel.serving import SchedulerConfig, ServingEngine

    probe_len = max(e["prompt_len"] for e in schedule)
    probe = jax.numpy.zeros((1, probe_len), jax.numpy.int32)
    params_v2 = type(model)(model.config).init(
        {"params": jax.random.PRNGKey(seed + 7)}, probe, train=False
    )["params"]

    t = [0.0]
    clock = lambda: t[0]  # noqa: E731 — the bench's injectable time axis

    def make_engine():
        return ServingEngine(
            model, params, n_slots=n_slots,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            clock=clock, decode_steps_per_tick=1,
        )

    policy = SwapPolicy(
        drain_ticks=60, canary_ticks=4, canary_seconds=2 * dt,
        canary_requests=1,
    )

    def run_leg(swap_params=None, version=None, fault_plans=None,
                max_ticks=8000):
        t[0] = 0.0
        fault_plans = fault_plans or {}
        handles = [
            ReplicaHandle(
                i, make_engine(), fault_plan=fault_plans.get(i),
                engine_factory=make_engine,
            )
            for i in range(n_replicas)
        ]
        fe = Frontend(
            handles, router=router, clock=clock,
            config=FrontendConfig(
                retry_limit=16, watchdog_ticks=3, watchdog_kill_ticks=8,
                restart=RestartPolicy(
                    backoff_seconds=4 * dt, probation_ticks=3,
                    probation_requests=2,
                ),
            ),
        )
        outs, submitted, ticks = [], 0, 0
        midstream_at_swap = None
        canary_tick = {}  # replica id -> fleet tick its FIRST canary began
        while ticks < max_ticks:
            now = ticks * dt
            while (
                submitted < len(schedule)
                and schedule[submitted]["arrival"] <= now
            ):
                outs.append(
                    fe.submit(_schedule_request(schedule[submitted]))
                )
                submitted += 1
            if swap_params is not None and ticks == swap_at_tick:
                midstream_at_swap = [
                    i for i, o in enumerate(outs)
                    if not o.done and o.tokens
                ]
                st = fe.begin_swap(
                    params=swap_params, version=version, policy=policy
                )
                assert st["state"] == "rolling", st
            t[0] += dt
            fe.step()
            ticks += 1
            canary = fe.swap_status().get("canary")
            if canary is not None:
                canary_tick.setdefault(canary, ticks)
            if (
                submitted >= len(schedule)
                and not fe.has_work()
                and fe.swap_status()["state"]
                not in ("rolling", "rolling_back")
                # a leg that drains before swap_at_tick still ticks on
                # until the swap fires and resolves (an idle-fleet swap
                # is legal; a silently-skipped one would KeyError the
                # record build below)
                and (swap_params is None or ticks > swap_at_tick)
            ):
                break
        return fe, outs, midstream_at_swap, ticks, canary_tick

    violations = []

    def check(cond, msg):
        if not cond:
            violations.append(msg)

    # leg 1: baseline
    fe0, outs0, _, ticks0, _ = run_leg()
    check(
        all(o.status == "finished" for o in outs0),
        "baseline: not every request finished",
    )
    base_tokens = [list(o.tokens) for o in outs0]
    # anchor the baseline itself against static generate (greedy truth)
    for i in (0, len(schedule) - 1):
        ref = np.asarray(generate(
            model, params,
            jnp.asarray(schedule[i]["prompt"], jnp.int32)[None, :],
            max_new_tokens=schedule[i]["max_new_tokens"],
        ))[0]
        check(
            base_tokens[i] == [int(x) for x in ref],
            f"baseline request {i} diverged from static generate",
        )

    # leg 2: the real rolling swap under load
    fe1, outs1, midstream, ticks1, canary_ticks = run_leg(
        swap_params=params_v2, version="v2"
    )
    s1 = fe1.swap_status()
    check(s1["state"] == "completed", f"swap leg did not complete: {s1}")
    check(
        all(v == "v2" for v in s1["replica_versions"].values()),
        f"fleet not 100% on v2 after swap: {s1['replica_versions']}",
    )
    check(
        all(o.status == "finished" for o in outs1),
        "swap leg: failed/lost requests: "
        + str([
            (i, o.status, o.finish_reason)
            for i, o in enumerate(outs1) if o.status != "finished"
        ]),
    )
    check(bool(midstream), "choreography: nothing was mid-stream at swap")
    for i in midstream or []:
        check(
            list(outs1[i].tokens) == base_tokens[i],
            f"in-flight-at-swap request {i} diverged from the no-swap "
            "baseline",
        )

    # leg 3: injected regression -> automatic rollback.  Null-value
    # weights keep every comparison bitwise; the stalled CANARY is the
    # regression (the watchdog observes it, the policy rolls back).
    # Tick flow is weight-independent (no EOS in the random workload),
    # so leg 2's observed canary-entry tick for the first target IS leg
    # 3's — the stall is aimed exactly at the audition window.
    first_target = fe1.replicas[0].replica_id
    check(
        first_target in canary_ticks,
        "choreography: the first target never reached canary in leg 2",
    )
    null_v2 = jax.tree_util.tree_map(lambda x: x, params)
    fe2, outs2, _, ticks2, _ = run_leg(
        swap_params=null_v2, version="v2-regression",
        fault_plans={first_target: FaultPlan(
            stall_at_tick=canary_ticks.get(first_target, swap_at_tick) + 1,
            stall_ticks=400,
        )},
    )
    s2 = fe2.swap_status()
    check(
        s2["state"] == "rolled_back",
        f"regression leg did not roll back: {s2}",
    )
    check(
        s2["verdict"] in ("canary_death", "slo_ttft", "slo_e2e"),
        f"untyped rollback verdict: {s2['verdict']}",
    )
    live = [h for h in fe2.replicas if h.health not in ("dead", "backoff")]
    check(
        bool(live) and all(h.weights_version == "initial" for h in live),
        "fleet not 100% on the old version after rollback: "
        + str({h.replica_id: h.weights_version for h in fe2.replicas}),
    )
    check(
        all(o.status == "finished" for o in outs2),
        "regression leg: failed/lost requests",
    )
    check(
        [list(o.tokens) for o in outs2] == base_tokens,
        "regression leg diverged from baseline (null-value swap must be "
        "bitwise invisible)",
    )

    record = {
        "bench": "serve_swap",
        **run_identity(cfg),
        "seed": seed,
        "replicas": n_replicas,
        "router": router,
        "n_requests": len(schedule),
        "n_slots": n_slots,
        "dt": dt,
        "swap_at_tick": swap_at_tick,
        "swap_policy": {
            "drain_ticks": policy.drain_ticks,
            "canary_ticks": policy.canary_ticks,
            "canary_seconds": policy.canary_seconds,
            "canary_requests": policy.canary_requests,
        },
        "baseline_ticks": ticks0,
        "swap_ticks": ticks1,
        "regression_ticks": ticks2,
        "midstream_at_swap": len(midstream or []),
        "swap_state": s1["state"],
        "swap_relocations": int(fe1.registry.counter(
            "cluster_swap_relocations_total"
        ).value),
        "swap_canary_finished": s1.get("canary_finished", 0),
        "rollback_state": s2["state"],
        "rollback_verdict": s2["verdict"],
        "rollback_deaths": fe2.summary()["replica_deaths"],
        "zero_failed_requests": all(
            o.status == "finished" for o in outs1 + outs2
        ),
        "inflight_bitwise_exact": all(
            list(outs1[i].tokens) == base_tokens[i]
            for i in (midstream or [])
        ),
        "regression_bitwise_exact": (
            [list(o.tokens) for o in outs2] == base_tokens
        ),
        "invariants_ok": not violations,
        "violations": violations,
    }
    logger.log_record(record)
    return record, violations


def run_autopilot_bench(model, params, cfg, *, n_replicas=2, max_replicas=4,
                        n_slots=2, router="least", seed=0, dt=0.05,
                        n_requests=96, new_tokens=8, overload=2.0,
                        logger=None, determinism_check=False):
    """The SLO-autopilot acceptance bench: deterministic
    fake-clock legs over ONE seeded overload schedule — offered load
    ``overload`` x the starting fleet's service capacity, mixed priority
    classes, per-request deadlines.

    1. ``baseline`` — a single no-fault engine serves every prompt with
       no deadlines: the greedy reference tokens.
    2. ``no_autopilot`` — the fixed fleet under the overload: the
       backlog grows without bound, queue-age p95 diverges, and late
       arrivals blow their deadlines en masse.
    3. ``autopilot`` — same schedule, autopilot armed: shed a bounded
       lowest-priority slice early (typed ``shed``), scale to
       ``max_replicas`` through the probation gate, retune admission.

    Invariants (the returned violations list is empty on pass):
    deadline-miss rate of NON-SHED requests < 5% while the no-autopilot
    leg misses worse; the autopilot leg's peak windowed queue-age p95
    stays bounded while the no-autopilot leg's diverges past it; shed
    fraction <= the policy's ``max_shed_fraction``; and every FINISHED
    request's greedy tokens are bitwise identical to the single-engine
    baseline.  ``determinism_check=True`` re-runs the autopilot leg and
    requires an identical typed action log.
    """
    import jax.numpy as jnp
    import numpy as np

    from tpu_parallel.cluster import (
        AutopilotPolicy,
        Frontend,
        FrontendConfig,
        ReplicaHandle,
        RestartPolicy,
    )
    from tpu_parallel.models.generate import generate
    from tpu_parallel.serving import SchedulerConfig, ServingEngine

    rnd = random.Random(seed)
    prompts = [
        [rnd.randrange(1, cfg.vocab_size)
         for _ in range(rnd.randint(3, min(12, cfg.seq_len - new_tokens - 2)))]
        for _ in range(n_requests)
    ]
    # per-step decode at one tick per token: the starting fleet retires
    # about n_replicas * n_slots / (new_tokens + 1) requests per tick,
    # so this arrival rate is `overload` x sustainable capacity
    capacity = n_replicas * n_slots / ((new_tokens + 1) * dt)
    rate = overload * capacity
    # deadlines sized to be comfortable at fleet capacity and hopeless
    # in an unbounded backlog; low priority is the sheddable slice
    deadline = 3.0 * (new_tokens + 1) * dt
    schedule = build_schedule(
        prompts, [0] * len(prompts), rate, seed, new_tokens,
        priority_dist=[(0, 6), (1, 3), (2, 1)],
        deadline_dist=[(deadline, 3), (2 * deadline, 1)],
    )

    refs = [
        [int(x) for x in np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=new_tokens,
        ))[0]]
        for p in prompts
    ]

    t = [0.0]
    clock = lambda: t[0]  # noqa: E731 — the bench's injectable time axis

    def factory():
        return ServingEngine(
            model, params, n_slots=n_slots,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            clock=clock, decode_steps_per_tick=1,
        )

    policy = AutopilotPolicy(
        queue_age_target=(new_tokens + 1) * dt,
        window_ticks=8, breach_ticks=2, clear_ticks=8,
        max_shed_fraction=0.4,
        # provably-unmeetable estimate: a queued request needs at least
        # one prefill tick + one decode tick per remaining token
        min_service_seconds=dt,
        service_seconds_per_token=dt,
        max_replicas=max_replicas, min_replicas=n_replicas,
        scale_cooldown_ticks=8, scale_down_idle_ticks=32,
        prefill_surge_share=n_slots,
    )

    def run_leg(autopilot, max_ticks=6000):
        t[0] = 0.0
        handles = [
            ReplicaHandle(i, factory(), engine_factory=factory)
            for i in range(n_replicas)
        ]
        fe = Frontend(
            handles, router=router, clock=clock,
            config=FrontendConfig(
                retry_limit=8, watchdog_ticks=6, watchdog_kill_ticks=24,
                restart=RestartPolicy(
                    backoff_seconds=4 * dt, probation_ticks=3,
                    probation_requests=2,
                ),
            ),
        )
        ap = fe.enable_autopilot(policy, factory) if autopilot else None
        outs, submitted, ticks = [], 0, 0
        peak_qage95 = 0.0
        while ticks < max_ticks:
            now = ticks * dt
            while (
                submitted < len(schedule)
                and schedule[submitted]["arrival"] <= now
            ):
                outs.append(
                    fe.submit(_schedule_request(schedule[submitted]))
                )
                submitted += 1
            t[0] += dt
            fe.step()
            ticks += 1
            if ap is not None:
                peak_qage95 = max(peak_qage95, ap._qage_p95())
            else:
                # the IDENTICAL sense function the autopilot reads
                # (cluster_queue_age), so both legs report a comparable
                # trajectory.  The raw per-tick value upper-bounds the
                # windowed p95, which only makes the divergence gate
                # harder to fake.
                from tpu_parallel.cluster.autopilot import (
                    cluster_queue_age,
                )

                peak_qage95 = max(
                    peak_qage95, cluster_queue_age(fe, t[0])
                )
            if submitted >= len(schedule) and not fe.has_work():
                break
        return fe, ap, outs, ticks, peak_qage95

    violations = []

    def check(cond, msg):
        if not cond:
            violations.append(msg)

    def leg_stats(outs):
        shed = [o for o in outs if o.finish_reason == "shed"]
        nonshed = [o for o in outs if o.finish_reason != "shed"]
        missed = [o for o in nonshed if o.finish_reason == "deadline"]
        finished = [o for o in nonshed if o.status == "finished"]
        return shed, nonshed, missed, finished

    fe0, _, outs0, ticks0, peak0 = run_leg(autopilot=False)
    shed0, nonshed0, missed0, finished0 = leg_stats(outs0)
    miss_rate0 = len(missed0) / max(1, len(nonshed0))

    fe1, ap1, outs1, ticks1, peak1 = run_leg(autopilot=True)
    shed1, nonshed1, missed1, finished1 = leg_stats(outs1)
    miss_rate1 = len(missed1) / max(1, len(nonshed1))
    shed_fraction = len(shed1) / max(1, len(outs1))

    check(
        all(o.done for o in outs0) and all(o.done for o in outs1),
        "non-termination: open requests at the end of a leg",
    )
    check(
        miss_rate1 < 0.05,
        f"autopilot leg non-shed deadline-miss rate {miss_rate1:.3f} "
        ">= 5%",
    )
    check(
        miss_rate0 > miss_rate1,
        f"no-autopilot leg should miss worse ({miss_rate0:.3f} vs "
        f"{miss_rate1:.3f}) — overload too tame to prove anything",
    )
    check(
        shed_fraction <= policy.max_shed_fraction,
        f"shed fraction {shed_fraction:.3f} > policy bound "
        f"{policy.max_shed_fraction}",
    )
    qage_bound = 4.0 * policy.queue_age_target
    check(
        peak1 <= qage_bound,
        f"autopilot queue-age p95 peak {peak1:.3f}s not bounded "
        f"(> {qage_bound:.3f}s)",
    )
    # the no-autopilot backlog age is structurally capped by deadline
    # enforcement (a pending request is cancelled once past its
    # deadline), so "diverges" = well past the SLO target AND at least
    # twice the controlled leg's peak
    check(
        peak0 > max(policy.queue_age_target, 2.0 * peak1),
        f"no-autopilot queue age {peak0:.3f}s never diverged "
        f"(target {policy.queue_age_target:.3f}s, autopilot peak "
        f"{peak1:.3f}s) — overload too tame",
    )
    for i, out in enumerate(outs1):
        if out.status == "finished":
            check(
                list(out.tokens) == refs[i],
                f"autopilot leg request {i} diverged from the "
                "single-engine baseline",
            )
    check(
        fe1.summary()["scale_ups"] >= 1,
        "autopilot never scaled up under 2x overload",
    )

    action_log = [
        (a.tick, a.kind, a.reason, a.detail) for a in ap1.actions
    ]
    if determinism_check:
        _, ap2, outs2, _, _ = run_leg(autopilot=True)
        log2 = [(a.tick, a.kind, a.reason, a.detail) for a in ap2.actions]
        check(
            action_log == log2,
            "autopilot action log not deterministic across identical runs",
        )
        check(
            [(o.status, o.finish_reason, list(o.tokens)) for o in outs1]
            == [(o.status, o.finish_reason, list(o.tokens)) for o in outs2],
            "autopilot leg outcomes not deterministic across identical "
            "runs",
        )

    s1 = fe1.summary()
    record = {
        "bench": "serve_autopilot",
        **run_identity(cfg),
        "seed": seed,
        "replicas": n_replicas,
        "max_replicas": max_replicas,
        "router": router,
        "n_requests": n_requests,
        "n_slots": n_slots,
        "new_tokens": new_tokens,
        "dt": dt,
        "overload_factor": overload,
        "arrival_rate_per_sec": round(rate, 3),
        "deadline_seconds": deadline,
        "policy": {
            "queue_age_target": policy.queue_age_target,
            "window_ticks": policy.window_ticks,
            "breach_ticks": policy.breach_ticks,
            "clear_ticks": policy.clear_ticks,
            "max_shed_fraction": policy.max_shed_fraction,
            "scale_cooldown_ticks": policy.scale_cooldown_ticks,
            "scale_down_idle_ticks": policy.scale_down_idle_ticks,
        },
        "no_autopilot": {
            "ticks": ticks0,
            "peak_queue_age_p95_s": round(peak0, 4),
            "deadline_miss_rate": round(miss_rate0, 4),
            "finished": len(finished0),
            "deadline_missed": len(missed0),
        },
        "autopilot": {
            "ticks": ticks1,
            "peak_queue_age_p95_s": round(peak1, 4),
            "deadline_miss_rate": round(miss_rate1, 4),
            "finished": len(finished1),
            "deadline_missed": len(missed1),
            "shed": len(shed1),
            "shed_fraction": round(shed_fraction, 4),
            "scale_ups": s1["scale_ups"],
            "scale_downs": s1["scale_downs"],
            "final_replicas": len(fe1.replicas),
            "actions": [
                {"tick": a.tick, "kind": a.kind, "reason": a.reason}
                for a in ap1.actions
            ],
        },
        "bitwise_exact_finished": all(
            list(out.tokens) == refs[i]
            for i, out in enumerate(outs1)
            if out.status == "finished"
        ),
        "invariants_ok": not violations,
        "violations": violations,
    }
    if logger is not None:
        logger.log_record(record)
    return record, violations


def run_capacity_probe(model, params, cfg, *, seed, logger):
    """The paged layout's capacity claim, measured at EQUAL pool bytes:
    a fixed-slot pool of ``s_fixed`` rows vs a paged pool holding the
    SAME K/V bytes as ``s_fixed * seq_len / block_tokens`` blocks.
    Short requests (one block worst case) admit until the fixed pool
    runs out of whole rows vs until the paged pool runs out of blocks —
    plus a burst decode-throughput leg at batch 8 so the block-table
    gather overhead is measured, not asserted."""
    from tpu_parallel.serving import Request, SchedulerConfig, ServingEngine

    seq_len = cfg.seq_len
    bt = max(1, seq_len // 4)
    s_fixed = 4
    n_blocks = s_fixed * seq_len // bt  # EQUAL pool bytes
    short_prompt = [5, 3, 7]
    short_new = max(1, bt - len(short_prompt) - 1)  # 1 block worst case
    n_short = 2 * n_blocks

    def concurrent_short(paged):
        kw = (
            dict(
                kv_block_tokens=bt, kv_pool_blocks=n_blocks,
                n_slots=n_blocks,
            )
            if paged
            else dict(n_slots=s_fixed)
        )
        eng = ServingEngine(
            model, params, decode_steps_per_tick=1,
            scheduler=SchedulerConfig(
                max_prefills_per_tick=n_blocks, max_queue=4 * n_blocks
            ),
            rng=jax.random.PRNGKey(seed), **kw,
        )
        outs = [
            eng.add_request(
                Request(
                    prompt=list(short_prompt), max_new_tokens=short_new
                )
            )
            for _ in range(n_short)
        ]
        eng.step()
        conc = eng.in_flight
        eng.run(max_ticks=5000)
        assert all(out.status == "finished" for out in outs)
        if paged:
            eng.pool.allocator.check()
            assert eng.pool.blocks_free == n_blocks  # no leak
        return conc

    fixed_conc = concurrent_short(False)
    paged_conc = concurrent_short(True)

    rnd = random.Random(seed)
    bench_prompts = [
        [rnd.randrange(1, cfg.vocab_size) for _ in range(3)]
        for _ in range(8)
    ]
    bench_new = min(16, seq_len - 4)

    def burst_tok_s(paged):
        kw = dict(kv_block_tokens=bt) if paged else {}
        eng = ServingEngine(
            model, params, n_slots=8,
            scheduler=SchedulerConfig(max_prefills_per_tick=8),
            rng=jax.random.PRNGKey(seed), **kw,
        )
        for p in bench_prompts:  # warm the compiles
            eng.add_request(Request(prompt=list(p), max_new_tokens=2))
        eng.run()
        eng.reset_metrics()
        t0 = time.perf_counter()
        outs = [
            eng.add_request(
                Request(prompt=list(p), max_new_tokens=bench_new)
            )
            for p in bench_prompts
        ]
        eng.run()
        wall = time.perf_counter() - t0
        assert all(out.status == "finished" for out in outs)
        return round(len(bench_prompts) * bench_new / wall, 1)

    fixed_tps = burst_tok_s(False)
    paged_tps = burst_tok_s(True)
    record = {
        "bench": "serve_paged_capacity",
        **run_identity(cfg),
        "seq_len": seq_len,
        "kv_block_tokens": bt,
        "kv_pool_blocks": n_blocks,
        "equal_pool_tokens": s_fixed * seq_len,
        "fixed_slots": s_fixed,
        "short_request_tokens": len(short_prompt) + short_new,
        "fixed_concurrent_short": fixed_conc,
        "paged_concurrent_short": paged_conc,
        "concurrency_ratio": round(paged_conc / max(1, fixed_conc), 2),
        "decode_batch": len(bench_prompts),
        "decode_new_tokens": bench_new,
        "fixed_decode_tok_s": fixed_tps,
        "paged_decode_tok_s": paged_tps,
        "paged_over_fixed_decode": round(paged_tps / fixed_tps, 3),
    }
    logger.log_record(record)
    return record


def run_kv_hierarchy_bench(model, params, cfg, *, seed, logger,
                           n_requests=96, dt=0.05):
    """The hierarchical-KV-memory acceptance bench (docs/10):
    radix prefix tree + host-RAM offload tier vs the aligned-LRU prefix
    cache, at EQUAL HBM pool bytes, on a Zipf multi-tenant workload —
    plus a KV-migration leg proving a relocated request continues from
    shipped blocks bitwise-identically.

    1. ``aligned_lru`` — the paged engine with the bucket-aligned LRU
       :class:`PrefixCache` (the pre-hierarchy configuration).
    2. ``radix`` — same pool blocks (equal HBM), the radix tree with
       frequency-aware eviction and a host offload tier.  Invariants:
       strictly higher prefix hit rate AND no worse TTFT p95 than leg 1,
       warm blocks actually spilled AND restored (``kv_host_offloads``,
       ``kv_host_restored_blocks`` > 0), zero restore fallbacks (a warm-
       tier hit never recomputes).
    3. ``migration`` — fake-clock 2-replica cluster, a rolling swap with
       ``drain_ticks=1`` forcing in-flight relocation: every request
       finishes bitwise-identical to a no-swap single-engine baseline,
       with ≥ 1 relocated request continuing from MIGRATED blocks
       (typed ``imported``) and zero untyped recomputes (every
       non-imported verdict is a counted fallback status).

    Returns ``(record, violations)``; empty violations is the
    acceptance criterion.
    """
    import json

    import jax.numpy as jnp
    import numpy as np

    from tpu_parallel.cluster import (
        Frontend,
        FrontendConfig,
        ReplicaHandle,
        RestartPolicy,
        SwapPolicy,
    )
    from tpu_parallel.serving import Request, SchedulerConfig, ServingEngine

    if cfg.seq_len < 128:
        # the hierarchy's TTFT claim needs prefill COMPUTE to save — on
        # the toy test config a prefill call is pure dispatch overhead
        # and any win hides inside one log-histogram bucket.  The bench
        # builds its own small-but-real model (d_model 192, seq_len 128:
        # ~10s on CPU), exactly like the capacity probe owns its pool
        # geometry; a passed gpt2_125m is already real.
        from tpu_parallel.models import GPTLM, tiny_test

        cfg = tiny_test(
            remat=False, d_model=192, n_layers=4, n_heads=4, seq_len=128
        )
        model = GPTLM(cfg)
        params = model.init(
            {"params": jax.random.PRNGKey(seed + 1)},
            jax.numpy.zeros((1, cfg.seq_len - 4), jax.numpy.int32),
            train=False,
        )["params"]

    bt = max(1, cfg.seq_len // 4)
    prefix_len = 2 * bt  # every tenant header spans two full blocks
    # short generations keep the point prefill-dominated: the hierarchy's
    # win is skipped prefill work, and TTFT must show it, not drown it
    # under decode time both legs share.  Suffixes stay shorter than the
    # shared header — the multi-tenant system-prompt shape this bench
    # models — so the working set is dominated by REUSABLE blocks
    new_tokens = 2
    suffix_max = max(
        2, min(cfg.seq_len // 3, cfg.seq_len - prefix_len - new_tokens - 2)
    )
    zipf_s, tenants = 1.2, 12
    prompts, groups = make_zipf_prompts(
        cfg, n_requests=n_requests, prompt_min=1, prompt_max=suffix_max,
        prefix_len=prefix_len, seed=seed, zipf_s=zipf_s, tenants=tenants,
    )
    n_slots = 4
    pool_blocks = 2 * n_slots * cfg.seq_len // bt  # EQUAL both legs
    common = dict(
        kv_block_tokens=bt, kv_pool_blocks=pool_blocks,
        prefill_buckets=(bt, 2 * bt, 4 * bt),
    )
    # comparable cache budgets inside the SAME-sized pool: the LRU's 8
    # entries hold up to ~2 blocks each (bucket keys at bt and 2*bt), ~
    # the radix tree's 16 resident device blocks; the host tier sits
    # BELOW the equal-HBM line — it is the hierarchy's whole point
    lru_kwargs = dict(common, prefix_cache_size=8)
    radix_kwargs = dict(
        common, prefix_cache_size=16, kv_radix_cache=True,
        kv_host_blocks=8 * tenants,
    )

    violations = []

    def check(cond, msg):
        if not cond:
            violations.append(msg)

    _, rec_lru = run_point(
        model, params, cfg, prompts, rate=0.0, n_slots=n_slots,
        new_tokens=new_tokens, seed=seed, engine_kwargs=lru_kwargs,
        label="aligned_lru",
    )
    _, rec_radix = run_point(
        model, params, cfg, prompts, rate=0.0, n_slots=n_slots,
        new_tokens=new_tokens, seed=seed, engine_kwargs=radix_kwargs,
        label="radix+host",
    )
    hr_lru = rec_lru["prefix_hit_rate"] or 0.0
    hr_radix = rec_radix["prefix_hit_rate"] or 0.0
    check(
        hr_radix > hr_lru,
        f"radix hit rate {hr_radix} not above aligned-LRU {hr_lru}",
    )
    check(
        rec_radix["ttft_ms_p95"] < rec_lru["ttft_ms_p95"],
        f"radix TTFT p95 {rec_radix['ttft_ms_p95']}ms does not beat "
        f"aligned-LRU {rec_lru['ttft_ms_p95']}ms",
    )
    check(
        rec_radix["kv_host_offloads"] > 0,
        "no warm block ever spilled to the host tier",
    )
    check(
        rec_radix["kv_host_restored_blocks"] > 0,
        "no warm block ever restored from the host tier",
    )
    check(
        rec_radix["kv_host_restore_failures"] == 0,
        f"{rec_radix['kv_host_restore_failures']} warm-tier hits fell "
        "back to recompute (restore failures)",
    )

    # -- leg 3: KV migration on the swap drain-timeout relocation path --
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731 — the bench's injectable time axis

    def mk():
        return ServingEngine(
            model, params, n_slots=2, decode_steps_per_tick=1,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            clock=clock, kv_block_tokens=bt,
            kv_pool_blocks=4 * (cfg.seq_len // bt),
            prefix_cache_size=16, kv_radix_cache=True,
        )

    mig_prompts = [
        list(p[: prefix_len + 2]) for p in prompts[:6]
    ]  # long enough that a mid-stream relocation has full blocks written
    mig_new = min(12, cfg.seq_len - prefix_len - 3)
    t[0] = 0.0
    base_eng = mk()
    bouts = [
        base_eng.add_request(Request(prompt=p, max_new_tokens=mig_new))
        for p in mig_prompts
    ]
    base_eng.run(max_ticks=2000)
    check(
        all(o.status == "finished" for o in bouts),
        "migration baseline: not every request finished",
    )
    base_tokens = [list(o.tokens) for o in bouts]

    t[0] = 0.0
    handles = [ReplicaHandle(i, mk(), engine_factory=mk) for i in range(2)]
    fe = Frontend(
        handles, router="rr", clock=clock,
        config=FrontendConfig(
            retry_limit=8, dispatch_queue_depth=8,
            restart=RestartPolicy(
                backoff_seconds=4 * dt, probation_ticks=3,
                probation_requests=4,
            ),
        ),
    )
    outs = [
        fe.submit(Request(prompt=p, max_new_tokens=mig_new))
        for p in mig_prompts
    ]
    for _ in range(4):  # let work get mid-stream before the swap
        t[0] += dt
        fe.step()
    # null-value weights: a real version roll whose numbers are
    # identical, so EVERY request stays bitwise-comparable to baseline
    null_v2 = jax.tree_util.tree_map(lambda x: x, params)
    st = fe.begin_swap(
        params=null_v2, version="v2-kv",
        policy=SwapPolicy(
            drain_ticks=1, canary_ticks=2, canary_seconds=dt,
            canary_requests=1,
        ),
    )
    check(st["state"] == "rolling", f"swap refused: {st}")
    ticks = 0
    while (
        fe.has_work()
        or fe.swap_status()["state"] in ("rolling", "rolling_back")
    ) and ticks < 5000:
        t[0] += dt
        fe.step()
        ticks += 1
    s = fe.summary()
    check(
        fe.swap_status()["state"] == "completed",
        f"migration leg swap did not complete: {fe.swap_status()}",
    )
    check(
        all(o.status == "finished" for o in outs),
        "migration leg: failed/lost requests",
    )
    check(
        [list(o.tokens) for o in outs] == base_tokens,
        "migrated continuation diverged from the no-fault baseline",
    )
    check(s["kv_exports"] > 0, "relocation never exported KV blocks")
    check(
        s["kv_migrations"]["imported"] > 0,
        f"no relocation continued from migrated blocks: "
        f"{s['kv_migrations']}",
    )
    untyped = {
        k: v
        for k, v in s["kv_migrations"].items()
        if v and k not in ("imported", "already_cached")
    }
    check(
        not untyped,
        f"recompute fallbacks in the controlled migration leg: {untyped}",
    )

    record = {
        "bench": "serve_kv_hierarchy",
        **run_identity(cfg),
        "seed": seed,
        "workload": {
            "n_requests": n_requests,
            "zipf_s": zipf_s,
            "tenants": tenants,
            "prefix_len": prefix_len,
            "suffix_max": suffix_max,
            "new_tokens": new_tokens,
        },
        "equal_hbm": {
            "kv_block_tokens": bt,
            "kv_pool_blocks": pool_blocks,
            "n_slots": n_slots,
        },
        "aligned_lru": {
            k: rec_lru[k]
            for k in (
                "prefix_hit_rate", "prefix_hits", "prefix_misses",
                "prefix_evictions", "prefills", "prefill_calls",
                "ttft_ms_p50", "ttft_ms_p95", "tokens_per_sec", "wall_s",
            )
        },
        "radix_host": {
            **{
                k: rec_radix[k]
                for k in (
                    "prefix_hit_rate", "prefix_hits", "prefix_misses",
                    "prefix_evictions", "prefills", "prefill_calls",
                    "ttft_ms_p50", "ttft_ms_p95", "tokens_per_sec",
                    "wall_s", "prefix_entries", "prefix_entry_bytes",
                )
            },
            "kv_host_offloads": rec_radix["kv_host_offloads"],
            "kv_host_restored_blocks": (
                rec_radix["kv_host_restored_blocks"]
            ),
            "kv_host_evictions": rec_radix["kv_host_evictions"],
            "kv_host_restore_failures": (
                rec_radix["kv_host_restore_failures"]
            ),
            "host_capacity_blocks": radix_kwargs["kv_host_blocks"],
        },
        "hit_rate_win": round(hr_radix - hr_lru, 4),
        "migration": {
            "n_requests": len(mig_prompts),
            "swap_state": fe.swap_status()["state"],
            "kv_exports": s["kv_exports"],
            "kv_migrations": {
                k: v for k, v in s["kv_migrations"].items() if v
            },
            "kv_migrated_blocks": s["kv_migrated_blocks"],
            "swap_relocations": int(
                fe.registry.counter(
                    "cluster_swap_relocations_total"
                ).value
            ),
            "bitwise_exact": (
                [list(o.tokens) for o in outs] == base_tokens
            ),
        },
        "invariants_ok": not violations,
        "violations": violations,
    }
    logger.log_record(record)
    print(json.dumps(record, indent=2))
    return record, violations


def run_kv_disk_bench(model, params, cfg, *, seed, logger,
                      n_requests=96, workdir=None):
    """The SSD-KV-tier hit-rate bench (docs/10): the
    radix + host hierarchy WITH a disk tier under it vs the identical
    RAM-only hierarchy, at equal HBM pool bytes and equal RAM budgets,
    on a Zipf multi-tenant workload whose working set is far above
    ``kv_host_blocks`` — the regime the disk tier exists for.

    1. ``ram_only`` — radix tree + host offload, no disk: warm blocks
       evicted past the host budget are simply LOST and recomputed.
    2. ``disk`` — same budgets plus the SSD tier: cold host evictions
       spill to per-block-CRC'd blobs and radix hits hydrate them back.
       Invariants: strictly higher prefix hit rate than leg 1, blocks
       actually spilled AND restored (``kv_disk_spills`` /
       ``kv_disk_restores`` > 0), and ZERO restore failures (a verified
       disk hit never recomputes — the tier's integrity contract).

    TTFT for both legs rides in the record unchecked: the restart-TTFT
    claim lives in ``daemon_bench --kv-disk``, where process death
    makes the comparison honest.  Returns ``(record, violations)``.
    Pass ``model=None`` to let the bench build its own small-but-real
    model (``daemon_bench`` calls it that way).
    """
    import json
    import shutil
    import tempfile

    if model is None or cfg.seq_len < 128:
        # same reasoning as run_kv_hierarchy_bench: the hit a disk
        # restore saves is prefill COMPUTE, so the toy 32-token config
        # would measure nothing but dispatch
        from tpu_parallel.models import GPTLM, tiny_test

        cfg = tiny_test(
            remat=False, d_model=192, n_layers=4, n_heads=4, seq_len=128
        )
        model = GPTLM(cfg)
        params = model.init(
            {"params": jax.random.PRNGKey(seed + 1)},
            jax.numpy.zeros((1, cfg.seq_len - 4), jax.numpy.int32),
            train=False,
        )["params"]

    bt = max(1, cfg.seq_len // 4)
    prefix_len = 2 * bt  # every tenant header spans two full blocks
    new_tokens = 2
    suffix_max = max(
        2, min(cfg.seq_len // 3, cfg.seq_len - prefix_len - new_tokens - 2)
    )
    zipf_s, tenants = 1.2, 12
    prompts, _ = make_zipf_prompts(
        cfg, n_requests=n_requests, prompt_min=1, prompt_max=suffix_max,
        prefix_len=prefix_len, seed=seed, zipf_s=zipf_s, tenants=tenants,
    )
    n_slots = 4
    pool_blocks = 2 * n_slots * cfg.seq_len // bt  # EQUAL both legs
    # RAM budgets far below the working set (tenants * 2 header blocks),
    # IDENTICAL in both legs: the only difference is the tier under them
    ram_kwargs = dict(
        kv_block_tokens=bt, kv_pool_blocks=pool_blocks,
        prefill_buckets=(bt, 2 * bt, 4 * bt),
        prefix_cache_size=6, kv_radix_cache=True, kv_host_blocks=4,
    )
    working_set_blocks = tenants * (prefix_len // bt)
    disk_dir = tempfile.mkdtemp(
        prefix="kv_disk_bench_", dir=workdir or None
    )
    disk_kwargs = dict(
        ram_kwargs, kv_disk_dir=disk_dir,
        kv_disk_blocks=4 * working_set_blocks,
    )

    violations = []

    def check(cond, msg):
        if not cond:
            violations.append(msg)

    _, rec_ram = run_point(
        model, params, cfg, prompts, rate=0.0, n_slots=n_slots,
        new_tokens=new_tokens, seed=seed, engine_kwargs=ram_kwargs,
        label="ram_only",
    )
    _, rec_disk = run_point(
        model, params, cfg, prompts, rate=0.0, n_slots=n_slots,
        new_tokens=new_tokens, seed=seed, engine_kwargs=disk_kwargs,
        label="disk",
    )
    hr_ram = rec_ram["prefix_hit_rate"] or 0.0
    hr_disk = rec_disk["prefix_hit_rate"] or 0.0
    check(
        hr_disk > hr_ram,
        f"disk-tier hit rate {hr_disk} not above RAM-only {hr_ram} at "
        f"a {working_set_blocks}-block working set over "
        f"{ram_kwargs['kv_host_blocks']} host blocks",
    )
    # spills mostly happen during the warmup pass (retained blobs never
    # re-spill), and run_point's reset_metrics zeroes the spill tally
    # before the measured window — so the evidence that cold host
    # evictions reached disk is the tier's *contents*, which survive
    # the reset: resident blobs and live manifest records
    check(
        rec_disk.get("kv_disk_blocks", 0) > 0
        and rec_disk.get("kv_disk_manifest_records", 0) > 0,
        "no cold host eviction ever spilled to the disk tier "
        f"(blobs={rec_disk.get('kv_disk_blocks')}, manifest "
        f"records={rec_disk.get('kv_disk_manifest_records')})",
    )
    check(
        rec_disk.get("kv_disk_restores", 0) > 0,
        "no warm block ever restored from the disk tier",
    )
    check(
        rec_disk.get("kv_disk_restore_failures", 0) == 0,
        f"{rec_disk.get('kv_disk_restore_failures')} disk-tier hits "
        "fell back to recompute (restore failures)",
    )

    keys = (
        "prefix_hit_rate", "prefix_hits", "prefix_misses",
        "prefills", "prefill_calls", "ttft_ms_p50", "ttft_ms_p95",
        "tokens_per_sec", "wall_s",
    )
    record = {
        "bench": "serve_kv_disk",
        **run_identity(cfg),
        "seed": seed,
        "workload": {
            "n_requests": n_requests,
            "zipf_s": zipf_s,
            "tenants": tenants,
            "prefix_len": prefix_len,
            "suffix_max": suffix_max,
            "new_tokens": new_tokens,
            "working_set_blocks": working_set_blocks,
        },
        "equal_budgets": {
            "kv_block_tokens": bt,
            "kv_pool_blocks": pool_blocks,
            "n_slots": n_slots,
            "prefix_cache_size": ram_kwargs["prefix_cache_size"],
            "kv_host_blocks": ram_kwargs["kv_host_blocks"],
        },
        "ram_only": {k: rec_ram[k] for k in keys},
        "disk": {
            **{k: rec_disk[k] for k in keys},
            **{
                k: rec_disk.get(k)
                for k in (
                    "kv_disk_blocks", "kv_disk_bytes",
                    "kv_disk_spills", "kv_disk_restores",
                    "kv_disk_restore_failures", "kv_disk_breaker_trips",
                    "kv_disk_manifest_records",
                    "kv_disk_manifest_compactions",
                )
            },
            "disk_capacity_blocks": disk_kwargs["kv_disk_blocks"],
        },
        "hit_rate_win": round(hr_disk - hr_ram, 4),
        "invariants_ok": not violations,
        "violations": violations,
    }
    logger.log_record(record)
    print(json.dumps(record, indent=2))
    shutil.rmtree(disk_dir, ignore_errors=True)
    return record, violations


def run_unified_bench(model, params, cfg, *, seed, logger, n_requests=24):
    """The UNIFIED ragged tick vs the per-phase ALTERNATING
    engine under a mixed prefill+decode Zipf workload — long multi-chunk
    prompts (tenant headers drawn Zipf, so the prefix load is realistic)
    continuously interleaving with in-flight decodes.  Three legs on
    the IDENTICAL workload: alternating (per-slot chunk extends + fused
    decode dispatch, ``unified_tick=False``), unified (one dispatch per
    tick), and the speculative pair (per-step verify vs fused verify
    blocks).  Gates: every leg bitwise-identical to its baseline; the
    unified tick cuts device dispatches per delivered token >= 2x vs
    alternating; ITL p95 no worse; ``step()`` launched busy ticks ahead
    of their predecessor's collect on the unified leg."""
    import json
    import time as _time

    from tpu_parallel.serving import (
        Request, SchedulerConfig, ServingEngine,
    )

    if cfg.seq_len < 128:
        # the CPU tiny default's 32-token window can't hold multi-chunk
        # prompts plus a decode run — build the bench's own small-but-
        # real model (the kv-hierarchy bench's pattern)
        from tpu_parallel.models import GPTLM, tiny_test

        cfg = tiny_test(
            dtype=jax.numpy.float32, remat=False, d_model=128,
            n_layers=3, n_heads=4, seq_len=128,
        )
        model = GPTLM(cfg)
        params = model.init(
            {"params": jax.random.PRNGKey(1)},
            jax.numpy.ones((1, 8), jax.numpy.int32), train=False,
        )["params"]
    chunk = cfg.seq_len // 8           # 16 at seq 128: 3-6 chunks/prompt
    new_tokens = cfg.seq_len // 8 + 2  # decode long enough to interleave
    prefix_len = chunk
    pmax = cfg.seq_len - new_tokens - prefix_len - 1
    prompts, _ = make_zipf_prompts(
        cfg, n_requests=n_requests, prompt_min=chunk + 2,
        prompt_max=pmax, prefix_len=prefix_len, seed=seed, zipf_s=1.1,
        tenants=8,
    )
    legs = {
        "alternating": dict(
            prefill_chunk_tokens=chunk, decode_steps_per_tick=8,
            unified_tick=False,
        ),
        "unified": dict(
            prefill_chunk_tokens=chunk, decode_steps_per_tick=8,
        ),
        "alternating_spec": dict(
            prefill_chunk_tokens=chunk, decode_steps_per_tick=1,
            draft_tokens=3,
        ),
        "unified_spec": dict(
            prefill_chunk_tokens=chunk, decode_steps_per_tick=8,
            draft_tokens=3,
        ),
    }
    results, tokens_by_leg = {}, {}
    for leg, kwargs in legs.items():
        eng = ServingEngine(
            model, params, n_slots=4,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            rng=jax.random.PRNGKey(seed), **kwargs,
        )
        outs = [
            eng.add_request(Request(prompt=p, max_new_tokens=new_tokens))
            for p in prompts
        ]
        # one warm drain compiles every shape, then measure from clean
        # metrics on the SAME engine (long-lived server discipline)
        eng.run()
        warm_tokens = [list(o.tokens) for o in outs]
        eng.reset_metrics()
        outs = [
            eng.add_request(Request(prompt=p, max_new_tokens=new_tokens))
            for p in prompts
        ]
        t0 = _time.perf_counter()
        eng.run()
        wall = _time.perf_counter() - t0
        tokens_by_leg[leg] = [list(o.tokens) for o in outs]
        assert tokens_by_leg[leg] == warm_tokens  # warm == measured
        s = eng.metrics.summary()
        results[leg] = {
            "host_dispatches": s["host_dispatches"],
            "tokens_out": s["tokens_out"],
            "dispatches_per_token": round(
                s["host_dispatches"] / max(s["tokens_out"], 1), 4
            ),
            "prefill_chunks": s["prefill_chunks"],
            "ticks": s["ticks"],
            "itl_ms_p50": s["itl_ms_p50"],
            "itl_ms_p95": s["itl_ms_p95"],
            "ttft_ms_p95": s["ttft_ms_p95"],
            "host_ms_per_tick_p50": s["host_ms_per_tick_p50"],
            "host_ms_per_tick_p95": s["host_ms_per_tick_p95"],
            "launch_ahead_share": s["launch_ahead_share"],
            "unified_tick_tokens_mean": s["unified_tick_tokens_mean"],
            "tokens_per_sec": s["tokens_per_sec"],
            "wall_s": round(wall, 3),
        }
    violations = []
    for base, fast in (
        ("alternating", "unified"),
        ("alternating_spec", "unified_spec"),
        # spec-vs-nonspec greedy parity closes the square
        ("alternating", "alternating_spec"),
    ):
        if tokens_by_leg[base] != tokens_by_leg[fast]:
            bad = sum(
                1 for a, b in zip(tokens_by_leg[base], tokens_by_leg[fast])
                if a != b
            )
            violations.append(
                f"{fast} diverged from {base} on {bad}/{n_requests} "
                "requests"
            )
    cut = (
        results["alternating"]["dispatches_per_token"]
        / max(results["unified"]["dispatches_per_token"], 1e-9)
    )
    if cut < 2.0:
        violations.append(
            f"unified dispatch cut {cut:.2f}x < 2x vs alternating"
        )
    if results["unified"]["launch_ahead_share"] <= 0:
        violations.append("the unified leg launched no tick ahead")
    itl_base = results["alternating"]["itl_ms_p95"]
    itl_uni = results["unified"]["itl_ms_p95"]
    if itl_base is not None and itl_uni is not None and (
        itl_uni > itl_base * 1.05
    ):
        violations.append(
            f"unified ITL p95 {itl_uni}ms regressed vs alternating "
            f"{itl_base}ms"
        )
    record = {
        "bench": "serve_unified",
        **run_identity(cfg),
        "seq_len": cfg.seq_len,
        "n_requests": n_requests,
        "n_slots": 4,
        "prompt_zipf": "1.1:8",
        "prefill_chunk_tokens": chunk,
        "new_tokens": new_tokens,
        "decode_steps_per_tick": 8,
        "dispatch_cut_vs_alternating": round(cut, 2),
        "legs": results,
        "bitwise_ok": not any("diverged" in v for v in violations),
        "invariants_ok": not violations,
        "violations": violations,
    }
    logger.log_record(record)
    print(json.dumps(record, indent=2))
    return record, violations


class _GarbageDrafter:
    """Adversarial smoke drafter: drafts one more than the true greedy
    next token (it knows the references), so every draft is wrong and the
    spec engine must survive on pure rejection.  (Scripts stay
    self-contained: this mirrors ``tests/_spec_drafters.AntiOracleDrafter``
    rather than importing from the test tree.)"""

    def __init__(self, refs_by_prompt, vocab):
        self.refs = refs_by_prompt
        self.vocab = vocab

    def draft(self, context, k):
        for prompt, ref in self.refs.items():
            if tuple(context[: len(prompt)]) == prompt:
                idx = len(context) - len(prompt)
                truth = int(ref[idx]) if idx < len(ref) else 0
                return [(truth + 1) % self.vocab] * k
        return [0] * k


def smoke(model, params, cfg, prompts, new_tokens):
    """Greedy parity gate: every fast-path mode — including the
    SPECULATIVE engine, with both the real n-gram drafter and an
    adversarial all-wrong drafter — must match static generate()
    token-for-token on every prompt (the non-spec engine modes are pinned
    against the same references, so spec-vs-nonspec parity is implied).
    Each mode's metric-registry snapshot is additionally validated
    against the exporter schema (``obs.validate_snapshot``), so a bench
    record can never come from a registry an exporter would choke on.
    Returns the number of mismatched (mode, request) pairs + schema
    problems."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_parallel.models.generate import generate
    from tpu_parallel.obs import validate_snapshot
    from tpu_parallel.serving import Request, SchedulerConfig, ServingEngine

    refs = [
        np.asarray(
            generate(
                model, params, jnp.asarray(p, jnp.int32)[None, :],
                max_new_tokens=new_tokens,
            )
        )[0]
        for p in prompts
    ]
    refs_by_prompt = {
        tuple(p): [int(t) for t in ref] for p, ref in zip(prompts, refs)
    }
    shortest = min(len(p) for p in prompts)
    # "bucketed"/"chunked"/"prefix" run the engine DEFAULT fused tick
    # (decode_steps_per_tick auto=8); "per_step" pins the T=1 engine and
    # "fused_chunked" the fused tick composed with chunked prefill, so a
    # fused-vs-per-step divergence fails the gate from both directions
    modes = {
        "exact": dict(prefill_buckets=None),
        "bucketed": {},
        "per_step": dict(decode_steps_per_tick=1),
        "fused_chunked": dict(
            decode_steps_per_tick=4,
            prefill_chunk_tokens=max(2, shortest // 2),
            unified_tick=False,  # the per-phase pin the unified modes beat
        ),
        "chunked": dict(prefill_chunk_tokens=max(2, shortest // 2)),
        # the UNIFIED ragged tick: chunked prefill + fused decode in ONE
        # dispatch per tick (in-device final-chunk activation), and its
        # speculative form (T draft-verify blocks per dispatch with
        # in-scan NGram drafting) — both gated bitwise against static
        # generate() like every other mode; "chunked"/"fused_chunked"
        # above pin the per-phase (unified_tick=False is implied at T=4
        # only for fused_chunked's explicit pin) baselines they must
        # match
        "unified": dict(
            prefill_chunk_tokens=max(2, shortest // 2),
            decode_steps_per_tick=8, unified_tick=True,
        ),
        "unified_spec": dict(
            prefill_chunk_tokens=max(2, shortest // 2),
            decode_steps_per_tick=4, draft_tokens=3,
        ),
        "prefix": dict(prefix_cache_size=4),
        "spec": dict(draft_tokens=3),
        "spec_adversarial": dict(
            draft_tokens=3,
            drafter=_GarbageDrafter(refs_by_prompt, cfg.vocab_size),
        ),
        # block-paged KV pool: same gates over the paged layout (default
        # fused tick; prefix sharing + COW; speculative verify) — paged
        # greedy output must match static generate() bitwise too
        "paged": dict(kv_block_tokens="auto"),
        "paged_prefix": dict(kv_block_tokens="auto", prefix_cache_size=4),
        "paged_spec": dict(kv_block_tokens="auto", draft_tokens=3),
        # hierarchical KV memory: radix tree (block-granular prefix
        # matching) alone and with the host offload tier squeezed so
        # spill/restore actually runs inside the parity gate
        "radix": dict(
            kv_block_tokens=max(2, cfg.seq_len // 4),
            kv_radix_cache=True, prefix_cache_size=8,
        ),
        "radix_host": dict(
            kv_block_tokens=max(2, cfg.seq_len // 4),
            kv_radix_cache=True, prefix_cache_size=2, kv_host_blocks=8,
        ),
    }
    failures = 0
    for name, kwargs in modes.items():
        eng = ServingEngine(
            model, params, n_slots=4,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            **kwargs,
        )
        outs = [
            eng.add_request(Request(prompt=p, max_new_tokens=new_tokens))
            for p in prompts
        ]
        eng.run()
        for i, (out, ref) in enumerate(zip(outs, refs)):
            if out.status != "finished" or list(out.tokens) != list(ref):
                print(
                    f"SMOKE FAIL [{name}] request {i}: "
                    f"{out.status} {out.tokens} != {list(ref)}",
                    file=sys.stderr,
                )
                failures += 1
        for problem in validate_snapshot(eng.registry.snapshot()):
            print(
                f"SMOKE FAIL [{name}] registry snapshot: {problem}",
                file=sys.stderr,
            )
            failures += 1
    # SLO-autopilot invariant gate: a compact deterministic fake-clock
    # overload run — the controller must keep non-shed deadline misses
    # under 5%, bound queue-age p95, respect the shed-fraction bound,
    # and keep every finished request bitwise identical to the
    # single-engine baseline (the standalone --autopilot bench adds the
    # action-log determinism re-run on top)
    _, ap_problems = run_autopilot_bench(model, params, cfg, seed=0)
    for problem in ap_problems:
        print(f"SMOKE FAIL [autopilot] {problem}", file=sys.stderr)
        failures += 1
    print(
        "smoke: PASS" if failures == 0 else f"smoke: {failures} FAILURES"
    )
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=str, default="8,0")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--new", type=int, default=0,
                    help="tokens per request (0 = model-dependent default)")
    ap.add_argument("--prompt-min", type=int, default=0)
    ap.add_argument("--prompt-max", type=int, default=0)
    ap.add_argument("--prompt-dist", action="store_true",
                    help="prefix-shared mixed-length prompt distribution")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared system-prefix tokens (--prompt-dist; "
                         "0 = backend default)")
    ap.add_argument("--buckets", type=str, default="auto",
                    help="'auto', 'off', or comma-separated bucket sizes")
    ap.add_argument("--chunk", type=int, default=0,
                    help="prefill chunk budget (0 = off)")
    ap.add_argument("--prefix-cache", type=int, default=0,
                    help="prefix-cache LRU entries (0 = off)")
    ap.add_argument("--spec", type=int, default=0,
                    help="speculative decode draft tokens (0 = off); the "
                         "record then carries acceptance rate and "
                         "tokens_per_decode_tick")
    ap.add_argument("--kv-block-tokens", type=str, default="0",
                    help="block-paged KV cache: tokens per block, or "
                         "'auto' for the bucket quantum (0 = fixed-slot "
                         "layout)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="paged pool capacity in blocks (0 = engine "
                         "default n_slots * seq_len / block_tokens)")
    ap.add_argument("--kv-radix", action="store_true",
                    help="hierarchical KV memory: radix prefix tree "
                         "instead of the aligned-LRU prefix cache "
                         "(needs --kv-block-tokens and --prefix-cache, "
                         "which then bounds resident device blocks)")
    ap.add_argument("--kv-host-blocks", type=int, default=0,
                    help="host-RAM KV offload tier capacity in blocks "
                         "(implies --kv-radix; 0 = off)")
    ap.add_argument("--prompt-zipf", type=str, default="",
                    help="Zipf multi-tenant prompt mix as S:TENANTS "
                         "(e.g. 1.2:16): tenant headers drawn with "
                         "weight 1/rank^S on a child rng — the arrival "
                         "stream stays bit-identical to unshaped "
                         "schedules at the same --seed")
    ap.add_argument("--kv-bench", action="store_true",
                    help="hierarchical-KV acceptance bench: "
                         "radix+host vs aligned-LRU at equal HBM pool "
                         "bytes on a Zipf multi-tenant mix, plus the "
                         "KV-migration relocation leg; nonzero exit on "
                         "any invariant violation")
    ap.add_argument("--kv-record", type=str, default="",
                    help="kv-bench: write the record to this JSON file")
    ap.add_argument("--kv-disk", action="store_true",
                    help="SSD-KV-tier hit-rate bench: disk-backed vs "
                         "RAM-only hierarchy at equal RAM budgets on a "
                         "working set far above kv_host_blocks; "
                         "nonzero exit on any invariant violation")
    ap.add_argument("--kv-disk-record", type=str, default="",
                    help="kv-disk: write the record to this JSON file")
    ap.add_argument("--unified-bench", action="store_true",
                    help="unified-ragged-tick acceptance bench: "
                         "alternating vs unified engines "
                         "on a mixed prefill+decode Zipf workload at "
                         "equal budgets — bitwise parity, >= 2x "
                         "dispatch cut per token, ticks launched "
                         "ahead; nonzero exit on any violation")
    ap.add_argument("--unified-record", type=str, default="",
                    help="unified-bench: write the record to this JSON "
                         "file")
    ap.add_argument("--capacity-probe", action="store_true",
                    help="emit a serve_paged_capacity record: concurrent "
                         "short-request admissions and burst decode "
                         "throughput, fixed-slot vs paged at EQUAL pool "
                         "bytes")
    ap.add_argument("--fused-tick", type=int, default=0,
                    help="decode_steps_per_tick for the measured engines "
                         "(0 = engine default 'auto'; 1 = the per-step "
                         "tick, the pre-fused configuration)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the cluster frontend "
                         "(1 = single-engine mode, the pre-cluster bench)")
    ap.add_argument("--router", type=str, default="least",
                    help="cluster routing policy or comma list to "
                         "compare: rr | least | prefix")
    ap.add_argument("--fault", action="store_true",
                    help="cluster mode: alias for --fault-spec 0:crash@8 "
                         "(the historical crash-one-replica scenario)")
    ap.add_argument("--fault-spec", type=str, default="",
                    help="cluster mode: per-replica faults as a comma "
                         "list of RID:KIND@ARG — crash@T | stall@T+N | "
                         "flap@K | reject@T+N (e.g. "
                         "'0:crash@8,1:stall@4+6,2:flap@10')")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="cluster mode: draw every replica's fault "
                         "schedule from FaultPlan.from_seed(SEED) with "
                         "self-healing armed; the record carries the "
                         "fault-storm counters")
    ap.add_argument("--trace-record", type=str, default="",
                    help="dump the generated request schedule (arrival, "
                         "prompt, prefix-group, priority, deadline) as "
                         "JSONL — the workload-replay exchange format")
    ap.add_argument("--trace-replay", "--workload", type=str,
                    default="", dest="trace_replay",
                    help="re-feed a recorded schedule — a --trace-record "
                         "file OR a daemon write-ahead journal (same "
                         "workload schema) — instead of generating one "
                         "(overrides --requests/--rate workload shape)")
    ap.add_argument("--time-compress", type=float, default=1.0,
                    help="divide every replayed arrival time by this "
                         "factor (10 = day-in-the-life at 10x speed)")
    ap.add_argument("--swap-bench", action="store_true",
                    help="deterministic rolling weight hot-swap bench "
                         "on a fake clock: baseline / swap / "
                         "injected-regression legs over one schedule; "
                         "nonzero exit on any invariant violation")
    ap.add_argument("--swap-at", type=int, default=12,
                    help="swap-bench: cluster tick the rollout starts at")
    ap.add_argument("--swap-dt", type=float, default=0.05,
                    help="swap-bench: fake-clock seconds per tick")
    ap.add_argument("--swap-record", type=str, default="",
                    help="swap-bench: write the record to this JSON file")
    ap.add_argument("--autopilot", action="store_true",
                    help="SLO-autopilot overload bench on a fake clock: "
                         "no-autopilot vs autopilot legs over one seeded "
                         "2x-overload schedule + action-log determinism "
                         "re-run; nonzero exit on any invariant violation")
    ap.add_argument("--autopilot-record", type=str, default="",
                    help="autopilot bench: write the record to this JSON "
                         "file")
    ap.add_argument("--priority-dist", type=str, default="",
                    help="weighted priority classes for the generated "
                         "schedule, VALUE:WEIGHT,... (e.g. '0:6,1:3,2:1')")
    ap.add_argument("--deadline-dist", type=str, default="",
                    help="weighted per-request deadlines (seconds) for "
                         "the generated schedule, VALUE:WEIGHT,... with "
                         "'none' for no deadline (e.g. '2.0:3,none:1')")
    ap.add_argument("--prefix-groups", type=int, default=4,
                    help="distinct shared system-headers in the "
                         "--prompt-dist workload (cluster mode: the "
                         "prefix-affinity placement unit)")
    ap.add_argument("--compare", action="store_true",
                    help="emit every point twice: exact prefill vs "
                         "the requested fast path")
    ap.add_argument("--smoke", action="store_true",
                    help="run the fast-path parity gate (+ registry "
                         "snapshot schema check); nonzero exit on "
                         "mismatch")
    ap.add_argument("--trace-out", type=str, default="",
                    help="write a Chrome trace-event JSON of every "
                         "measured point's request lifecycles "
                         "(Perfetto-openable)")
    ap.add_argument("--metrics-out", type=str, default="",
                    help="write the last point's registry snapshot as "
                         "Prometheus text exposition")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default="serve_bench")
    ap.add_argument("--model", choices=("tiny", "gpt2_125m"),
                    default="tiny",
                    help="the served model: the 0.1M-parameter test config "
                         "(the CPU gates) or full-width GPT-2 125M (a chip)")
    args = ap.parse_args()

    from tpu_parallel.models import GPTLM, gpt2_125m, tiny_test
    from tpu_parallel.utils.logging_utils import MetricLogger

    # the caller names the model (--model); the backend never picks it, and
    # every record says where it ran (run_identity)
    real = args.model == "gpt2_125m"
    cfg = (
        gpt2_125m(dropout_rate=0.0, remat=False)
        if real
        else tiny_test(remat=False)
    )
    new_tokens = args.new or (64 if real else 8)
    if args.prompt_dist:
        prefix_len = args.prefix_len or (128 if real else 8)
        prompt_min = args.prompt_min or 1
        prompt_max = args.prompt_max or (
            min(384, cfg.seq_len - new_tokens - prefix_len) if real
            else cfg.seq_len - new_tokens - prefix_len - 3
        )
    else:
        prefix_len = 0
        prompt_min = args.prompt_min or (128 if real else 3)
        prompt_max = args.prompt_max or (
            min(512, cfg.seq_len - new_tokens) if real
            else cfg.seq_len - new_tokens - 2
        )
    model = GPTLM(cfg)
    probe = jax.numpy.zeros((1, prompt_max + prefix_len), jax.numpy.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]
    if args.prompt_zipf:
        zipf_s, zipf_tenants = parse_zipf(args.prompt_zipf)
        zp_len = args.prefix_len or (128 if real else 8)
        zp_max = max(1, prompt_max - zp_len + prefix_len)
        if zp_max < prompt_min:
            raise SystemExit(
                f"--prompt-zipf: suffix range empty — prompt_max "
                f"{prompt_max} leaves {zp_max} suffix tokens after the "
                f"{zp_len}-token tenant header, below --prompt-min "
                f"{prompt_min}; raise --prompt-max or lower "
                "--prefix-len/--prompt-min"
            )
        prompts, groups = make_zipf_prompts(
            cfg, n_requests=args.requests, prompt_min=prompt_min,
            prompt_max=zp_max,
            prefix_len=zp_len, seed=args.seed, zipf_s=zipf_s,
            tenants=zipf_tenants,
        )
    else:
        prompts, groups = make_prompts(
            cfg, n_requests=args.requests, prompt_min=prompt_min,
            prompt_max=prompt_max, prefix_len=prefix_len, seed=args.seed,
            prefix_groups=(args.prefix_groups if args.prompt_dist else 1),
        )
    rates = [float(r) for r in args.rate.split(",")]

    # workload-replay harness: --trace-record dumps the first rate
    # point's schedule; --trace-replay swaps the generated workload for
    # a recorded one (time-compressed), feeding the SAME runners
    replay = None
    priority_dist = (
        parse_dist(args.priority_dist) if args.priority_dist else None
    )
    deadline_dist = (
        parse_dist(args.deadline_dist) if args.deadline_dist else None
    )
    if args.trace_replay:
        replay = load_trace(args.trace_replay, args.time_compress)
        rates = rates[:1]  # the trace IS the arrival process
    if args.trace_record:
        recorded = write_trace(
            args.trace_record,
            build_schedule(prompts, groups, rates[0], args.seed,
                           new_tokens, priority_dist=priority_dist,
                           deadline_dist=deadline_dist),
            meta=dict(
                seed=args.seed, rate=rates[0],
                n_requests=args.requests, new_tokens=new_tokens,
                prefix_groups=(
                    args.prefix_groups if args.prompt_dist else 1
                ),
                prompt_zipf=args.prompt_zipf or None,
                priority_dist=args.priority_dist or None,
                deadline_dist=args.deadline_dist or None,
            ),
        )
        print(f"trace recorded: {recorded}")

    if args.unified_bench:
        import json

        logger = MetricLogger(logdir=".", name=args.out)
        record, violations = run_unified_bench(
            model, params, cfg, seed=args.seed, logger=logger,
            n_requests=min(args.requests, 24),
        )
        logger.close()
        if args.unified_record:
            with open(args.unified_record, "w") as fh:
                json.dump(record, fh, indent=2)
                fh.write("\n")
            print(f"record: {args.unified_record}")
        if violations:
            print(
                f"unified_bench: {len(violations)} INVARIANT "
                "VIOLATION(S)",
                file=sys.stderr,
            )
            sys.exit(1)
        print("unified_bench: all invariants held")
        return

    if args.kv_bench:
        import json

        logger = MetricLogger(logdir=".", name=args.out)
        record, violations = run_kv_hierarchy_bench(
            model, params, cfg, seed=args.seed, logger=logger,
        )
        logger.close()
        if args.kv_record:
            with open(args.kv_record, "w") as fh:
                json.dump(record, fh, indent=2)
                fh.write("\n")
            print(f"record: {args.kv_record}")
        if violations:
            print(
                f"kv_bench: {len(violations)} INVARIANT VIOLATION(S)",
                file=sys.stderr,
            )
            sys.exit(1)
        print("kv_bench: all invariants held")
        return

    if args.kv_disk:
        import json

        logger = MetricLogger(logdir=".", name=args.out)
        record, violations = run_kv_disk_bench(
            model, params, cfg, seed=args.seed, logger=logger,
        )
        logger.close()
        if args.kv_disk_record:
            with open(args.kv_disk_record, "w") as fh:
                json.dump(record, fh, indent=2)
                fh.write("\n")
            print(f"record: {args.kv_disk_record}")
        if violations:
            print(
                f"kv_disk_bench: {len(violations)} INVARIANT "
                "VIOLATION(S)",
                file=sys.stderr,
            )
            sys.exit(1)
        print("kv_disk_bench: all invariants held")
        return

    if args.autopilot:
        import json

        logger = MetricLogger(logdir=".", name=args.out)
        record, violations = run_autopilot_bench(
            model, params, cfg, router=args.router.split(",")[0],
            seed=args.seed, determinism_check=True, logger=logger,
        )
        logger.close()
        print(json.dumps(record, indent=2))
        if args.autopilot_record:
            with open(args.autopilot_record, "w") as fh:
                json.dump(record, fh, indent=2)
                fh.write("\n")
            print(f"record: {args.autopilot_record}")
        if violations:
            print(
                f"autopilot_bench: {len(violations)} INVARIANT "
                "VIOLATION(S)",
                file=sys.stderr,
            )
            sys.exit(1)
        print("autopilot_bench: all invariants held")
        return

    if args.swap_bench:
        import json

        from tpu_parallel.utils.logging_utils import MetricLogger

        schedule = replay if replay is not None else build_schedule(
            prompts, groups, rates[0], args.seed, new_tokens
        )
        logger = MetricLogger(logdir=".", name=args.out)
        record, violations = run_swap_bench(
            model, params, cfg, schedule,
            n_replicas=max(2, args.replicas), n_slots=args.slots,
            router=args.router.split(",")[0], seed=args.seed,
            dt=args.swap_dt, swap_at_tick=args.swap_at, logger=logger,
        )
        record["workload"] = (
            {"trace_replay": args.trace_replay,
             "time_compress": args.time_compress}
            if replay is not None
            else "generated"
        )
        logger.close()
        print(json.dumps(record, indent=2))
        if args.swap_record:
            with open(args.swap_record, "w") as fh:
                json.dump(record, fh, indent=2)
                fh.write("\n")
            print(f"record: {args.swap_record}")
        if violations:
            print(
                f"swap_bench: {len(violations)} INVARIANT VIOLATION(S)",
                file=sys.stderr,
            )
            sys.exit(1)
        print("swap_bench: all invariants held")
        return

    if args.smoke:
        failures = smoke(model, params, cfg, prompts[:6], new_tokens)
        if failures:
            sys.exit(1)

    if args.buckets == "off":
        fast = dict(prefill_buckets=None)
        fast_label = "exact"
    else:
        if args.buckets == "auto" and args.prompt_dist:
            # align buckets on the shared prefix so prefix reuse can key
            # off a bucket boundary (the engine appends seq_len itself)
            buckets = tuple(
                b for b in (prefix_len, prefix_len * 2, prefix_len * 4)
                if b < cfg.seq_len
            )
        elif args.buckets == "auto":
            buckets = "auto"
        else:
            buckets = tuple(int(b) for b in args.buckets.split(","))
        fast = dict(prefill_buckets=buckets)
        fast_label = "bucketed"
    if args.chunk > 0:
        fast["prefill_chunk_tokens"] = args.chunk
    if args.prefix_cache > 0:
        fast["prefix_cache_size"] = args.prefix_cache
    if args.spec > 0:
        fast["draft_tokens"] = args.spec
        fast_label += "+spec"
    if args.fused_tick > 0:
        fast["decode_steps_per_tick"] = args.fused_tick
        if args.fused_tick == 1:
            fast_label += "+per_step"
    if args.kv_block_tokens not in ("0", ""):
        fast["kv_block_tokens"] = (
            "auto"
            if args.kv_block_tokens == "auto"
            else int(args.kv_block_tokens)
        )
        if args.kv_pool_blocks > 0:
            fast["kv_pool_blocks"] = args.kv_pool_blocks
        fast_label += "+paged"
    if args.kv_radix or args.kv_host_blocks > 0:
        fast["kv_radix_cache"] = True
        if args.kv_host_blocks > 0:
            fast["kv_host_blocks"] = args.kv_host_blocks
        fast_label += "+radix"

    if args.replicas > 1:
        # cluster mode: one record per (rate, router policy) on the SAME
        # workload, so policies compare apples to apples (--compare is a
        # single-engine knob; the policy list IS the comparison here)
        if args.compare:
            print(
                "serve_bench: --compare ignored with --replicas > 1 "
                "(compare router policies via --router rr,least,prefix)",
                file=sys.stderr,
            )
        fault_spec = args.fault_spec
        if args.fault and not fault_spec:
            fault_spec = "0:crash@8"  # the pre-PR-8 hardcoded scenario
        if fault_spec and args.chaos is not None:
            raise SystemExit(
                "--chaos and --fault/--fault-spec are mutually exclusive "
                "(chaos mode draws every replica's schedule from the seed)"
            )
        fault_plans = parse_fault_spec(fault_spec) if fault_spec else None
        if fault_plans:
            bad = [r for r in fault_plans if r >= args.replicas]
            if bad:
                raise SystemExit(
                    f"--fault-spec names replicas {bad} but only "
                    f"{args.replicas} exist"
                )
        tracer = None
        if args.trace_out:
            from tpu_parallel.obs import Tracer

            tracer = Tracer()
        logger = MetricLogger(logdir=".", name=args.out)
        warm = True
        fe = None
        for rate in rates:
            for policy in args.router.split(","):
                fe, record = run_cluster_point(
                    model, params, cfg, prompts,
                    rate=rate, n_replicas=args.replicas, router=policy,
                    n_slots=args.slots, new_tokens=new_tokens,
                    seed=args.seed, engine_kwargs=dict(fast),
                    fault_plans=fault_plans, chaos_seed=args.chaos,
                    warm=warm, tracer=tracer, schedule=replay,
                    priority_dist=priority_dist,
                    deadline_dist=deadline_dist,
                )
                if fault_spec:
                    record["fault_spec"] = fault_spec
                if replay is not None:
                    record["trace_replay"] = args.trace_replay
                    record["time_compress"] = args.time_compress
                warm = False  # jits shared per model: warm once
                logger.log_record(record)
        logger.close()
        if tracer is not None:
            from tpu_parallel.obs import write_chrome_trace

            print(f"trace: {write_chrome_trace(tracer, args.trace_out)}")
        if args.metrics_out and fe is not None:
            from tpu_parallel.obs import write_prometheus

            print(
                "metrics: "
                f"{write_prometheus(fe.registry, args.metrics_out)}"
            )
        return

    configs = [(fast_label, fast)]
    if args.compare and fast_label != "exact":
        configs.insert(0, ("exact", dict(prefill_buckets=None)))

    tracer = None
    if args.trace_out:
        from tpu_parallel.obs import Tracer

        tracer = Tracer()

    logger = MetricLogger(logdir=".", name=args.out)
    if args.capacity_probe:
        run_capacity_probe(model, params, cfg, seed=args.seed,
                           logger=logger)
    eng = None
    for rate in rates:
        for label, engine_kwargs in configs:
            eng, record = run_point(
                model, params, cfg, prompts,
                rate=rate, n_slots=args.slots, new_tokens=new_tokens,
                seed=args.seed, engine_kwargs=engine_kwargs, label=label,
                tracer=tracer, schedule=replay,
                priority_dist=priority_dist, deadline_dist=deadline_dist,
            )
            if replay is not None:
                record["trace_replay"] = args.trace_replay
                record["time_compress"] = args.time_compress
            logger.log_record(record)
    logger.close()

    if tracer is not None:
        from tpu_parallel.obs import write_chrome_trace

        print(f"trace: {write_chrome_trace(tracer, args.trace_out)}")
    if args.metrics_out and eng is not None:
        from tpu_parallel.obs import write_prometheus

        print(f"metrics: {write_prometheus(eng.registry, args.metrics_out)}")


if __name__ == "__main__":
    main()
