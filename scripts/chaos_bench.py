"""Chaos-soak harness: seeded randomized fault storms against the
self-healing cluster, with hard fleet invariants.

A production scheduler treats recovery as the COMMON case; this harness
proves it.  From one seed it draws a per-replica fault schedule
(``FaultPlan.from_seed`` — crashes, observed stalls, flapping
crash-loops, admission-reject windows, mixed per replica), drives a
request stream through the :class:`~tpu_parallel.cluster.Frontend` with
the progress watchdog and :class:`~tpu_parallel.cluster.RestartPolicy`
circuit breaker armed, and asserts the invariants the self-healing
story stands on:

1. **Termination** — every accepted request reaches a terminal state
   (nothing pends forever through a full-fleet flap).
2. **Exactness** — every request FINISHES and its greedy token stream is
   bitwise identical to a no-fault single-engine baseline, through every
   crash, watchdog kill, restart and probation hand-off.
3. **No leaks** — zero open token-budget reservations at the end, and
   every live replica's cache pool is fully released with aligned
   position tables.
4. **Healing** — every dead replica with restart budget left actually
   came back, and at least one restarted replica passed probation and
   served completed requests afterward.

Everything runs on a FAKE clock advanced ``--dt`` per cluster tick, so
the whole storm — including the breaker's exponential backoff — is a
deterministic function of the seed: same seed, same storm, same
recovery, every run (the tier-1 smoke in ``tests/test_cluster.py``
pins one).

Usage:
  python scripts/chaos_bench.py [--seed S] [--replicas N] [--requests N]
      [--slots K] [--new T] [--router rr|least|prefix] [--horizon H]
      [--max-ticks M] [--record FILE]

Exits nonzero on any invariant violation.  ``--record`` writes one JSON
record (schedule summary, death/restart/watchdog tallies, invariant
verdicts) in the style of the ``SERVE_r*.json`` rounds.
"""

import argparse
import dataclasses
import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tpu_parallel.utils.profiling import run_identity

REQUIRED_KINDS = ("crash", "stall", "flap")  # the storm must contain each


def make_prompts(cfg, rnd, n_requests, lo, hi):
    return [
        [rnd.randrange(1, cfg.vocab_size)
         for _ in range(rnd.randint(lo, hi))]
        for _ in range(n_requests)
    ]


def baseline_tokens(model, params, prompts, new_tokens, n_slots):
    """Greedy reference: one no-fault engine over the same prompts
    (engine batching is output-invariant, pinned in the serving suite)."""
    from tpu_parallel.serving import Request, SchedulerConfig, ServingEngine

    eng = ServingEngine(
        model, params, n_slots=n_slots,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
    )
    outs = [
        eng.add_request(Request(prompt=p, max_new_tokens=new_tokens))
        for p in prompts
    ]
    eng.run()
    assert all(o.status == "finished" for o in outs)
    return [list(o.tokens) for o in outs]


def build_fault_plans(seed, n_replicas, horizon, swap=False):
    """One seeded :class:`FaultPlan` per replica.  The required kinds
    (crash / stall / flap) spread round-robin across the fleet so every
    storm exercises all three shapes even at 2 replicas; extra reject
    windows land by coin flip.  ``swap`` adds the ``swap@T`` OPERATOR
    event to one seeded replica's plan — the harness (not the plan)
    triggers a fleet-wide rolling weight swap when the cluster reaches
    that tick, so the rollout collides with the storm.  Child rngs
    derive from the master seed, so plans are a pure function of
    (seed, n_replicas, horizon, swap)."""
    from tpu_parallel.cluster import FaultPlan

    master = random.Random(seed)
    kinds = [set() for _ in range(n_replicas)]
    for i, kind in enumerate(REQUIRED_KINDS):
        kinds[i % n_replicas].add(kind)
    for i in range(n_replicas):
        if master.random() < 0.3:
            kinds[i].add("reject")
    if swap:
        kinds[master.randrange(n_replicas)].add("swap")
    plans = []
    for i in range(n_replicas):
        child = random.Random(master.randrange(2 ** 31))
        plans.append(
            FaultPlan.from_seed(child, horizon, kinds=tuple(sorted(kinds[i])))
        )
    return plans


def plan_to_record(plan) -> dict:
    d = dataclasses.asdict(plan)
    factory = d.pop("exception_factory", None)
    d["exception_factory"] = getattr(factory, "__name__", None)
    return {k: v for k, v in d.items() if v not in (None, 0)}


def run_soak(model, params, cfg, prompts, refs, *, seed, n_replicas,
             n_slots, new_tokens, router="least", horizon=64, dt=0.05,
             max_ticks=4000, watchdog_ticks=3, watchdog_kill_ticks=8,
             max_restarts=3, backoff_seconds=0.4, probation_ticks=4,
             probation_requests=2, retry_limit=16, swap=False,
             autopilot=False, autopilot_queue_age_target=None):
    """Drive one seeded storm to completion.  Returns ``(record,
    violations)`` — an empty violations list is a passing soak.

    ``swap=True`` arms the ``swap@T`` operator event: at the seeded
    tick the harness begins a NULL-VALUE rolling weight swap (same
    numbers under a new version id, so the bitwise invariant stays
    meaningful) that must resolve — completed with every live replica
    on the new version, or rolled back with every live replica on the
    old one — without wedging, while replicas crash, stall and flap
    around (and under) it.

    ``autopilot=True`` arms the SLO autopilot in SCALE-ONLY trim
    (``max_shed_fraction=0``: a storm may not lose a single request, so
    shedding is pinned off while scale-up through the probation gate
    collides with the crashes and stalls — and, under ``swap=True``,
    with the mid-storm rollout, where any due scale action must be
    typed-refused rather than interleave).  Every healing invariant
    must hold unchanged; the record carries the controller tallies."""
    from tpu_parallel.cluster import (
        BACKOFF,
        DEAD,
        PROBATION,
        AutopilotPolicy,
        Frontend,
        FrontendConfig,
        ReplicaHandle,
        RestartPolicy,
        SwapPolicy,
    )
    from tpu_parallel.serving import Request, SchedulerConfig, ServingEngine

    t = [0.0]
    clock = lambda: t[0]  # noqa: E731 — the storm's injectable time axis

    def factory():
        # per-step decode tick: fault choreography (stall windows,
        # crash ticks) stays at one-token granularity, matching the
        # failover test suite; jits are shared per model so restarts
        # never recompile
        return ServingEngine(
            model, params, n_slots=n_slots,
            scheduler=SchedulerConfig(max_prefills_per_tick=2),
            clock=clock, decode_steps_per_tick=1,
        )

    plans = build_fault_plans(seed, n_replicas, horizon, swap=swap)
    swap_tick = min(
        (p.swap_at_tick for p in plans if p.swap_at_tick is not None),
        default=None,
    )
    handles = [
        ReplicaHandle(i, factory(), fault_plan=plans[i],
                      engine_factory=factory)
        for i in range(n_replicas)
    ]
    policy = RestartPolicy(
        max_restarts=max_restarts, backoff_seconds=backoff_seconds,
        backoff_factor=2.0, probation_ticks=probation_ticks,
        probation_requests=probation_requests,
    )
    fe = Frontend(
        handles, router=router, clock=clock,
        config=FrontendConfig(
            retry_limit=retry_limit, watchdog_ticks=watchdog_ticks,
            watchdog_kill_ticks=watchdog_kill_ticks, restart=policy,
        ),
    )
    ap = None
    if autopilot:
        ap = fe.enable_autopilot(
            AutopilotPolicy(
                queue_age_target=(
                    autopilot_queue_age_target
                    if autopilot_queue_age_target is not None
                    else 8 * dt
                ),
                window_ticks=4, breach_ticks=2, clear_ticks=8,
                max_shed_fraction=0.0,  # a storm must lose NO request
                max_replicas=n_replicas + 2, min_replicas=n_replicas,
                scale_cooldown_ticks=8,
                # scale-down stays off: retiring a replica before its
                # seeded faults fire would tame the storm under test
                scale_down_idle_ticks=None,
            ),
            factory,
        )

    # arrivals spread over the fault horizon, so traffic keeps flowing
    # while replicas crash, stall and come back — plus an AFTERMATH
    # cohort held until the first restart lands, so a healed replica
    # always has work to prove itself on (a restarted replica with
    # nothing left to serve would prove nothing).  Still deterministic:
    # the release condition is a function of the seeded storm, never of
    # wall time.
    rnd = random.Random(seed + 1)
    n_aftermath = max(2, len(prompts) // 6)
    n_main = len(prompts) - n_aftermath
    arrivals = sorted(
        rnd.randrange(0, max(1, horizon)) for _ in range(n_main)
    )
    outs = []
    ever_died = set()
    # completed requests served by POST-RESTART incarnations, cumulative
    # across incarnations (a healed replica that served and then flapped
    # again still proved the restart path)
    served_after_restart = {h.replica_id: 0 for h in handles}
    tick = 0
    submitted = 0

    swap_begin_state = None

    def tick_once():
        """Advance the fake clock one dt, step the cluster, fold this
        tick's death/served-after-restart observations into the tallies
        the healing invariants are judged on.  The seeded swap@T event
        fires here too — an OPERATOR action colliding with the storm."""
        nonlocal tick, swap_begin_state
        if (
            swap_tick is not None
            and swap_begin_state is None
            and tick >= swap_tick
        ):
            swap_begin_state = fe.begin_swap(
                params=params, version="storm-v2",
                policy=SwapPolicy(
                    drain_ticks=12, canary_ticks=3,
                    canary_seconds=2 * dt, canary_requests=1,
                ),
            )["state"]
        t[0] += dt
        fe.step()
        for h in handles:
            if h.health in (DEAD, BACKOFF):
                ever_died.add(h.replica_id)
            elif h.restarts > 0:
                served_after_restart[h.replica_id] = max(
                    served_after_restart[h.replica_id],
                    h.engine.metrics.finished,
                )
        tick += 1

    while (submitted < len(prompts) or fe.has_work()) and tick < max_ticks:
        while (
            submitted < n_main and arrivals[submitted] <= tick
        ):
            outs.append(
                fe.submit(
                    Request(
                        prompt=prompts[submitted],
                        max_new_tokens=new_tokens,
                    )
                )
            )
            submitted += 1
        if submitted == n_main and (
            any(h.restarts > 0 for h in handles) or tick > 4 * horizon
        ):
            while submitted < len(prompts):
                outs.append(
                    fe.submit(
                        Request(
                            prompt=prompts[submitted],
                            max_new_tokens=new_tokens,
                        )
                    )
                )
                submitted += 1
        tick_once()

    # drive to quiescence: the storm may kill a replica on the very last
    # serving tick; the fleet must be allowed to converge (pending
    # restarts fire, probation resolves, flap budgets burn out, a
    # mid-storm rollout completes or rolls back) before the healing and
    # swap invariants are judged
    while tick < max_ticks and (
        # fe.replicas covers the original fleet AND any autopilot
        # scale-ups still auditioning in probation
        any(h.health in (BACKOFF, PROBATION) for h in fe.replicas)
        or fe.swap_status()["state"] in ("rolling", "rolling_back")
        # a storm that resolves before the seeded swap@T tick still
        # ticks on until the operator event FIRES (an idle-fleet swap
        # is legal; silently skipping it would misreport a refusal)
        or (swap_tick is not None and swap_begin_state is None)
    ):
        tick_once()

    s = fe.summary()
    rec_state = fe.recovery_summary()
    violations = []

    if submitted < len(prompts) or fe.has_work():
        violations.append(
            f"non-termination: {max_ticks} ticks exhausted with "
            f"{sum(1 for o in outs if not o.done)} requests open"
        )
    for i, out in enumerate(outs):
        if not out.done:
            violations.append(f"request {i} not terminal: {out.status}")
        elif out.status != "finished":
            violations.append(
                f"request {i} {out.status} ({out.finish_reason}) — the "
                "storm must lose no request"
            )
        elif list(out.tokens) != list(refs[i]):
            violations.append(
                f"request {i} diverged from the no-fault baseline"
            )
    if s["inflight_tokens"] != 0:
        violations.append(
            f"leaked token-budget reservations: {s['inflight_tokens']}"
        )
    for h in fe.replicas:  # original fleet + autopilot scale-ups
        if h.health in (DEAD, BACKOFF):
            continue  # abandoned engines owe nothing
        pool = h.engine.pool
        if pool.n_free != pool.n_slots:
            violations.append(
                f"replica {h.replica_id} leaked slots: "
                f"{pool.n_free}/{pool.n_slots} free"
            )
        else:
            for slot in range(pool.n_slots):
                pool.assert_slot_aligned(slot)
    if s["replica_deaths"] < 1:
        violations.append("storm produced no deaths — schedule too tame")
    if s["watchdog_degraded"] < 1:
        violations.append(
            "no stall was ever OBSERVED (watchdog never degraded anyone)"
        )
    for h in handles:
        st = rec_state[h.replica_id]
        if h.replica_id in ever_died and st["budget_left"] > 0:
            if h.health in (DEAD, BACKOFF):
                violations.append(
                    f"replica {h.replica_id} dead with "
                    f"{st['budget_left']} restart attempts left"
                )
    healed_and_served = any(
        n > 0 for n in served_after_restart.values()
    )
    if ever_died and s["restarts"] >= 1 and not healed_and_served:
        violations.append(
            "no restarted replica served completed requests afterward"
        )
    if s["restarts"] >= 1 and s["probation_promotions"] < 1:
        violations.append("no restarted replica ever passed probation")
    swap_status = fe.swap_status()
    if swap_tick is not None:
        # the mid-storm rollout must RESOLVE (crashes defer or skip
        # targets, never wedge it) and leave zero version mix among the
        # live fleet
        if swap_begin_state != "rolling":
            violations.append(
                f"swap@{swap_tick} refused: {swap_begin_state}"
            )
        if swap_status["state"] == "completed":
            want = "storm-v2"
        elif swap_status["state"] == "rolled_back":
            want = "initial"
        else:
            want = None
            violations.append(
                f"swap never resolved: {swap_status['state']}"
            )
        if want is not None:
            mixed = {
                h.replica_id: h.weights_version
                for h in handles
                if h.health not in (DEAD, BACKOFF)
                and h.weights_version != want
            }
            if mixed:
                violations.append(
                    f"live replicas off the {want} version after "
                    f"{swap_status['state']}: {mixed}"
                )

    record = {
        "bench": "chaos_soak",
        **run_identity(cfg),
        "seed": seed,
        "replicas": n_replicas,
        "router": router,
        "n_requests": len(prompts),
        "n_slots": n_slots,
        "new_tokens": new_tokens,
        "horizon_ticks": horizon,
        "dt": dt,
        "ticks": tick,
        "fault_plans": [plan_to_record(p) for p in plans],
        "watchdog_ticks": watchdog_ticks,
        "watchdog_kill_ticks": watchdog_kill_ticks,
        "restart_policy": {
            "max_restarts": max_restarts,
            "backoff_seconds": backoff_seconds,
            "probation_ticks": probation_ticks,
            "probation_requests": probation_requests,
        },
        "autopilot": autopilot,
        "autopilot_scale_ups": (
            None if ap is None else s["scale_ups"]
        ),
        "autopilot_refusals": (
            None if ap is None else int(fe.registry.counter(
                "cluster_autopilot_refusals_total",
                reason="swap_in_progress",
            ).value)
        ),
        "autopilot_actions": (
            None if ap is None
            else [
                {"tick": a.tick, "kind": a.kind, "reason": a.reason}
                for a in ap.actions
            ]
        ),
        "fleet_size_final": len(fe.replicas),
        "swap": swap,
        "swap_at_tick": swap_tick,
        "swap_state": swap_status["state"],
        "swap_verdict": swap_status.get("verdict"),
        "swap_rollbacks": s["swap_rollbacks"],
        "finished": s["finished"],
        "retries": s["retries"],
        "replica_deaths": s["replica_deaths"],
        "watchdog_degraded": s["watchdog_degraded"],
        "watchdog_kills": s["watchdog_kills"],
        "restarts": s["restarts"],
        "restart_failures": s["restart_failures"],
        "probation_promotions": s["probation_promotions"],
        "probation_demotions": s["probation_demotions"],
        "replica_restarts": {h.replica_id: h.restarts for h in handles},
        "served_after_restart": served_after_restart,
        "final_health": {h.replica_id: h.health for h in handles},
        "bitwise_exact": all(
            o.status == "finished" and list(o.tokens) == list(r)
            for o, r in zip(outs, refs)
        ),
        "all_terminal": all(o.done for o in outs),
        "invariants_ok": not violations,
        "violations": violations,
    }
    return record, violations


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--new", type=int, default=0,
                    help="tokens per request (0 = backend default)")
    ap.add_argument("--router", type=str, default="least")
    ap.add_argument("--horizon", type=int, default=64,
                    help="fault-schedule tick horizon")
    ap.add_argument("--max-ticks", type=int, default=4000)
    ap.add_argument("--swap", action="store_true",
                    help="arm the seeded swap@T operator event: a "
                         "null-value rolling weight swap collides with "
                         "the storm and must resolve without wedging")
    ap.add_argument("--autopilot", action="store_true",
                    help="arm the SLO autopilot in scale-only trim "
                         "(shedding pinned off): autoscaling collides "
                         "with the storm — and any mid-swap scale is "
                         "typed-refused — under the same invariants")
    ap.add_argument("--autopilot-queue-age-target", type=float,
                    default=None,
                    help="autopilot breach target in seconds (default "
                         "8 x dt); lower it to force scale activity "
                         "in small storms")
    ap.add_argument("--record", type=str, default="",
                    help="write the soak record to this JSON file")
    ap.add_argument("--model", choices=("tiny", "gpt2_125m"),
                    default="tiny",
                    help="the served model: the tiny test config (the CPU "
                         "gate) or full-width GPT-2 125M (a chip)")
    args = ap.parse_args()

    from tpu_parallel.models import GPTLM, gpt2_125m, tiny_test

    # the caller names the model; the backend never picks it
    real = args.model == "gpt2_125m"
    cfg = (
        gpt2_125m(dropout_rate=0.0, remat=False)
        if real
        else tiny_test(remat=False)
    )
    new_tokens = args.new or (32 if real else 8)
    model = GPTLM(cfg)
    rnd = random.Random(args.seed)
    lo, hi = 3, min(16, cfg.seq_len - new_tokens - 2)
    prompts = make_prompts(cfg, rnd, args.requests, lo, hi)
    probe = jax.numpy.zeros((1, hi), jax.numpy.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]

    refs = baseline_tokens(model, params, prompts, new_tokens, args.slots)
    record, violations = run_soak(
        model, params, cfg, prompts, refs, seed=args.seed,
        n_replicas=args.replicas, n_slots=args.slots,
        new_tokens=new_tokens, router=args.router, horizon=args.horizon,
        max_ticks=args.max_ticks, swap=args.swap,
        autopilot=args.autopilot,
        autopilot_queue_age_target=args.autopilot_queue_age_target,
    )
    print(json.dumps(record, indent=2))
    if args.record:
        with open(args.record, "w") as fh:
            json.dump(record, fh, indent=2)
            fh.write("\n")
        print(f"record: {args.record}")
    if violations:
        print(
            f"chaos_bench: {len(violations)} INVARIANT VIOLATION(S)",
            file=sys.stderr,
        )
        sys.exit(1)
    print("chaos_bench: all invariants held")


if __name__ == "__main__":
    main()
