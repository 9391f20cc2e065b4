"""Operations and bytes the selective recurrence of the Mamba-2 layers needs,
and what a traced span held of that work (``span_work``).  Both costs count
what the MATHEMATICS has to move or compute, whatever implements it, so the
least time they give is a lower bound that a sound program cannot beat: a
share of it cannot pass 100%.

``ssm`` holds ``layers`` (recurrent layers), ``heads``, ``head_dim``,
``d_state``, ``groups``, ``state_bytes`` (bytes of one number of
the state in the type the configuration states) and ``bytes_per_value`` (of an
activation).

**The state update** (decode: one token a live slot).  A layer-step of one
LIVE slot reads its state ``[heads, head_dim, d_state]`` once and writes it
once, reads the row's ``x`` (``heads x head_dim``), ``B`` and ``C`` (``groups x
d_state`` each) and ``dt`` (``heads``) and writes ``y``.  A parked slot's state
need not be touched.  FLOPs: decay, outer product and add, then the
contraction with ``C``: ``5 heads head_dim d_state``.

**The scan** (prefill: the REAL prompt tokens, pads and dummy rows left out).
FLOPs a token and layer: ``4 heads head_dim d_state``, what a token adds to
the state and what it reads from it; the sequential form needs 5 of these and
the chunked form these 4 plus its products inside a chunk (``chunk x (groups
d_state + heads head_dim)`` more), so 4 is under both.  Bytes: each token's
``x``, ``B``, ``C``, ``dt`` in and ``y`` out, and a prompt's final state
written once (a fresh prompt starts from zeros, which nobody has to read).
At these sizes the bytes bound it: 17 KB a token against 2.1 MFLOP.
"""


def state_update_cost(slot_steps: float, ssm: dict) -> dict:
    """FLOPs and HBM bytes of ``slot_steps`` one-token updates of a live
    slot's state (summed over the steps of the window), all layers."""
    h, p, n, g = ssm["heads"], ssm["head_dim"], ssm["d_state"], ssm["groups"]
    state = h * p * n
    row = 2 * h * p + 2 * g * n + h  # x, y; B, C; dt
    return {
        "flops": slot_steps * ssm["layers"] * 5 * state,
        "bytes": slot_steps * ssm["layers"] * (
            2 * state * ssm["state_bytes"] + row * ssm["bytes_per_value"]
        ),
    }


def scan_cost(tokens: float, prompts: float, ssm: dict) -> dict:
    """FLOPs and HBM bytes of the recurrence over ``tokens`` real prompt
    tokens in ``prompts`` fresh prompts, all layers."""
    h, p, n, g = ssm["heads"], ssm["head_dim"], ssm["d_state"], ssm["groups"]
    row = 2 * h * p + 2 * g * n + h
    return {
        "flops": tokens * ssm["layers"] * 4 * h * p * n,
        "bytes": ssm["layers"] * (
            tokens * row * ssm["bytes_per_value"]
            + prompts * h * p * n * ssm["state_bytes"]
        ),
    }


def span_work(step_runs: dict, scan_runs: dict, counters: dict,
              steps_per_tick: int) -> dict:
    """What the traced span held of the recurrence's work, counted from the
    trace itself (``lib/xplane_counts.executions``), for a program whose
    layers are unrolled.

    ``step_runs`` ``{op: runs}``: the compiled ops under ``ssm.step``.  Each
    runs once a decode step, so their mean is the decode steps the span held,
    a step the span's edge cut counted by the part of its ops that ran.  How
    many slots were LIVE in a step is not in a trace: the window's own mean,
    ``(tokens_out - prefills) / (decode_ticks x steps_per_tick)`` (every
    token but each request's first came out of a decode step).

    ``scan_runs`` ``{(rows, tokens): {op: runs}}``: the ops under ``ssm.scan``
    by the shape of the prefill call they belong to.  Every op but the few of
    the short loop over a call's chunks runs once a call, so the most common
    count is the calls of that shape (a call cut by the span's edge is in or
    out as most of its ops are).  The positions those calls computed, times
    the window's share of REAL prompt tokens among computed positions, are
    the span's real tokens; its prompts are its calls times the window's
    prompts a call."""
    get = lambda k: counters.get(k) or 0
    steps = sum(step_runs.values()) / len(step_runs) if step_runs else 0.0
    window_steps = get("decode_ticks") * steps_per_tick
    live = (
        max(get("tokens_out") - get("prefills"), 0) / window_steps
        if window_steps else 0.0
    )
    calls = {}
    for (rows, tokens), ops in scan_runs.items():
        counts = list(ops.values())  # the most common; of two, the smaller
        calls[(int(rows), int(tokens))] = max(sorted(set(counts)), key=counts.count)
    positions = sum(n * rows * tokens for (rows, tokens), n in calls.items())
    computed = get("prefill_tokens_real") + get("prefill_tokens_padded")
    real_share = get("prefill_tokens_real") / computed if computed else 0.0
    per_call = get("prefills") / get("prefill_calls") if get("prefill_calls") else 0.0
    return {
        "decode_steps": steps, "live_slots": live,
        "slot_steps": steps * live,
        "prefill_calls": {f"{r}x{t}": n for (r, t), n in sorted(calls.items())},
        "positions": positions,
        "prompt_tokens": positions * real_share,
        "prompts": sum(calls.values()) * per_call,
    }
