"""Operations and bytes latent attention's two attention cores need, and what
of them a traced span held.

**Prefill** (the expanded form through the flash kernel): a head scores at
``qk`` (192) and sums values at ``v`` (128), so a prompt of ``S`` tokens costs
the causal half of ``2 * heads * (qk + v) * S^2`` FLOPs a layer; it reads
``q`` and ``k`` (``heads * S * qk`` each) and ``v`` and writes the output
(``heads * S * v`` each).  ``S`` is the prompt's REAL length: the positions a
bucket pads it with are no work, so padding reads as lost share.

**Decode** (the absorbed form against the stored rows): a stored row of
``row`` (576) numbers is read once a step and layer, scored by every head at
``row`` and summed at ``kv_rank`` (512): ``2 * heads * (row + kv_rank)`` =
278,528 FLOPs for ``row * 2`` = 1,152 bytes, 241.8 FLOPs a byte where a TPU
v5e's ridge is 240.5.  Both are linear in what is counted, so totals over any
set of calls give that set's least time: a sum of lower bounds.
"""


def prefill_attention_cost(sum_sq: float, mla: dict) -> dict:
    """FLOPs and HBM bytes of the flash calls whose prompts' real lengths
    have the squares ``sum_sq`` in all (summed over calls AND layers).
    ``mla`` holds ``heads``, ``qk``, ``v``, ``bytes_per_value``; the bytes
    are counted at ``sqrt(sum_sq)`` rows, under every mix of lengths with
    that sum (compute bounds it by two orders anyway)."""
    h, qk, v, b = mla["heads"], mla["qk"], mla["v"], mla["bytes_per_value"]
    return {
        "flops": 2 * h * (qk + v) * sum_sq / 2,
        "bytes": b * h * 2 * (qk + v) * sum_sq ** 0.5,
    }


def stored_rows_cost(rows: float, mla: dict) -> dict:
    """FLOPs and HBM bytes of decode steps that read ``rows`` stored rows
    (summed over slots, steps and layers)."""
    h, b = mla["heads"], mla["bytes_per_value"]
    return {
        "flops": rows * 2 * h * (mla["row"] + mla["kv_rank"]),
        "bytes": rows * mla["row"] * b,
    }


def span_work(programs, marks: dict, lo: float, hi: float,
              steps_per_tick: int, layers: int):
    """What the traced span held of the two cores' work, summed over the
    programs that ran in it with the work EACH held (nothing is averaged over
    a bucket or a window).

    ``programs``: every watched program the engine ran, in the order the
    device completed them, ``{"kind", "start", "done"}`` on the engine's
    clock (``obs/device_clock.py``'s stamps) with what it computed: a
    ``prefill`` its prompt's REAL length ``real``, a ``tick`` the stored
    ``rows`` its decode steps read over the latent layers (the host's
    mirrors at launch) and the ``tokens`` it fed.  ``marks`` ``{index: trace
    seconds}``: where the trace itself holds program ``index``'s completion
    (an annotation the driver's probe sets as the stamp arrives), which ties
    the trace's clock to the engine's: the middle one of ``done - mark``.
    ``lo`` and ``hi``: the trace's first op's start and last op's end.

    A program the span's edge cuts counts by the part of its run that fell
    inside.  None where the trace holds no mark."""
    offsets = sorted(
        programs[i]["done"] - at for i, at in marks.items() if i < len(programs)
    )
    if not offsets:
        return None
    shift = offsets[len(offsets) // 2]
    lo, hi = lo + shift, hi + shift
    out = {
        "decode_steps": 0.0, "stored_rows": 0.0, "decode_tokens": 0.0,
        "prefill_calls": 0.0, "prefill_tokens": 0.0, "flash_sum_sq": 0.0,
        "programs_s": 0.0, "span_s": hi - lo,
    }
    for p in programs:
        ran = p["done"] - p["start"]
        inside = min(p["done"], hi) - max(p["start"], lo)
        if ran <= 0 or inside <= 0:
            continue
        part = inside / ran
        out["programs_s"] += inside
        if p["kind"] == "prefill":
            out["prefill_calls"] += part
            out["prefill_tokens"] += part * p["real"]
            out["flash_sum_sq"] += part * layers * p["real"] ** 2
        else:
            out["decode_steps"] += part * steps_per_tick
            out["stored_rows"] += part * p["rows"]
            out["decode_tokens"] += part * p["tokens"]
    return out


def span_expert_passes(span: dict, counters: dict, steps_per_tick: int,
                       expert_layers: int, held: int) -> dict:
    """The routed experts' work in the traced span, ``{"calls", "held_rows",
    "touched"}`` for ``lib/moe_cost.routed_experts_cost``, from
    :func:`span_work`'s sums and the window's counters.

    A pass is one expert layer's run over one program step: the span held
    ``decode_steps x expert_layers`` decode passes and ``prefill calls x
    expert_layers`` prefill passes.  Rows routed to held experts follow the
    tokens fed: the window's held assignments a token and layer
    (``moe_assignments_held`` over the decode rows and real prompt tokens it
    fed) times the tokens the span fed.  A prefill pass touches every held
    expert; what a decode pass touches is what is left of the window's mean
    (``moe_experts_touched_mean`` over ``moe_calls``) once the window's own
    prefill passes are taken out (not clamped: a count that is off shows)."""
    get = lambda k: counters.get(k) or 0
    decode_w = get("decode_ticks") * steps_per_tick * expert_layers
    prefill_w = get("prefill_calls") * expert_layers
    tokens_w = max(get("tokens_out") - get("prefills"), 0) + get("prefill_tokens_real")
    rows_a_token = (
        get("moe_assignments_held") / (tokens_w * expert_layers) if tokens_w else 0.0
    )
    touched_decode = 0.0
    if decode_w:
        touched_w = get("moe_experts_touched_mean") * (decode_w + prefill_w)
        touched_decode = (touched_w - prefill_w * held) / decode_w
    decode_s = span["decode_steps"] * expert_layers
    prefill_s = span["prefill_calls"] * expert_layers
    tokens_s = span["decode_tokens"] + span["prefill_tokens"]
    return {
        "calls": decode_s + prefill_s,
        "held_rows": rows_a_token * tokens_s * expert_layers,
        "touched": decode_s * touched_decode + prefill_s * held,
    }


def scope(run, pattern: str):
    """``{"seconds", "events"}`` of the ops under ``pattern`` in the traced
    span and the span's busy seconds, or ``(None, None)`` where nothing was
    traced or the program has no such scope (a record without the key)."""
    scopes = run.facts.get("scopes") or {}
    found = scopes.get(pattern)
    if not found or not scopes.get("busy_s"):
        return None, None
    return found, scopes["busy_s"]


def share(run, pattern: str):
    """Device time (%) of the ops under ``pattern`` over busy time, or None."""
    found, busy = scope(run, pattern)
    return None if found is None else 100.0 * found["seconds"] / busy
