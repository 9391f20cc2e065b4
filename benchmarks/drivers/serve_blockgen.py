"""Driver of the serving cells of block-diffusion expert decoders
(``sdar_moe``): HTTP/SSE traffic through the daemon, as the other serving
cells, with this family's model, its per-request knob and its reference.

The same path: a ``ServingEngine`` (fixed-slot pool, a tick of 8 block
forwards, whole-prompt prefill in the cell's buckets) behind ``Frontend`` ->
``ServingDaemon`` -> ``DaemonHTTPServer`` on loopback, weights made on the
device from ``--seed`` in the type they are served in, load from a child
process that never imports JAX: the window itself is ``lib/serve_window.py``,
and this file is what belongs to the family:

- the model is built from the configuration file's own keys (the published
  ``config.json`` keys) and its ``assumed`` sizes (block length, mask id);
- weights come from ``lib/sdar_weights.py``;
- a request carries its denoising steps a block.  ``lib/traffic.py`` makes
  prompts and budgets and ``lib/loadgen.py`` posts those two; the mix's
  ``request_knobs`` are drawn here (:class:`KnobTraffic`, in equal shares,
  paired with prompts by the seed) and posted by ``lib/loadgen_knobs.py``,
  which is ``lib/loadgen.py`` with a request's further keys put into its
  submit body.  Both are handed to ``lib/serve_window.py`` from outside, for
  the length of the run, as its probes are: the window's file is not edited;
- the reference is ``reference/sdar_moe_ref.py``, its layers made one at a
  time.

``correct`` (logits, not tokens; of what the timed path produced): the
streams compared are picked WHILE the window runs, as the engine retires
them (:class:`StreamProbe`: the longest that ended in the window and a
seeded reservoir of the rest), with the step of its block at which each
position was filled, which only the engine knows.  After the engine and its
weights are freed they are REPLAYED in the reference: the clean sequence
once, then for each step index the generated blocks as they stood at that
step (a block sees the clean blocks before it and itself), which gives the
fp32 logits that the program's forward of that step should have produced.
Numbers, each with its limit: ``served_logit_gap``, the widest gap of a
served id's fp32 logit under the fp32 best at its position and step;
``served_off_best_share``, the share (%) of served ids that are not the fp32
best there; ``served_choice_gap``, the widest shortfall (in log confidence)
of a filled position's fp32 confidence under the best masked position's that
was passed over in the same forward; ``served_choice_off_share``, the share
(%) of filled positions that had such a shortfall.  A request's last block,
where the budget cuts it, is left out: what the program held in its
undelivered positions is not known to anyone but the program.  ``--control
1`` also reads the control, the reference with float8 operands: what IT would
fill and with what, read in the fp32 logits and confidences.

The comparison's device memory is bounded and read (PR 43): the replay holds
one layer at a time with nothing in flight when weights are made
(``reference/sdar_moe_ref.py``), :class:`MemoryWatch` samples ``bytes_in_use``
at each draw and layer boundary, and ``run.facts["comparison_memory"]``
carries the sampled peak beside ``replay_bytes_bound`` of the mix's worst
sample; ``lib/serve_window.py`` logs both.  ``benchmarks/compare_blockgen.py``
runs this comparison alone, on streams made from a seed.
"""

import os
import random
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from drivers import serve as serve_driver
from lib import serve_window, sdar_weights, xplane_scopes
from lib import traffic as traffic_lib
from reference import sdar_moe_ref

REFERENCE_PAD = 1024  # reference sequences pad to a multiple: four shapes
LOADGEN = os.path.join(os.path.dirname(serve_driver.LOADGEN), "loadgen_knobs.py")
# device time is read by scope; the grouped matmuls' custom calls carry no
# scope and are found by their op name; `sort` is what must not come back
MOE_OPS = r"moe\.|ragged-dot"
SCOPES = (MOE_OPS, r"moe\.router", r"moe\.experts", r"ragged-dot",
          r"ragged-dot-streamed", r"ragged-dot-none", r"attn\.block",
          r"diffusion\.unmask", r"^sort")
# grouped-matmul ops a layer's pass: the streamed kernel is called twice
# (gate and up in one pass, then down: ops/grouped_ffn.py), lax.ragged_dot
# three times (models/moe.py::_grouped_ffn)
KERNELS_PER_CALL = {"ragged-dot-streamed": 2, "ragged-dot-none": 3}


def model_config(config: dict, engine: dict):
    """The program's ``GPTConfig`` for a configuration file of this family
    (its top level holds the published keys)."""
    from tpu_parallel.models.gpt import block_diffusion_decoder
    from tpu_parallel.models.layers import ExpertsSpec

    if (config["attention_bias"] or config["tie_word_embeddings"]
            or config["use_sliding_window"] or config["mlp_only_layers"]
            or config["decoder_sparse_step"] != 1 or config["rope_scaling"]
            or not config["norm_topk_prob"] or config["hidden_act"] != "silu"):
        raise ValueError("a key of this family that the driver does not build")
    sizes = config["model"]
    return block_diffusion_decoder(
        experts=ExpertsSpec(
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"],
            score="softmax",
        ),
        block_len=sizes["block_len"],
        mask_token_id=sizes["mask_token_id"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        seq_len=engine["slot_positions"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=getattr(jnp, config["precision"]["compute"]),
        remat=False,
        prefill_flash=True,
        **engine.get("model_overrides", {}),
    )


def reference_shape(config: dict) -> dict:
    return {
        "block_len": config["model"]["block_len"],
        "rope_theta": float(config["rope_theta"]),
        "num_experts_per_tok": config["num_experts_per_tok"],
        "eps": config["rms_norm_eps"],
    }


def replay_bound(config: dict, mix: dict, streams: int) -> int:
    """``replay_bytes_bound`` of the worst sample a mix allows: ``streams``
    streams of the longest prompt with the longest answer, at the most
    denoising steps a block; sizes from the configuration's published keys."""
    sizes = {
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "experts": config["num_experts"],
        "expert_width": config["moe_intermediate_size"],
        "vocab_size": config["vocab_size"],
        "block_len": config["model"]["block_len"],
    }
    generated = mix["output_tokens"]["max"]
    return sdar_moe_ref.replay_bytes_bound(
        sizes, mix["prompt_tokens"]["max"] + generated, generated, streams,
        max(mix["request_knobs"]["denoising_steps"]["values"]),
        pad=REFERENCE_PAD, passes=2,
    )


class MemoryWatch:
    """``bytes_in_use`` of the first device at the points it is called at.
    The replay calls it after each draw of weights and at each layer's end,
    with nothing in flight, so the largest sample is the comparison's peak
    up to one call's temporaries; the runtime's own ``peak_bytes_in_use`` is
    a process's lifetime peak, and in a cell's run the engine's stands in
    it."""

    def __init__(self):
        self.device = jax.local_devices()[0]
        self.samples = []  # (where, bytes in use)

    def __call__(self, where: str) -> None:
        stats = self.device.memory_stats() or {}  # None off the chip
        self.samples.append((where, stats.get("bytes_in_use", 0)))

    @property
    def peak(self) -> int:
        return max((held for _, held in self.samples), default=0)


class KnobTraffic:
    """``lib/traffic.py`` as ``lib/serve_window.py`` uses it, with the mix's
    ``request_knobs`` drawn: each knob's values in equal shares over the
    pool, paired with the requests by the seed."""

    percentile = staticmethod(traffic_lib.percentile)

    @staticmethod
    def make_requests(mix, seed, vocab, seq_len):
        requests = traffic_lib.make_requests(mix, seed, vocab, seq_len)
        rng = random.Random(seed ^ 0x4B0B5)
        for name, knob in sorted(mix.get("request_knobs", {}).items()):
            values = [
                knob["values"][i % len(knob["values"])]
                for i in range(len(requests))
            ]
            rng.shuffle(values)
            for request, value in zip(requests, values):
                request[name] = value
        return requests


class StreamProbe:
    """Picks, while the window runs, the streams that are compared, and
    keeps what only the engine knows of them: the step of its block at which
    each served position was filled.

    ``release_slot`` is wrapped from outside (as ``annotate`` wraps
    ``launch``): the engine retires a stream there, on the pump's thread,
    with the request's record whole.  Host lists only: nothing is read from
    the device.  While ``active`` it holds at most ``most`` streams: the
    longest so far and a reservoir of the others (each equally likely, drawn
    from the seed)."""

    def __init__(self, engine, seed: int, most: int):
        self.rng = random.Random(seed ^ 0x57A7E)
        self.most = most
        self.active = False
        self.longest = None
        self.rest = []
        self.seen = 0
        inner = engine.release_slot

        def release_slot(slot):
            out = engine._slot_out[slot]
            if self.active and out is not None and out.finish_reason == "length":
                self.ended(out)
            inner(slot)

        engine.release_slot = release_slot

    def ended(self, out):
        new = types.SimpleNamespace(
            prompt=tuple(out.request.prompt), tokens=list(out.tokens),
            fill_steps=list(out.fill_steps),
            denoising_steps=out.request.denoising_steps,
        )
        size = lambda s: len(s.prompt) + len(s.tokens)
        if self.longest is None or size(new) > size(self.longest):
            new, self.longest = self.longest, new
            if new is None:
                return
        self.seen += 1
        keep = self.most - 1
        at = len(self.rest) if len(self.rest) < keep else self.rng.randrange(self.seen)
        if at < keep:
            self.rest[at:at + 1] = [new]

    def held(self) -> list:
        return ([self.longest] if self.longest else []) + self.rest


def describe(run):
    """The cell's model and the shape of its parameters, no weights made:
    what ``compare`` needs of ``built``."""
    from tpu_parallel.models import GPTLM

    cfg = model_config(run.config, run.cell["engine"])
    model = GPTLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        )
    )["params"]
    return types.SimpleNamespace(
        model=model, cfg=cfg, abstract=abstract,
        served=getattr(jnp, run.cell["engine"]["served_parameters"]),
        vocab=run.traffic["token_ids"]["below"],
    )


class BlockGen:
    """What ``lib/serve_window.py`` asks of a family of model."""

    name = "serve_blockgen"
    counter_keys = (
        "ticks", "decode_ticks", "prefills", "prefill_calls",
        "prefill_tokens_real", "prefill_tokens_padded", "block_forwards",
        "block_commit_forwards", "block_tokens_filled", "blocks_completed",
        "tokens_per_forward", "commit_forward_share", "moe_calls",
        "moe_experts_touched_mean", "moe_rows_per_expert_max_over_mean",
        "slot_occupancy_mean", "queue_depth_mean", "busy_tick_ms_mean",
        "tick_device_wait_ms_mean", "tick_prefill_ms_mean",
        "launch_ahead_share", "itl_ms_p50", "tokens_out",
    )

    def build(self, run):
        built = describe(run)
        built.params = sdar_weights.make_params(
            run.seed, built.abstract, dtype=built.served
        )
        return built

    def engine_built(self, run, engine):
        run.log(f"block_plan: {engine.block_plan}")
        run.log(f"moe_plan: {engine.moe_plan}")
        self.probe = StreamProbe(engine, run.seed, run.cell["reference_streams"])

    def window(self, run, opened: bool):
        self.probe.active = opened

    def traced(self, run, trace_file) -> str:
        scopes = xplane_scopes.by_pattern(trace_file, SCOPES)
        run.facts["scopes"] = scopes
        if scopes:
            # the span's own work: a layer's pass of the experts is a fixed
            # number of grouped-matmul ops, so the ops the trace holds say
            # how many passes it held; a pass's rows and touched experts are
            # the window's means (the counters move a tick at a time)
            calls = sum(
                scopes[name]["events"] / per
                for name, per in KERNELS_PER_CALL.items()
            )
            window = run.counters.get("moe_calls") or 0
            held = run.counters.get("moe_assignments_held", 0)
            touched = run.counters.get("moe_experts_touched_mean", 0.0)
            run.facts["traced_experts"] = {
                "calls": calls,
                "held_rows": calls * held / window if window else 0.0,
                "touched": calls * touched,
            }
        return (f"device time by scope: {scopes}; the span's expert passes: "
                f"{run.facts.get('traced_experts')}")

    def closed(self, run, engine, built):
        run.facts["experts"] = {
            "d_model": built.cfg.d_model,
            "width": run.config["moe_intermediate_size"],
            "bytes_per_value": jnp.dtype(built.served).itemsize,
        }
        self.held = self.probe.held()
        run.log(f"stream probe: {self.probe.seen + bool(self.held)} streams "
                f"ended in the window, {len(self.held)} held")

    def compare(self, run, ended, requests, built):
        compare(run, self.held, ended, requests, built)


def run(run) -> None:
    """The window, with this family's traffic and load generator handed to
    it for the length of the run."""
    swapped = (
        (serve_window, "traffic_lib", KnobTraffic),
        (serve_driver, "LOADGEN", LOADGEN),
    )
    before = [getattr(module, name) for module, name, _ in swapped]
    for module, name, value in swapped:
        setattr(module, name, value)
    try:
        serve_window.run(run, BlockGen())
    finally:
        for (module, name, _), value in zip(swapped, before):
            setattr(module, name, value)


def decisions(fill_steps, block_len: int):
    """The forwards in which a position was CHOSEN: ``[(t, rows of the
    block still masked at step t, which of them were filled at t)]`` for
    each block and step that filled something and passed something over."""
    out = []
    steps = np.asarray(fill_steps)
    for lo in range(0, len(steps), block_len):
        block = steps[lo:lo + block_len]
        for t in range(int(block.max()) + 1 if len(block) else 0):
            masked = np.nonzero(block >= t)[0] + lo
            chosen = steps[masked] == t
            if chosen.any() and not chosen.all():
                out.append((t, masked, chosen))
    return out


def numbers(replayed, picks, confidences, weights, shape):
    """The four compared numbers of ``picks`` (``[stream][t] -> ids [R]``,
    the ids filled at step ``t`` where ``fill_steps == t``) and of the
    choices ``confidences`` (``[stream][t] -> [R]``) would make, read in the
    fp32 logits and confidences of ``replayed``."""
    size = shape["block_len"]
    worst = off = count = 0
    choice_worst, choice_off, choices = 0.0, 0, 0
    for s, stream in enumerate(replayed):
        fp32 = {}
        for t, hidden in stream["hidden"].items():
            _, top, at, conf = sdar_moe_ref.read(weights, hidden, picks[s][t], shape)
            fp32[t] = conf
            rows = stream["fill_steps"] == t
            gap = (top - at)[rows]
            worst = max(worst, float(gap.max()) if gap.size else 0.0)
            off += int((gap > 0).sum())
            count += int(rows.sum())
        for t, masked, chosen in decisions(stream["fill_steps"], size):
            # what the judged confidences would have filled: as many as the
            # program did, the most confident first
            order = np.argsort(-confidences[s][t][masked], kind="stable")
            mine = np.zeros_like(chosen)
            mine[order[:int(chosen.sum())]] = True
            true = np.log(fp32[t][masked])
            short = np.maximum(true[~mine].max() - true[mine], 0.0)
            choice_worst = max(choice_worst, float(short.max()))
            choice_off += int((short > 0).sum())
            choices += int(mine.sum())
    return {
        "served_logit_gap": worst,
        "served_off_best_share": 100.0 * off / max(count, 1),
        "served_choice_gap": choice_worst,
        "served_choice_off_share": 100.0 * choice_off / max(choices, 1),
    }, count, choices


def compare(run, held, ended, requests, built) -> None:
    shape = reference_shape(run.config)
    mask_id = built.cfg.mask_token_id
    by_prompt = {tuple(requests[r["idx"]]["prompt"]): r for r in ended}
    # what the client read is what the engine recorded, token for token
    sample = [s for s in held
              if by_prompt.get(s.prompt, {"tokens": None})["tokens"] == s.tokens]
    if not sample:
        run.check("streams_compared", 1, 0)
        return
    sample.sort(key=lambda s: len(s.prompt) + len(s.tokens))
    streams = [
        {"prompt": s.prompt, "tokens": s.tokens, "fill_steps": s.fill_steps}
        for s in sample
    ]

    watch = MemoryWatch()
    made = (run.seed, built.abstract, built.cfg.n_heads, built.cfg.n_kv_heads,
            built.served)

    def replay(weights, precision):
        return sdar_moe_ref.replay(
            weights, streams, shape, mask_id, precision, pad=REFERENCE_PAD,
            watch=watch,
        )

    t0 = time.perf_counter()
    weights = sdar_weights.to_reference(*made)
    watch("top-level weights")
    replayed = replay(weights, "float32")
    served = [{t: r["tokens"] for t in r["hidden"]} for r in replayed]
    # the program's choices are its fill steps: judge them by a confidence
    # that ranks the filled positions first
    as_served = [
        {t: np.where(r["fill_steps"] == t, 1.0, 0.0) for t in r["hidden"]}
        for r in replayed
    ]
    got, count, choices = numbers(replayed, served, as_served, weights, shape)
    run.log(f"reference: {len(streams)} streams replayed in "
            f"{time.perf_counter() - t0:.1f}s (longest "
            f"{len(sample[-1].prompt) + len(sample[-1].tokens)} positions; "
            f"steps a block {[s.denoising_steps for s in sample]}); {count} "
            f"served ids and {choices} choices compared: "
            + " ".join(f"{k}={v:.6g}" for k, v in got.items()))
    limits = run.cell["limits"]
    for name in limits:
        run.check(name, got[name], limits[name])
    if run.control:
        precision = run.cell["control_precision"]
        # a second pass over the layers, under the same top-level weights
        low = replay(dict(weights, layers=sdar_weights.layers(*made)), precision)
        picks, confidences = [], []
        for r in low:
            reads = {
                t: sdar_moe_ref.read(weights, h, r["tokens"], shape, precision)
                for t, h in r["hidden"].items()
            }
            picks.append({t: x[0] for t, x in reads.items()})
            confidences.append({t: x[3] for t, x in reads.items()})
        ctl, _, _ = numbers(replayed, picks, confidences, weights, shape)
        over = [k for k in limits if not ctl[k] <= limits[k]]
        run.log(f"control {precision}: "
                + " ".join(f"{k}={v:.6g}" for k, v in ctl.items())
                + f" (over its limit: {', '.join(over) or 'none'})")
        run.facts["control"] = {precision: dict(ctl, over=over)}
    watch("numbers read")
    run.facts["comparison_memory"] = {
        "sampled_peak_bytes": watch.peak,
        "bound_bytes": replay_bound(
            run.config, run.traffic, run.cell["reference_streams"]
        ),
    }
    if watch.peak:  # the CPU's runtime reports none
        run.log("comparison memory, GB in use: " + ", ".join(
            f"{where} {held / 1e9:.2f}" for where, held in watch.samples
        ))
