"""Driver of the serving cells of latent-attention expert decoders
(``pangu_ultra_moe``: MLA with one stored row a position that all heads
share, sandwich norms, a leading dense layer before sigmoid-routed expert
layers): HTTP/SSE traffic through the daemon, as the other serving cells, with
this family's model and its reference.

The same path: a ``ServingEngine`` (fixed-slot pool, fused tick of 8,
whole-prompt prefill ONE prompt a call in the cell's buckets) behind
``Frontend`` -> ``ServingDaemon`` -> ``DaemonHTTPServer`` on loopback, weights
made on the device from ``--seed`` in the type they are served in, load from a
child process (``lib/loadgen.py``) that never imports JAX: the window itself
is ``lib/serve_window.py``, and this file is what belongs to the family:

- the model is built from the configuration file's own keys (the published
  ``config.json`` keys): the five latent sizes, the leading dense layers from
  ``first_k_dense_replace``, the held experts and the router's width from
  ``n_routed_experts`` and ``published``;
- a slot holds ONE row of ``kv_lora_rank + qk_rope_head_dim`` numbers a
  position and layer and no K/V heads: the engine's ``latent_plan``,
  ``attn_plan`` and ``moe_plan`` are logged;
- the reference is ``reference/pangu_ultra_moe_ref.py`` (the expanded form
  only), its layers made one at a time: ONE layer's float32 weights are on
  the device at a time beside the bfloat16 draw they are upcast from, and the
  memory the comparison holds is sampled after each layer
  (``run.facts["comparison_memory"]``).

What else is handed to the window from outside, for the length of the run (as
``drivers/serve_blockgen.py`` hands its knobs): :class:`WaveTraffic`, the
generator's own pool dealt wave by wave, and
:class:`SpanProbe`, which keeps what every watched program computed so that
the rooflines count the traced span's OWN work.

``correct``, as cell 3's (``drivers/serve_moe.py``): once the engine is freed,
the longest stream that ended in the window (it has to pass the cell's
``longest_stream_passes`` positions)
and a seeded sample of the rest go through the reference.  Two numbers, each
with its limit: ``served_off_best_share``, the share (%) of served tokens that
are not the float32 reference's best, and ``served_logit_gap``, the widest gap
of a served token's float32 logit under the float32 best over the vocabulary
slice.  ``--control 1`` also reads the float8 control and a witness (the
reference itself with bfloat16 operands: no control, it has to fail nothing).
"""

import random
import time
import types

import jax
import jax.numpy as jnp

from drivers.serve_blockgen import MemoryWatch
from lib import mla_cost, pangu_weights, serve_window, weights
from lib import traffic as traffic_lib
from lib import xplane, xplane_scopes
from reference import pangu_ultra_moe_ref

REFERENCE_PAD = 2048  # reference sequences pad to a multiple: four shapes
# device time is read by scope; the grouped matmuls' custom calls carry no
# scope and are found by their op name
MOE_OPS = r"moe\.|ragged-dot"
# the experts' lax.cond over its two buffers is an op of its own in the trace
# and its branch's ops are events beside it: read apart, and taken off
MOE_CONDS = r"moe\.experts/cond(:|$)"
MLA_PROJ = r"mla\.(q_proj|kv_down|kv_up|absorb|out_proj)"
MLA_STORED = r"mla\.scores/stored"
MLA_FLASH = r"mla\.scores/flash"
SCOPES = (r"attn\.latent", MLA_PROJ, r"mla\.q_proj", r"mla\.kv_down",
          r"mla\.kv_up", r"mla\.absorb", r"mla\.out_proj", r"mla\.scores",
          MLA_STORED, MLA_FLASH, MOE_OPS, MOE_CONDS, r"moe\.router", r"moe\.experts",
          r"moe\.shared", r"ragged-dot", r"ragged-dot-streamed",
          r"ragged-dot-none", r"^sort")
# where the trace holds a watched program's completion (SpanProbe)
MARK = "latent_attn.done."
# what the device may still hold when the comparison begins.  Eighteen sound
# runs read 0.20 GB and one 0.28 (the streams' tokens, what the runtime keeps
# for its programs: my chip runs, PR 47); the smallest thing of the engine's
# that could be left behind is the pool, 1.52 GB, or one layer's held
# experts, 1.51 GB.  Half a GB lies between the two, about twice over the one
# and a third of the other; the comparison itself holds up to 9.1 GB of 15.75
FREED_BOUND_BYTES = 1 << 29


def model_config(config: dict, engine: dict):
    """The program's ``GPTConfig`` for a configuration file of this family
    (its top level holds the published keys, cut as ``reduced`` says)."""
    from tpu_parallel.models.gpt import latent_experts_decoder
    from tpu_parallel.models.layers import ExpertsSpec, LatentSpec

    if (config["attention_bias"] or config["tie_word_embeddings"]
            or not config["norm_topk_prob"] or not config["sandwich_norm"]
            or config["num_nextn_predict_layers"]
            or config["hidden_act"] != "silu"
            or config["num_key_value_heads"] != config["num_attention_heads"]):
        raise ValueError("a key of this family that the driver does not build")
    return latent_experts_decoder(
        latent=LatentSpec(
            q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
            nope_dim=config["qk_nope_head_dim"],
            rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        ),
        experts=ExpertsSpec(
            n_experts=config["published"]["n_routed_experts"],
            top_k=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"],
            score="sigmoid",
            shared=config["n_shared_experts"],
            held=(0, config["n_routed_experts"]),
            shared_sum=True,
            route_scale=float(config["routed_scaling_factor"]),
        ),
        dense_layers=config["first_k_dense_replace"],
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        mlp_dim=config["intermediate_size"],
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
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "eps": config["rms_norm_eps"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "held": (0, config["n_routed_experts"]),
    }


def parameters(config: dict) -> int:
    """The parameters a set of this family's keys describes, counted from the
    keys: the share's from the file's top level, the uncut model's from its
    ``published`` (without the multi-token-prediction module)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rq, rk = config["q_lora_rank"], config["kv_lora_rank"]
    attention = (
        d * rq + rq + rq * heads * qk
        + d * (rk + config["qk_rope_head_dim"]) + rk
        + rk * heads * (config["qk_nope_head_dim"] + config["v_head_dim"])
        + heads * config["v_head_dim"] * d
    )
    expert = 3 * d * config["moe_intermediate_size"]
    dense_layer = attention + 4 * d + 3 * d * config["intermediate_size"]
    router = d * config.get("published", config)["n_routed_experts"]
    expert_layer = (
        attention + 4 * d + router
        + (config["n_shared_experts"] + config["n_routed_experts"]) * expert
    )
    dense = config["first_k_dense_replace"]
    return (
        dense * dense_layer
        + (config["num_hidden_layers"] - dense) * expert_layer
        + 2 * config["vocab_size"] * d + d
    )


class WaveTraffic:
    """``lib/traffic.py`` as ``lib/serve_window.py`` uses it, its pool dealt
    WAVE BY WAVE.

    Client ``c`` of a closed loop sends requests ``c``, ``c + clients``, ...
    of the pool, so requests ``[k x clients, (k + 1) x clients)`` are the
    ``k``-th wave: what all clients send ``k``-th.  The generator shuffles its
    stratified lengths over the whole pool; here the SAME set of lengths (and
    of budgets) is cut into ``clients`` strata of neighbouring sizes and
    every wave takes one of each, in an order of its own: every wave is the
    whole distribution, and the seed still decides which prompt meets which
    budget and which client.  A window of this cell holds about 112
    whole-prompt prefills whose cost varies tenfold; over the whole-pool
    shuffle its ``serve_out_tok_s`` spread by more than the driver admits
    (PERF.md section 6, PR 47)."""

    percentile = staticmethod(traffic_lib.percentile)

    @staticmethod
    def make_requests(mix, seed, vocab, seq_len):
        requests = traffic_lib.make_requests(mix, seed, vocab, seq_len)
        rng = random.Random(seed ^ 0x3A7E5)
        clients = mix["arrivals"]["clients"]
        prompts = deal_waves(
            sorted((r["prompt"] for r in requests), key=len), clients, rng
        )
        budgets = deal_waves(
            sorted(traffic_lib.stratified(mix["output_tokens"], len(requests))),
            clients, rng,
        )
        return [
            {"prompt": p, "max_new_tokens": max(1, min(o, seq_len - len(p)))}
            for p, o in zip(prompts, budgets)
        ]


def deal_waves(ordered: list, clients: int, rng) -> list:
    """``ordered`` (sorted, ``clients x waves`` long) as ``waves`` waves of
    ``clients``: stratum ``j`` is the ``waves`` neighbours ``ordered[j x
    waves:(j + 1) x waves]``, and every wave holds one of each stratum."""
    waves = len(ordered) // clients
    if waves * clients != len(ordered):
        raise ValueError("a pool that is no whole number of waves")
    strata = [ordered[j * waves:(j + 1) * waves] for j in range(clients)]
    for stratum in strata:
        rng.shuffle(stratum)
    out = []
    for k in range(waves):
        wave = [stratum[k] for stratum in strata]
        rng.shuffle(wave)
        out.extend(wave)
    return out


class SpanProbe:
    """What every watched program of the engine computed, kept from outside
    so that a traced span's work is the sum over the programs that ran in it
    (``lib/mla_cost.span_work``), not a mean over a bucket or a window.

    The engine tells its metrics record of a prefill call's real prompt
    length at launch, of a tick's stored rows read and delivered tokens at
    collect, and of every watched program's ``[start, done)`` on its own
    clock as the completion clock stamps it (``obs/device_clock.py``): the
    three calls are wrapped on each record (``reset_metrics`` swaps records,
    so it is wrapped too).  The device runs programs in launch order, so the
    ``k``-th stamp of a kind belongs to the ``k``-th launch of that kind.
    As a stamp arrives an empty annotation ``latent_attn.done.<index>`` goes
    into the profiler's host plane (nothing when no trace runs): it ties the
    trace's clock to the engine's."""

    def __init__(self, engine):
        self.work = {"prefill": [], "tick": []}  # in launch order
        self.stamps = []  # (kind, start, done), in completion order
        self._fed = 0
        self._opened = {"prefill": 0, "tick": 0}  # the work before the record
        reset = engine.reset_metrics

        def hooked_reset(*args, **kwargs):
            record = reset(*args, **kwargs)
            self._opened = {kind: len(w) for kind, w in self.work.items()}
            self._hook(record)
            return record

        engine.reset_metrics = hooked_reset
        self._hook(engine.metrics)

    def _hook(self, record):
        prefill_call, tick = record.record_prefill_call, record.record_tick
        busy_tick, device = record.record_busy_tick, record.record_device

        def record_prefill_call(chunks=0, real=0, padded=0):
            self.work["prefill"].append({"real": real})
            return prefill_call(chunks=chunks, real=real, padded=padded)

        def record_tick(*args, **kwargs):
            self._fed = kwargs["new_tokens"] - kwargs["prefills"]
            return tick(*args, **kwargs)

        def record_busy_tick(*args, **kwargs):
            self.work["tick"].append(
                {"rows": kwargs["latent_rows"] or 0, "tokens": self._fed}
            )
            return busy_tick(*args, **kwargs)

        def record_device(kind, shape, idle_from, start, done):
            with jax.profiler.TraceAnnotation(f"{MARK}{len(self.stamps)}"):
                self.stamps.append((kind, start, done))
            return device(kind, shape, idle_from, start, done)

        record.record_prefill_call = record_prefill_call
        record.record_tick = record_tick
        record.record_busy_tick = record_busy_tick
        record.record_device = record_device

    def since_reset(self) -> str:
        """What the current metrics record was told of, as the probe kept
        it: the record's own counters have to say the same."""
        prefills = self.work["prefill"][self._opened["prefill"]:]
        ticks = self.work["tick"][self._opened["tick"]:]
        return (f"{len(prefills)} prefill calls of "
                f"{sum(w['real'] for w in prefills)} real tokens, "
                f"{len(ticks)} busy ticks that read "
                f"{sum(w['rows'] for w in ticks)} stored rows")

    def programs(self) -> list:
        """``[{"kind", "start", "done", **work}]`` in completion order; a
        program whose work is not known yet (a tick still to be collected)
        holds none."""
        seen = {"prefill": 0, "tick": 0}
        out = []
        for kind, start, done in self.stamps:
            if kind not in seen:
                raise NotImplementedError(f"a watched program {kind!r}")
            k, known = seen[kind], self.work[kind]
            seen[kind] += 1
            work = known[k] if k < len(known) else {"real": 0, "rows": 0, "tokens": 0}
            out.append({"kind": kind, "start": start, "done": done, **work})
        return out


class LatentAttn:
    """What ``lib/serve_window.py`` asks of a family of model."""

    name = "serve_latent_attn"
    counter_keys = (
        "ticks", "decode_ticks", "prefills", "prefill_calls",
        "prefill_tokens_real", "prefill_tokens_padded",
        "latent_positions_read", "latent_bytes_per_position", "moe_calls",
        "moe_experts_touched_mean", "moe_rows_per_expert_max_over_mean",
        "slot_occupancy_mean", "queue_depth_mean", "busy_tick_ms_mean",
        "tick_device_wait_ms_mean", "tick_prefill_ms_mean",
        "launch_ahead_share", "tokens_out",
    )

    def build(self, run):
        from tpu_parallel.models import GPTLM

        cfg = model_config(run.config, run.cell["engine"])
        model = GPTLM(cfg)
        abstract = jax.eval_shape(
            lambda: model.init(
                {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
                train=False,
            )
        )["params"]
        served = getattr(jnp, run.cell["engine"]["served_parameters"])
        return types.SimpleNamespace(
            model=model, cfg=cfg, abstract=abstract, served=served,
            vocab=run.config["vocab_size"],
            params=weights.make_params(run.seed, abstract, dtype=served),
        )

    def engine_built(self, run, engine):
        run.log(f"latent_plan: {engine.latent_plan}")
        run.log(f"attn_plan: {engine.attn_plan}")
        run.log(f"moe_plan: {engine.moe_plan}")
        self.steps_per_tick = engine.decode_steps_per_tick
        self.kinds = engine.layer_kinds  # sublayers by kind over the depth
        self.pool = engine.pool
        self.probe = SpanProbe(engine)

    def window(self, run, opened: bool):
        run.log(f"window {'opens' if opened else 'closes'} with "
                f"{self.pool.n_slots - self.pool.n_free} of "
                f"{self.pool.n_slots} slots seated")
        if not opened:
            run.log(f"span probe, in the window: {self.probe.since_reset()}")

    def traced(self, run, trace_file) -> str:
        scopes = xplane_scopes.by_pattern(trace_file, SCOPES)
        run.facts["scopes"] = scopes
        trace = xplane.load(trace_file, (MARK,))
        ops = [
            op for dev in trace["devices"].values() for op in dev["ops"]
        ]
        marks = {int(name[len(MARK):]): end for name, _, end in trace["host"]}
        span = ops and mla_cost.span_work(
            self.probe.programs(), marks, min(s for _, s, _ in ops),
            max(e for _, _, e in ops), self.steps_per_tick,
            self.kinds["attention"],
        )
        if span:  # the traced span's own work
            run.facts["span_mla"] = span
            # the experts' passes follow the same programs: a decode step and
            # a prefill call run every expert layer once
            run.facts["traced_experts"] = mla_cost.span_expert_passes(
                span, run.counters, self.steps_per_tick,
                self.kinds.get("experts", 0), run.config["n_routed_experts"],
            )
        return (f"device time by scope: {scopes}; the trace holds "
                f"{len(marks)} completion marks of {len(self.probe.stamps)} "
                f"programs; the span's attention cores: "
                f"{run.facts.get('span_mla')}; the span's expert passes: "
                f"{run.facts.get('traced_experts')}")

    def closed(self, run, engine, built):
        plan, config = engine.latent_plan, run.config
        value_bytes = jnp.dtype(built.cfg.dtype).itemsize
        run.facts["mla"] = {
            "layers": plan["layers"], "heads": plan["heads"],
            "qk": plan["nope_dim"] + plan["rope_dim"], "v": plan["v_dim"],
            "kv_rank": plan["kv_rank"], "row": plan["row"],
            "bytes_per_value": value_bytes,
        }
        run.facts["experts"] = {
            "d_model": config["hidden_size"],
            "width": config["moe_intermediate_size"],
            "bytes_per_value": jnp.dtype(built.served).itemsize,
        }
        # The daemon is drained and its pump has ended: nothing runs the
        # engine again.  Its weights and pool (12.1 GB) are deleted HERE and
        # not left to the collector (PR 45: a handler thread of the HTTP
        # server can outlive lib/serve_window.py's patience, and the
        # comparison then starts on a chip that is three quarters full)
        freed = 0
        for leaf in jax.tree.leaves((built.params, engine.pool.cache)):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                freed += leaf.nbytes
                leaf.delete()
        self.pool = None
        run.log(f"engine's weights and pool deleted: {freed / 1e9:.2f} GB")

    def compare(self, run, ended, requests, built):
        in_use = (jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
        if in_use > FREED_BOUND_BYTES:
            raise RuntimeError(
                f"{in_use / 1e9:.2f} GB are still in use on the device where "
                f"the comparison may begin on {FREED_BOUND_BYTES / 1e9:.2f} GB: "
                "something besides the engine's weights and pool (deleted in "
                "closed()) was left there, and the reference's first layers "
                "would run out of memory"
            )
        compare(run, ended, requests, built)


def run(run) -> None:
    """The window, with this cell's deal of the traffic handed to it for the
    length of the run."""
    before = serve_window.traffic_lib
    serve_window.traffic_lib = WaveTraffic
    try:
        serve_window.run(run, LatentAttn())
    finally:
        serve_window.traffic_lib = before


def compare(run, done, requests, built) -> None:
    if not done:
        run.check("streams_compared", 1, 0)
        return
    shape, cfg = reference_shape(run.config), built.cfg
    rng = random.Random(run.seed ^ 0xC0FFEE)
    size = lambda r: len(requests[r["idx"]]["prompt"]) + len(r["tokens"])
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    sample = [longest] + rng.sample(
        rest, min(len(rest), run.cell["reference_streams"] - 1)
    )
    sample.sort(key=size)
    sequences, rows, served_tokens = [], [], []
    for r in sample:
        prompt = requests[r["idx"]]["prompt"]
        seq = prompt + r["tokens"]
        padded = min(-(-len(seq) // REFERENCE_PAD) * REFERENCE_PAD, cfg.seq_len)
        sequences.append(jnp.asarray(seq + [0] * (padded - len(seq)), jnp.int32))
        rows.append(slice(len(prompt) - 1, len(seq) - 1))
        served_tokens.append(jnp.asarray(r["tokens"], jnp.int32))
    memory = MemoryWatch()

    def reference(**kw):
        """Every sampled stream through the reference, each layer's weights
        made once (from the seed, in the served type, upcast) for all and
        dropped before the next layer's are made."""
        ref_weights = pangu_weights.to_reference(
            run.seed, built.abstract, cfg.n_heads, dtype=built.served
        )
        memory("top-level weights")
        return pangu_ultra_moe_ref.forward_each(
            ref_weights, sequences, shape, rows=rows, watch=memory, **kw
        )

    def gap_of(full, pick):
        return jnp.max(full, axis=-1) - jnp.take_along_axis(
            full, pick[:, None], axis=-1
        )[:, 0]

    def read(gaps):
        worst = max(float(jnp.max(g)) for g in gaps)
        return worst, sum(int(jnp.sum(g > 0)) for g in gaps)

    t0 = time.perf_counter()
    logits = reference()
    jax.block_until_ready(logits)
    run.log(f"reference: {len(sequences)} sequences of "
            f"{[len(s) for s in sequences]} positions (padded) in "
            f"{time.perf_counter() - t0:.1f}s; logits std "
            f"{float(jnp.std(logits[-1])):.4f}")
    count = sum(len(t) for t in served_tokens)
    worst, off_best = read([gap_of(l, t) for l, t in zip(logits, served_tokens)])
    run.log(f"reference: {len(sample)} streams, {count} served tokens "
            f"(longest {size(longest)} positions); {off_best} tokens are "
            f"not the fp32 best; widest gap {worst:.6g}")
    run.check("longest_stream_passes",
              run.cell["longest_stream_passes"] + 1, size(longest))
    limits = run.cell["limits"]
    run.check("served_logit_gap", worst, limits["served_logit_gap"])
    run.check("served_off_best_share", 100.0 * off_best / count,
              limits["served_off_best_share"])
    if run.control:
        run.facts["control"] = {}
        witness = run.config["precision"]["compute"]
        for name, precision in (
            (run.cell["control_precision"], run.cell["control_precision"]),
            # a WITNESS, not a control: the reference itself in the precision
            # the program computes in, code that shares nothing with it
            (f"witness_{witness}", witness),
        ):
            low = reference(precision=precision)
            ctl_worst, ctl_off = read([
                gap_of(l, jnp.argmax(c, axis=-1)) for l, c in zip(logits, low)
            ])
            moved = max(
                float(jnp.max(jnp.abs(c - l))) for l, c in zip(logits, low)
            )
            numbers = {
                "served_logit_gap": ctl_worst,
                "served_off_best_share": 100.0 * ctl_off / count,
            }
            over = [k for k, v in numbers.items() if not v <= limits[k]]
            run.log(f"control {name}: "
                    + " ".join(f"{k}={v:.6g}" for k, v in numbers.items())
                    + f" (its logits lie at most {moved:.6g} from the fp32 "
                    f"ones; over its limit: {', '.join(over) or 'none'})")
            run.facts["control"][name] = dict(numbers, logit_move=moved, over=over)
    # what the comparison held: the top level (embedding, head), ONE layer's
    # float32 weights beside the served-type draw they are upcast from, and
    # the streams' activations with one block of scores; `bound` states the
    # first two and 4 GB for the rest
    top = 4 * (2 * cfg.vocab_size + 1) * cfg.d_model
    layer = pangu_weights.layer_bytes(built.abstract)
    run.facts["comparison_memory"] = {
        "sampled_peak_bytes": memory.peak,
        "bound_bytes": top + layer + layer // 2 + (4 << 30),
    }
    if memory.peak:  # the CPU's runtime reports none
        run.log("comparison memory, GB in use: " + ", ".join(
            f"{where} {held / 1e9:.2f}" for where, held in memory.samples
        ))
