"""Driver of the serving cells of hybrid decoders (recurrent Mamba-2 layers
beside attention layers): HTTP/SSE traffic through the daemon, as
``drivers/serve.py`` does for GPT-2, with this family's model and this
family's reference.

The same path: a ``ServingEngine`` (fixed-slot pool, fused tick of 8) behind
``Frontend`` -> ``ServingDaemon`` -> ``DaemonHTTPServer`` on loopback, weights
made on the device from ``--seed`` in the type they are served in, load from a
child process (``lib/loadgen.py``) that never imports JAX: the window itself
is ``lib/serve_window.py``, and this file is what belongs to the family:

- the model is built from the configuration file's own keys (the published
  ``config.json`` keys): layer kinds from ``layer_types``, the Mamba-2 sizes
  and the four stated multipliers;
- weights come from ``lib/granite_weights.py`` (the Mamba-2 ranges for the
  leaves a recurrence is sensitive to);
- prompts are prefilled WHOLE, ``prefill_batch`` a call, padded to a bucket of
  the cell's ladder (a dummy row or a padded position runs the scan and every
  matmul at full cost: ``engine.prefill_pad_share`` reads what that is);
- a slot holds a recurrent state of one size beside its K/V stripe: the
  engine's ``ssm_plan`` is logged;
- the reference is ``reference/granite_hybrid_ref.py``, its layers made one at
  a time.

``correct``: the streams compared are picked WHILE the window runs, as the
engine retires them (``StateProbe``: the longest that ended in the window and
a seeded reservoir of the rest), because what a stream leaves in its slot is
gone once the slot is seated again: the probe reads the slot's ``ssm_state``
rows out of the timed engine's pool when the stream ends.  After the engine
and its weights are freed, those streams go through the reference from token
0, prompt and served tokens in one sequential pass: what bucket-padded
prefill plus hundreds of one-token state updates produced is held to one
uninterrupted recurrence.  Four numbers, each with its limit:
``served_logit_gap``, the widest gap of a served token's fp32 logit under the
fp32 best; ``served_off_best_share``, the share (%) of served tokens that are
not the reference's best; ``served_state_gap``, the largest distance, over
streams and recurrent layers, between the state the slot held and the
reference's after the same tokens, as a share of the reference's norm; and
``served_state_bfloat16_share``, the share (%) of the numbers of those states
that a bfloat16 holds exactly (a float32 state: one in 65536; a state kept or
rounded in bfloat16: all of them).  ``--control 1`` also reads two controls,
the reference with float8 operands and the reference with its recurrent state
rounded to bfloat16 after every step (everything else float32): what each
would serve, read in the fp32 logits, and the states each would leave.
"""

import random
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from lib import granite_weights, serve_window, ssm_cost
from lib import xplane_counts, xplane_scopes
from reference import granite_hybrid_ref

REFERENCE_PAD = 256  # reference sequences pad to a multiple: few shapes
# device time is read by scope; `sort` is the sampler (it has no scope)
SCOPES = (r"ssm\.", r"ssm\.in_proj", r"ssm\.conv", r"ssm\.scan", r"ssm\.step",
          r"ssm\.gate_norm", r"ssm\.out_proj", r"attn\.full", r"^sort")
# how often the compiled ops of the recurrence ran: the span's own work
RUNS = {"step": r"ssm\.step", "scan": r"ssm\.scan/call(\d+)x(\d+)"}
KINDS = {"mamba": "ssm", "attention": "attention"}


def model_config(config: dict, engine: dict):
    """The program's ``GPTConfig`` for a configuration file of this family
    (its top level holds the published keys)."""
    from tpu_parallel.models.gpt import hybrid_ssm_decoder
    from tpu_parallel.models.layers import SSMSpec

    depth = config["num_hidden_layers"]
    kinds = [KINDS[k] for k in config["layer_types"][:depth]]
    period = next(
        p for p in range(1, depth + 1)
        if depth % p == 0 and kinds == kinds[:p] * (depth // p)
    )
    heads = config["mamba_n_heads"]
    if heads * config["mamba_d_head"] != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba heads x head width is not expand x hidden size")
    if config["num_local_experts"] or config["mamba_proj_bias"] or not (
        config["mamba_conv_bias"] and config["tie_word_embeddings"]
    ) or config["position_embedding_type"] != "nope":
        raise ValueError("a key of this family that the driver does not build")
    return hybrid_ssm_decoder(
        pattern=tuple(kinds[:period]),
        ssm=SSMSpec(
            n_heads=heads, head_dim=config["mamba_d_head"],
            d_state=config["mamba_d_state"], n_groups=config["mamba_n_groups"],
            d_conv=config["mamba_d_conv"], chunk=config["mamba_chunk_size"],
        ),
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=depth,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        mlp_dim=config["shared_intermediate_size"],
        seq_len=engine["slot_positions"],
        norm_eps=config["rms_norm_eps"],
        attn_scale=float(config["attention_multiplier"]),
        embed_scale=float(config["embedding_multiplier"]),
        residual_scale=float(config["residual_multiplier"]),
        logit_scale=1.0 / float(config["logits_scaling"]),
        dtype=getattr(jnp, config["precision"]["compute"]),
        remat=False,
        prefill_flash=True,
        **engine.get("model_overrides", {}),
    )


def reference_shape(config: dict) -> dict:
    return {
        "layer_types": tuple(config["layer_types"][:config["num_hidden_layers"]]),
        "eps": config["rms_norm_eps"],
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        **{k: config[k] for k in (
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv",
        )},
    }


class StateProbe:
    """Reads, out of the timed engine's pool, the recurrent state a finished
    stream left in its slot, and so picks the streams that are compared.

    ``release_slot`` is wrapped from outside (as ``annotate`` wraps
    ``launch``): the engine retires a stream there, on the pump's thread,
    with the slot's row still what the stream's last fed token left.  The
    device carries the slot as dead from the step that sampled its last
    token, so the tick in flight runs it as a pad (position -1), which by the
    mixer's pad rule changes no state; the slot is seated again only by a
    later launch, and ``pool.extract`` is queued before that.  The state
    is the one after ``prompt + tokens[:-1]``: the last token is sampled and
    never fed.

    While ``active`` (the window) it holds, on the device, at most ``most``
    streams' states: the longest stream so far and a reservoir of the others
    (each of them equally likely, drawn from the seed).  One read happens
    before the window, so that ``extract`` is compiled in set-up."""

    def __init__(self, engine, seed: int, most: int):
        self.engine = engine
        self.rng = random.Random(seed ^ 0x57A7E)
        self.most = most
        self.active = False
        self.warmed = False
        self.longest = None
        self.rest = []
        self.seen = 0  # streams offered to the reservoir
        self.reads = 0
        inner = engine.release_slot

        def release_slot(slot):
            out = engine._slot_out[slot]
            if out is not None and out.finish_reason == "length":
                self.ended(slot, out)
            inner(slot)

        engine.release_slot = release_slot

    def read(self, slot):
        """ONE program dispatched on the pump's thread and nothing else:
        every further op here (a slice a leaf, say) is a dispatch more inside
        a tick, and 36 of them a read made the cell's tick 5% longer."""
        self.reads += 1
        return granite_weights.slot_states(self.engine.pool.extract(slot))

    def ended(self, slot, out):
        if not self.active:
            if not self.warmed:
                self.read(slot)
                self.warmed = True
            return
        new = types.SimpleNamespace(
            prompt=tuple(out.request.prompt), tokens=len(out.tokens),
            state=None,
        )
        size = lambda s: len(s.prompt) + s.tokens
        if self.longest is None or size(new) > size(self.longest):
            new.state = self.read(slot)
            new, self.longest = self.longest, new
            if new is None:
                return
        self.seen += 1
        keep = self.most - 1
        at = len(self.rest) if len(self.rest) < keep else self.rng.randrange(self.seen)
        if at < keep:
            if new.state is None:
                new.state = self.read(slot)
            self.rest[at:at + 1] = [new]

    def close(self) -> list:
        """The streams held, their states on the host; the engine let go."""
        held = ([self.longest] if self.longest else []) + self.rest
        for s in held:
            s.state = [np.asarray(x)[0] for x in s.state]
        self.engine = None
        return held


class Hybrid:
    """What ``lib/serve_window.py`` asks of a family of model."""

    name = "serve_hybrid"
    counter_keys = (
        "ticks", "decode_ticks", "prefills", "prefill_calls",
        "prefill_tokens_real", "prefill_tokens_padded",
        "state_bytes_per_slot", "slot_occupancy_mean", "queue_depth_mean",
        "busy_tick_ms_mean", "tick_device_wait_ms_mean",
        "tick_prefill_ms_mean", "launch_ahead_share", "tokens_out",
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
            params=granite_weights.make_params(run.seed, abstract, dtype=served),
        )

    def engine_built(self, run, engine):
        run.log(f"ssm_plan: {engine.ssm_plan}")
        self.steps_per_tick = engine.decode_steps_per_tick
        self.probe = StateProbe(engine, run.seed, run.cell["reference_streams"])

    def window(self, run, opened: bool):
        self.probe.active = opened

    def traced(self, run, trace_file) -> str:
        scopes = xplane_scopes.by_pattern(trace_file, SCOPES)
        run.facts["scopes"] = scopes
        runs = xplane_counts.executions(trace_file, RUNS)
        if runs is not None:  # the traced span's own work
            run.facts["span_ssm"] = ssm_cost.span_work(
                runs["step"].get((), {}), runs["scan"], run.counters,
                self.steps_per_tick,
            )
        span = run.facts.get("span_ssm")
        if span and run.seconds > 0:  # a cross-check, not a metric
            rate = run.counters.get("decode_ticks", 0) * self.steps_per_tick / run.seconds
            span["decode_steps_by_rate"] = rate * scopes["busy_s"]
        return f"device time by scope: {scopes}; the span's work: {span}"

    def closed(self, run, engine, built):
        plan, config = engine.ssm_plan, run.config
        if built.cfg.scan_layers:
            raise NotImplementedError(
                "the span's work is counted for unrolled layers (each "
                "compiled op runs once a step)"
            )
        run.facts["ssm"] = {
            "layers": plan["ssm_layers"], "heads": plan["heads"],
            "head_dim": plan["head_dim"], "d_state": plan["d_state"],
            "groups": plan["groups"],
            "state_bytes": jnp.dtype(config["precision"]["recurrent_state"]).itemsize,
            "bytes_per_value": jnp.dtype(built.cfg.dtype).itemsize,
        }
        self.held = self.probe.close()
        run.log(f"state probe: {self.probe.seen + bool(self.held)} streams "
                f"ended in the window, {self.probe.reads} slot states read, "
                f"{len(self.held)} held")

    def compare(self, run, ended, requests, built):
        compare(run, self.held, ended, requests, built)


def run(run) -> None:
    serve_window.run(run, Hybrid())


def state_numbers(states, reference_states) -> tuple:
    """``(gap, share)`` of the states ``[stream][layer] -> [H, P, N]``: the
    largest distance from the reference's state as a share of its norm, and
    the share (%) of all their numbers that a bfloat16 holds exactly (the low
    16 bits of the float32 are zero)."""
    gap, exact, count = 0.0, 0, 0
    for ours, theirs in zip(states, reference_states):
        if len(ours) != len(theirs):
            raise ValueError(f"{len(ours)} states against {len(theirs)}")
        for a, r in zip(ours, theirs):
            a = np.ascontiguousarray(np.asarray(a), np.float32)
            r = np.asarray(r, np.float64)
            gap = max(gap, float(np.linalg.norm(a - r) / np.linalg.norm(r)))
            exact += int(np.count_nonzero((a.view(np.uint32) & 0xFFFF) == 0))
            count += a.size
    return gap, 100.0 * exact / count


def compare(run, held, ended, requests, built) -> None:
    shape = reference_shape(run.config)
    by_prompt = {tuple(requests[r["idx"]]["prompt"]): r for r in ended}
    sample = [(s, by_prompt[s.prompt]) for s in held
              if len(by_prompt.get(s.prompt, {"tokens": ()})["tokens"]) == s.tokens]
    if not sample:
        run.check("streams_compared", 1, 0)
        return
    size = lambda pair: len(pair[0].prompt) + pair[0].tokens
    sample.sort(key=size)
    # every stream's head reads one block of `most` rows from its prompt's
    # last position on (one shape for the [rows, vocabulary] product, whatever
    # the seed's lengths); what lies after the last served token is padding
    # that, in a causal model, changes no row that is compared
    most = max(r["max_new_tokens"] for r in requests)
    sequences, rows, served_tokens, fed = [], [], [], []
    for s, r in sample:
        seq = list(s.prompt) + r["tokens"]
        first = len(s.prompt) - 1
        padded = -(-(first + most) // REFERENCE_PAD) * REFERENCE_PAD
        sequences.append(jnp.asarray(seq + [0] * (padded - len(seq)), jnp.int32))
        rows.append(slice(first, first + most))
        served_tokens.append(jnp.asarray(r["tokens"], jnp.int32))
        fed.append(len(seq) - 2)  # the last token FED: the last served is not
    slot_states = [s.state for s, _ in sample]

    def reference(**kw):
        """Every sampled stream through the reference, each layer's weights
        made once (from the seed, in the served type, upcast) for all: the
        logits, and the recurrent states the last fed token left."""
        ref_weights = granite_weights.to_reference(
            run.seed, built.abstract, built.cfg.n_heads, built.cfg.n_kv_heads,
            dtype=built.served,
        )
        return granite_hybrid_ref.forward_each(
            ref_weights, sequences, shape, rows=rows, keep=fed, **kw
        )

    def gap_of(full, pick):
        return jnp.max(full, axis=-1) - jnp.take_along_axis(
            full, pick[:, None], axis=-1
        )[:, 0]

    def read(gaps):
        worst = max(float(jnp.max(g)) for g in gaps)
        return worst, sum(int(jnp.sum(g > 0)) for g in gaps)

    t0 = time.perf_counter()
    logits, states = reference()
    jax.block_until_ready(logits)
    run.log(f"reference: {len(sequences)} sequences of "
            f"{[len(s) for s in sequences]} positions (padded) in "
            f"{time.perf_counter() - t0:.1f}s; logits std "
            f"{float(jnp.std(logits[0])):.4f}")
    count = sum(len(t) for t in served_tokens)
    logits = [l[:len(t)] for l, t in zip(logits, served_tokens)]
    worst, off_best = read([gap_of(l, t) for l, t in zip(logits, served_tokens)])
    state_gap, state_share = state_numbers(slot_states, states)
    run.log(f"reference: {len(sample)} streams, {count} served tokens "
            f"(longest {size(sample[-1])} positions); {off_best} tokens are "
            f"not the fp32 best; widest gap {worst:.6g}; {len(states[0])} "
            f"states a stream read out of the engine's slots")
    limits = run.cell["limits"]
    run.check("served_logit_gap", worst, limits["served_logit_gap"])
    run.check("served_off_best_share", 100.0 * off_best / count,
              limits["served_off_best_share"])
    run.check("served_state_gap", state_gap, limits["served_state_gap"])
    run.check("served_state_bfloat16_share", state_share,
              limits["served_state_bfloat16_share"])
    if run.control:
        run.facts["control"] = {}
        for name, kw in (
            (run.cell["control_precision"],
             {"precision": run.cell["control_precision"]}),
            (f"state_{run.cell['control_state_precision']}",
             {"state_precision": run.cell["control_state_precision"]}),
        ):
            low, low_states = reference(**kw)
            low = [l[:len(t)] for l, t in zip(low, served_tokens)]
            ctl_worst, ctl_off = read([
                gap_of(l, jnp.argmax(c, axis=-1)) for l, c in zip(logits, low)
            ])
            moved = max(
                float(jnp.max(jnp.abs(c - l))) for l, c in zip(logits, low)
            )
            ctl_gap, ctl_share = state_numbers(low_states, states)
            numbers = {
                "served_logit_gap": ctl_worst,
                "served_off_best_share": 100.0 * ctl_off / count,
                "served_state_gap": ctl_gap,
                "served_state_bfloat16_share": ctl_share,
            }
            over = [k for k, v in numbers.items() if not v <= limits[k]]
            run.log(f"control {name}: "
                    + " ".join(f"{k}={v:.6g}" for k, v in numbers.items())
                    + f" (its logits lie at most {moved:.6g} from the fp32 "
                    f"ones; over its limit: {', '.join(over) or 'none'})")
            run.facts["control"][name] = dict(
                numbers, logit_move=moved, over=over
            )
