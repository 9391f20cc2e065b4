"""Driver of the serving cells of one-sublayer-a-layer hybrid decoders
(``nemotron_h``: a layer is a Mamba-2 mixer, attention without positions, or
a LatentMoE, each behind one RMSNorm): HTTP/SSE traffic through the daemon,
as the other serving cells, with this family's model and its reference.

The same path: a ``ServingEngine`` (fixed-slot pool, fused tick of 8,
whole-prompt prefill in the cell's buckets) behind ``Frontend`` ->
``ServingDaemon`` -> ``DaemonHTTPServer`` on loopback, weights made on the
device from ``--seed`` in the type they are served in, load from a child
process (``lib/loadgen.py``) that never imports JAX: the window itself is
``lib/serve_window.py``, and this file is what belongs to the family:

- the model is built from the configuration file's own keys (the published
  ``config.json`` keys): layer kinds from ``hybrid_override_pattern``, the
  Mamba-2 sizes, the router's and the experts' sizes; the held experts and
  the router's width from ``n_routed_experts`` and ``published``;
- weights come from ``lib/nemotron_weights.py`` (the Mamba-2 ranges, a
  selection bias that is not zero);
- a slot holds a recurrent state a ``M`` layer, a K/V stripe a ``*`` layer
  and nothing for an ``E`` layer: the engine's ``ssm_plan``, ``moe_plan`` and
  ``attn_plan`` are logged;
- the reference is ``reference/nemotron_h_ref.py``, its layers made one at a
  time: ONE layer's float32 weights are on the device at a time (the share is
  18.6 GB in float32), and the memory the comparison holds is sampled after
  each layer (``run.facts["comparison_memory"]``).

``correct``, as ``drivers/serve_hybrid.py`` reads it (its ``StateProbe``,
with this family's pick of the state leaves, and ``state_numbers`` as it
is): the longest stream that ended in the window and a seeded reservoir of
the rest, picked as the engine retires them with the slot's recurrent states read out of the timed pool; once the
engine is freed they go through the reference from token 0 in one sequential
pass.  Four numbers, each with its limit: ``served_logit_gap``,
``served_off_best_share``, ``served_state_gap``,
``served_state_bfloat16_share``.  ``--control 1`` also reads two controls,
the reference with float8 operands and the reference with its recurrent state
rounded to bfloat16 after every step.  (``compare`` is ``serve_hybrid``'s with
this family's reference, weights and a watch on the memory it holds: that file
names its own reference inside the function, and folding the two onto one is
a ``benchmark`` PR's, as for the window's older copies.)
"""

import time
import types

import jax
import jax.numpy as jnp

from drivers.serve_blockgen import MemoryWatch
from drivers.serve_hybrid import StateProbe, state_numbers
from lib import nemotron_weights, serve_window, ssm_cost
from lib import xplane_counts, xplane_scopes
from reference import nemotron_h_ref

# device time is read by scope; the grouped matmuls' custom calls carry no
# scope and are found by their op name; `sort` is the sampler (no scope)
MOE_OPS = r"moe\.|ragged-dot"
LATENT_OPS = r"moe\.latent_"
SCOPES = (r"ssm\.", r"ssm\.in_proj", r"ssm\.conv", r"ssm\.scan", r"ssm\.step",
          r"ssm\.gate_norm", r"ssm\.out_proj", MOE_OPS, r"moe\.router",
          LATENT_OPS, r"moe\.experts", r"moe\.shared", r"ragged-dot",
          r"ragged-dot-streamed", r"ragged-dot-none", r"attn\.full", r"^sort")
# how often the compiled ops of the recurrence ran: the span's own work
RUNS = {"step": r"ssm\.step", "scan": r"ssm\.scan/call(\d+)x(\d+)"}
# grouped-matmul ops a layer's pass of two-matrix experts: the streamed
# kernel is called twice (up, then down: ops/grouped_ffn.py), lax.ragged_dot
# twice (models/moe.py::_relu2_ffn)
KERNELS_PER_CALL = {"ragged-dot-streamed": 2, "ragged-dot-none": 2}
# what the device may still hold when the comparison begins (the probe's
# states are on the host by then): sound runs read 0.13 GB, the engine's
# weights and pool are 12.56 GB (my chip runs, PR 45)
FREED_BOUND_BYTES = 256 << 20


def model_config(config: dict, engine: dict):
    """The program's ``GPTConfig`` for a configuration file of this family
    (its top level holds the published keys, cut as ``reduced`` says)."""
    from tpu_parallel.models.gpt import one_sublayer_decoder
    from tpu_parallel.models.layers import ExpertsSpec, SSMSpec

    depth = config["num_hidden_layers"]
    heads = config["mamba_num_heads"]
    if heads * config["mamba_head_dim"] != config["expand"] * config["hidden_size"]:
        raise ValueError("mamba heads x head width is not expand x hidden size")
    if (config["attention_bias"] or config["mlp_bias"] or config["use_bias"]
            or config["mamba_proj_bias"] or not config["use_conv_bias"]
            or config["tie_word_embeddings"] or not config["norm_topk_prob"]
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["num_nextn_predict_layers"]
            or config["mamba_hidden_act"] != "silu"
            or config["layer_norm_epsilon"] != config["norm_eps"]):
        raise ValueError("a key of this family that the driver does not build")
    return one_sublayer_decoder(
        pattern=config["hybrid_override_pattern"][:depth],
        ssm=SSMSpec(
            n_heads=heads, head_dim=config["mamba_head_dim"],
            d_state=config["ssm_state_size"], n_groups=config["n_groups"],
            d_conv=config["conv_kernel"], chunk=config["chunk_size"],
        ),
        experts=ExpertsSpec(
            n_experts=config["published"]["n_routed_experts"],
            top_k=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"],
            score="sigmoid",
            shared=config["n_shared_experts"],
            held=(0, config["n_routed_experts"]),
            latent=config["moe_latent_size"],
            ffn=config["mlp_hidden_act"],
            shared_width=config["moe_shared_expert_intermediate_size"],
            shared_sum=True,
            select_bias=True,
            route_scale=float(config["routed_scaling_factor"]),
        ),
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=depth,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        seq_len=engine["slot_positions"],
        norm_eps=config["norm_eps"],
        dtype=getattr(jnp, config["precision"]["compute"]),
        remat=False,
        prefill_flash=True,
        **engine.get("model_overrides", {}),
    )


def reference_shape(config: dict) -> dict:
    return {
        "pattern": config["hybrid_override_pattern"][:config["num_hidden_layers"]],
        "eps": config["norm_eps"],
        "held": (0, config["n_routed_experts"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        **{k: config[k] for k in (
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
            "conv_kernel", "num_experts_per_tok",
        )},
    }


def parameters(config: dict) -> int:
    """The share's parameters, counted from the file's keys."""
    d, pattern = config["hidden_size"], reference_shape(config)["pattern"]
    d_inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    conv = d_inner + 2 * config["n_groups"] * config["ssm_state_size"]
    heads, kv, hd = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    latent, width = config["moe_latent_size"], config["moe_intermediate_size"]
    kinds = {
        "M": d * (d_inner + conv + config["mamba_num_heads"])
        + conv * config["conv_kernel"] + conv
        + 3 * config["mamba_num_heads"] + d_inner + d_inner * d,
        "*": 2 * d * heads * hd + 2 * d * kv * hd,
        "E": d * config["published"]["n_routed_experts"]
        + config["published"]["n_routed_experts"] + 2 * d * latent
        + config["n_shared_experts"] * 2 * d
        * config["moe_shared_expert_intermediate_size"]
        + config["n_routed_experts"] * 2 * latent * width,
    }
    return (sum(kinds[k] + d for k in pattern)
            + 2 * config["vocab_size"] * d + d)


class SlotProbe(StateProbe):
    """``serve_hybrid.StateProbe`` over a pool in which only the ``M`` layers
    hold a state (five of eleven layers have no cache leaf at all)."""

    def read(self, slot):
        self.reads += 1
        return nemotron_weights.slot_states(self.engine.pool.extract(slot))


class LatentMoE:
    """What ``lib/serve_window.py`` asks of a family of model."""

    name = "serve_latent_moe"
    counter_keys = (
        "ticks", "decode_ticks", "prefills", "prefill_calls",
        "prefill_tokens_real", "prefill_tokens_padded",
        "state_bytes_per_slot", "moe_calls", "moe_experts_touched_mean",
        "moe_rows_per_expert_max_over_mean", "decode_tiles_walked_share",
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
            params=nemotron_weights.make_params(run.seed, abstract, dtype=served),
        )

    def engine_built(self, run, engine):
        run.log(f"ssm_plan: {engine.ssm_plan}")
        run.log(f"moe_plan: {engine.moe_plan}")
        run.log(f"attn_plan: {engine.attn_plan}")
        self.steps_per_tick = engine.decode_steps_per_tick
        self.probe = SlotProbe(engine, run.seed, run.cell["reference_streams"])

    def window(self, run, opened: bool):
        self.probe.active = opened
        # the closed loop's state at the window's edges: 192 clients submit
        # at once and their first submits queue on the pump's lock, so how
        # full the pool is when the window opens is worth a line
        pool = self.probe.engine.pool
        run.log(f"window {'opens' if opened else 'closes'} with "
                f"{pool.n_slots - pool.n_free} of {pool.n_slots} slots seated")

    def traced(self, run, trace_file) -> str:
        scopes = xplane_scopes.by_pattern(trace_file, SCOPES)
        run.facts["scopes"] = scopes
        runs = xplane_counts.executions(trace_file, RUNS)
        if runs is not None:  # the traced span's own work
            run.facts["span_ssm"] = ssm_cost.span_work(
                runs["step"].get((), {}), runs["scan"], run.counters,
                self.steps_per_tick,
            )
        if scopes:
            # a layer's pass of the experts is a fixed number of
            # grouped-matmul ops, so the ops the trace holds say how many
            # passes it held; a pass's rows and touched experts are the
            # window's means (the counters move a tick at a time)
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
        return (f"device time by scope: {scopes}; the span's recurrence: "
                f"{run.facts.get('span_ssm')}; the span's expert passes: "
                f"{run.facts.get('traced_experts')}")

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
        run.facts["experts"] = {
            "latent": config["moe_latent_size"],
            "width": config["moe_intermediate_size"],
            "bytes_per_value": jnp.dtype(built.served).itemsize,
        }
        self.held = self.probe.close()
        run.log(f"state probe: {self.probe.seen + bool(self.held)} streams "
                f"ended in the window, {self.probe.reads} slot states read, "
                f"{len(self.held)} held")
        # The daemon is drained and its pump has ended: nothing runs the
        # engine again.  Its weights and pool (12.56 GB) are deleted HERE and
        # not left to the collector: in 2 runs of 7 a handler thread of the
        # HTTP server outlived lib/serve_window.py's 60 s of patience, the
        # engine stayed referenced and the comparison ran out of device
        # memory at its first layer (my chip runs, PR 45)
        freed = 0
        for leaf in jax.tree.leaves((built.params, engine.pool.cache)):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                freed += leaf.nbytes
                leaf.delete()
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
        compare(run, self.held, ended, requests, built)


def run(run) -> None:
    serve_window.run(run, LatentMoE())


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
    # every stream is padded to a slot's positions (ONE shape a kind of layer,
    # whatever the seed's lengths: a compile of a block costs as much as its
    # run) and its head reads one block of `most` rows from its prompt's last
    # position on; what lies after the last served token is padding that, in
    # a causal model, changes no row that is compared
    most = max(r["max_new_tokens"] for r in requests)
    padded = built.cfg.seq_len
    sequences, rows, served_tokens, fed = [], [], [], []
    for s, r in sample:
        seq = list(s.prompt) + r["tokens"]
        first = len(s.prompt) - 1
        if first + most > padded:
            raise ValueError(f"a prompt of {first + 1} and {most} rows past {padded}")
        sequences.append(jnp.asarray(seq + [0] * (padded - len(seq)), jnp.int32))
        rows.append(slice(first, first + most))
        served_tokens.append(jnp.asarray(r["tokens"], jnp.int32))
        fed.append(len(seq) - 2)  # the last token FED: the last served is not
    slot_states = [s.state for s, _ in sample]
    memory, marks = MemoryWatch(), []

    def watch(where):
        memory(where)
        marks.append((where, time.perf_counter()))

    def reference(**kw):
        """Every sampled stream through the reference, each layer's weights
        made once (from the seed, in the served type, upcast) for all and
        dropped before the next layer's are made: the logits, and the
        recurrent states the last fed token left."""
        ref_weights = nemotron_weights.to_reference(
            run.seed, built.abstract, built.cfg.n_heads, built.cfg.n_kv_heads,
            dtype=built.served,
        )
        watch("top-level weights")
        return nemotron_h_ref.forward_each(
            ref_weights, sequences, shape, rows=rows, keep=fed, watch=watch,
            **kw,
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
    by_kind = {}
    for (_, before), (where, after) in zip(marks, marks[1:]):
        by_kind[where[-1]] = by_kind.get(where[-1], 0.0) + after - before
    run.log(f"reference: {len(sequences)} sequences of "
            f"{[len(s) for s in sequences]} positions (padded) in "
            f"{time.perf_counter() - t0:.1f}s (top level "
            f"{marks[0][1] - t0:.1f}s, layers by kind "
            f"{ {k: round(v, 1) for k, v in by_kind.items()} }, head "
            f"{time.perf_counter() - marks[-1][1]:.1f}s); logits std "
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
        passes = [
            (run.cell["control_precision"],
             {"precision": run.cell["control_precision"]}),
            (f"state_{run.cell['control_state_precision']}",
             {"state_precision": run.cell["control_state_precision"]}),
        ]
        # a WITNESS, not a control: the reference itself in the precision the
        # program computes in.  What it reads is what that precision does to
        # this model in an implementation that shares no code with the
        # program; a program that reads far more has a fault of its own
        witness = run.config["precision"]["compute"]
        passes.append((f"witness_{witness}", {"precision": witness}))
        for name, kw in passes:
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
    # what the comparison held: the top level (embedding and head), ONE
    # layer's float32 weights beside the bfloat16 draw they are upcast from,
    # and the streams' activations; `bound` states the first two
    top = 4 * (2 * built.cfg.vocab_size + 1) * built.cfg.d_model
    layer = nemotron_weights.layer_bytes(built.abstract)
    run.facts["comparison_memory"] = {
        "sampled_peak_bytes": memory.peak,
        "bound_bytes": top + layer + layer // 2 + (2 << 30),
    }
    if memory.peak:  # the CPU's runtime reports none
        run.log("comparison memory, GB in use: " + ", ".join(
            f"{where} {held / 1e9:.2f}" for where, held in memory.samples
        ))
