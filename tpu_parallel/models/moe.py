"""Mixture-of-Experts MLP with expert parallelism (top-k routing).

``moe_top_k=1`` is Switch (gate = raw router probability); ``>1`` is
GShard-style with gates renormalized over the chosen experts and capacity
claimed choice-major under the same static-shape dispatch.

No reference capability exists (SURVEY.md §2.2: EP "Absent"); built for the
framework's EP slot, TPU-first:

- **Static shapes everywhere**: capacity-based routing (``capacity_factor``)
  with one-hot dispatch/combine einsums — the Mesh-TensorFlow/Switch
  formulation that XLA compiles to dense MXU work, no dynamic gather.
- **Expert parallelism over the ``model`` mesh axis**: each rank owns
  ``n_experts / ep`` experts (weights stacked per-rank via ModuleShard, so
  gradient sync already treats them as partitioned).  Activations are
  replicated over the model axis (the batch shards over data/seq), so
  dispatch needs **no communication at all**: each rank slices out its own
  experts' dispatch/combine masks, runs only its experts (``1/ep`` of the
  expert FLOPs), and the partial combines close with one ``psum`` — the
  same collective shape as a TP row-parallel projection, so the existing
  pmean-over-model gradient sync stays exact.
- **Router in fp32** (numerically fragile softmax over experts), activations
  in the model dtype.
- Load-balance auxiliary loss (Switch: ``E * sum(f_i * P_i)``) sown into a
  ``"losses"`` collection; ``make_gpt_loss`` folds it into the objective.
  ``aux_scale`` gates the sown value — the pipeline schedule passes 0.0 on
  bubble ticks so garbage activations contribute exactly zero to (and take
  no gradient from) the router regularizer.

Works mesh-free too (no bound model axis): all experts live on the one
device, no slicing, no psum — same module, same params layout rules as the
rest of the structural-TP design.

:class:`RoutedExperts` is the DROPLESS layer (serving, and one chip's share
of a deployment): assignments sorted by expert, grouped matmuls whose work
follows the rows routed here, a buffer for the worst case, shared experts
beside the routed ones, and a ``held`` range of the experts.  :class:`MoEMLP`
above it keeps the capacity formulation for the training configurations
that use it; the serving engine refuses a model that would drop.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpu_parallel.ops.grouped_ffn import grouped_ffn, grouped_ffn_plan
from tpu_parallel.parallel.tp import ModuleShard, axis_size_or_none


class ExpertFFN(nn.Module):
    """One expert: the standard transformer FFN at model dtype.

    Projection outputs carry the same ``"proj"`` checkpoint names as the
    dense MLP (layers.py), so the proj/proj_attn remat policies save the
    expert matmuls instead of recomputing them in the backward.
    """

    config: "TransformerConfig"  # noqa: F821 — forward ref, see layers.py

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        hidden = cfg.mlp_dim
        if cfg.mlp == "swiglu":
            gate = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype, name="gate")(x)
            up = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype, name="up")(x)
            h = nn.silu(checkpoint_name(gate, "proj")) * checkpoint_name(up, "proj")
        else:
            h = nn.gelu(
                checkpoint_name(nn.Dense(hidden, dtype=cfg.dtype, name="up")(x), "proj")
            )
        return checkpoint_name(
            nn.Dense(cfg.d_model, dtype=cfg.dtype, name="down")(h), "proj"
        )


def _dispatch_masks(onehots, gates, n_experts: int, capacity: int):
    """[T, E, C] dispatch/combine one-hots from per-choice expert one-hots.

    Choices claim capacity slots choice-major (every token's first choice
    before any second choice), tracked by a running per-expert count so the
    slot index stays unique across choices.  Shared by the dense
    (full-token-set) and all_to_all (per-sender-slice) dispatch paths —
    only the token set and the capacity quota differ."""
    tokens = onehots[0].shape[0]
    count = jnp.zeros((n_experts,), jnp.float32)
    dispatch = jnp.zeros((tokens, n_experts, capacity), jnp.float32)
    combine = jnp.zeros((tokens, n_experts, capacity), jnp.float32)
    for j, onehot in enumerate(onehots):
        position = (jnp.cumsum(onehot, axis=0) - 1.0 + count[None, :]) * onehot
        in_capacity = (position < capacity).astype(jnp.float32) * onehot
        pos_idx = jnp.sum(position, axis=-1).astype(jnp.int32)  # [T]
        pos_onehot = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)
        # [T, E, C]: 1 where token t's choice j landed in slot c of expert e
        dispatch_j = in_capacity[:, :, None] * pos_onehot[:, None, :]
        dispatch = dispatch + dispatch_j
        combine = combine + dispatch_j * gates[:, j, None, None]
        count = count + jnp.sum(onehot, axis=0)
    return dispatch, combine


def _topk_gates(probs, top_k: int):
    """(gates [T, k], one-hots list) for top-k routing: Switch keeps the raw
    router probability at k=1; GShard renormalizes over the chosen experts
    so the combined output is a convex mixture."""
    n_experts = probs.shape[-1]
    gate_vals, expert_idx = lax.top_k(probs, top_k)  # [T, k] each
    if top_k == 1:
        gates = gate_vals
    else:
        gates = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    onehots = [
        jax.nn.one_hot(expert_idx[:, j], n_experts, dtype=jnp.float32)
        for j in range(top_k)
    ]
    return gates, onehots


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: top-k routed experts, EP over ``model``."""

    config: "TransformerConfig"  # noqa: F821

    @nn.compact
    def __call__(
        self, x: jax.Array, train: bool = True, aux_scale: jax.Array | None = None
    ) -> jax.Array:
        cfg = self.config
        n_experts = cfg.moe_experts
        ep_size = axis_size_or_none(cfg.model_axis) or 1
        if n_experts % ep_size != 0:
            raise ValueError(
                f"moe_experts={n_experts} not divisible by model axis {ep_size}"
            )
        local_experts = n_experts // ep_size
        b, s, d = x.shape
        tokens = b * s
        xf = x.reshape(tokens, d)

        # --- route (fp32) ---------------------------------------------------
        top_k = cfg.moe_top_k
        if not 1 <= top_k <= n_experts:
            # moe_experts=0 disables MoE entirely (dense MLP); top_k has no
            # analogous "off" value, so reject rather than silently clamp
            raise ValueError(
                f"moe_top_k={top_k} must be in [1, moe_experts={n_experts}]"
            )
        router = nn.Dense(
            n_experts, use_bias=False, dtype=jnp.float32, name="router"
        )
        if cfg.moe_dispatch not in ("dense", "alltoall"):
            raise ValueError(
                f"moe_dispatch={cfg.moe_dispatch!r} (dense | alltoall)"
            )
        if cfg.moe_dispatch == "alltoall" and cfg.moe_router == "expert_choice":
            raise NotImplementedError(
                "expert_choice routing needs the dense dispatch (each "
                "expert takes its global top-capacity tokens; a sharded "
                "token set cannot rank them locally)"
            )
        if (
            cfg.moe_dispatch == "alltoall"
            and cfg.moe_router == "topk"
            and ep_size > 1
        ):
            # ep == 1 falls through to the dense path: with one rank there
            # is no axis to exchange over, and the masks are already local
            return self._topk_alltoall(x, router, aux_scale, ep_size, train)
        logits = router(xf.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)  # [T, E]

        if cfg.moe_router == "expert_choice":
            return self._expert_choice(
                x, xf, probs, aux_scale, ep_size, local_experts, train
            )
        if cfg.moe_router != "topk":
            raise ValueError(
                f"moe_router={cfg.moe_router!r} (topk | expert_choice)"
            )
        gates, onehots = _topk_gates(probs, top_k)

        # Load-balance loss: E * sum_i fraction_i * router_prob_i, with
        # fraction_i the share of (token, choice) assignments to expert i
        # (Switch's f_i at top_k=1).  aux_scale (0.0 on pipeline bubble
        # ticks) zeroes both the value and, through the multiply, its
        # gradient into the router.
        assign_frac = sum(oh.mean(axis=0) for oh in onehots) / top_k
        balance = n_experts * jnp.sum(assign_frac * probs.mean(axis=0))
        if aux_scale is not None:
            balance = balance * jnp.asarray(aux_scale, jnp.float32)
        self.sow(
            "losses",
            "moe_balance",
            balance,
            reduce_fn=lambda a, b_: a + b_,
            init_fn=lambda: jnp.float32(0.0),
        )

        # --- capacity + dispatch masks (static shapes) ----------------------
        capacity = max(
            1, int(cfg.moe_capacity_factor * top_k * tokens / n_experts + 0.999)
        )
        dispatch, combine = _dispatch_masks(onehots, gates, n_experts, capacity)

        # --- expert parallelism: slice my experts, partial-combine, psum ----
        return self._apply_experts(
            x, xf, dispatch, combine, ep_size, local_experts, train
        )

    def _topk_alltoall(self, x, router, aux_scale, ep_size, train):
        """Sharded-token dispatch: each EP rank routes its ``T/ep`` token
        slice locally and exchanges expert payloads with one ``all_to_all``
        each way.

        Per-rank mask memory and dispatch-einsum cost drop from
        ``[T, E, C]`` to ``[T/ep, E, C/ep]`` (``ep^2`` smaller); expert
        FLOPs are unchanged.  Capacity becomes a per-(sender, expert)
        quota of ``C/ep`` slots — identical results to the dense path
        while nothing overflows (pinned by
        ``tests/test_moe.py::test_alltoall_matches_dense``), different
        drop CHOICES under pressure (GShard's formulation: a hot sender
        can drop while another sender's quota sits idle).

        Wire protocol (``E = ep * E_local``, ``C_s`` = per-sender quota):
        ``x_send [E, C_s, d]`` --a2a(split 0, concat 1)--> ``[E_local,
        ep*C_s, d]`` (slot blocks in sender-rank order) -> experts ->
        ``y_exp [E_local, ep*C_s, d]`` --a2a(split 1, concat 0)-->
        ``[E, C_s, d]`` back at the sender -> combine -> ``[T/ep, d]``
        --all_gather--> the replicated ``[T, d]`` the trunk expects."""
        cfg = self.config
        n_experts = cfg.moe_experts
        top_k = cfg.moe_top_k
        b, s, d = x.shape
        tokens = b * s
        if tokens % ep_size:
            raise ValueError(
                f"tokens={tokens} not divisible by EP axis size {ep_size} "
                "(alltoall dispatch shards the token set)"
            )
        t_local = tokens // ep_size
        rank = lax.axis_index(cfg.model_axis)
        xs = lax.dynamic_slice_in_dim(
            x.reshape(tokens, d), rank * t_local, t_local, axis=0
        )

        logits = router(xs.astype(jnp.float32))  # [T/ep, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gates, onehots = _topk_gates(probs, top_k)

        # balance loss on GLOBAL statistics: local means pmean'd over the
        # EP axis reproduce the dense path's full-batch fractions exactly
        assign_frac = sum(oh.mean(axis=0) for oh in onehots) / top_k
        assign_frac = lax.pmean(assign_frac, cfg.model_axis)
        mean_probs = lax.pmean(probs.mean(axis=0), cfg.model_axis)
        balance = n_experts * jnp.sum(assign_frac * mean_probs)
        if aux_scale is not None:
            balance = balance * jnp.asarray(aux_scale, jnp.float32)
        self.sow(
            "losses",
            "moe_balance",
            balance,
            reduce_fn=lambda a, b_: a + b_,
            init_fn=lambda: jnp.float32(0.0),
        )

        cap_send = max(
            1, int(cfg.moe_capacity_factor * top_k * t_local / n_experts + 0.999)
        )
        dispatch, combine = _dispatch_masks(onehots, gates, n_experts, cap_send)

        # dispatch my tokens into per-expert slots, exchange payloads
        x_send = jnp.einsum(
            "td,tec->ecd", xs.astype(jnp.float32), dispatch
        ).astype(cfg.dtype)  # [E, C_s, d]
        with jax.named_scope("moe_dispatch_a2a"):
            x_recv = lax.all_to_all(
                x_send, cfg.model_axis, split_axis=0, concat_axis=1, tiled=True
            )  # [E_local, ep*C_s, d]

        expert_stack = nn.vmap(
            ExpertFFN,
            in_axes=0,
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
        )
        y_exp = ModuleShard(
            functools.partial(expert_stack, cfg),
            axis_name=cfg.model_axis,
            name="experts",
        )(x_recv)  # [E_local, ep*C_s, d]

        with jax.named_scope("moe_combine_a2a"):
            y_back = lax.all_to_all(
                y_exp, cfg.model_axis, split_axis=1, concat_axis=0, tiled=True
            )  # [E, C_s, d] — my tokens' outputs, expert-major
        ys = jnp.einsum(
            "ecd,tec->td", y_back.astype(jnp.float32), combine
        )  # [T/ep, d]
        with jax.named_scope("moe_token_all_gather"):
            y = lax.all_gather(
                ys, cfg.model_axis, axis=0, tiled=True
            )  # [T, d] replicated over EP, as the trunk expects
        y = y.astype(cfg.dtype).reshape(b, s, d)
        if cfg.dropout_rate > 0.0:
            y = nn.Dropout(rate=cfg.dropout_rate, deterministic=not train)(y)
        return y

    def _expert_choice(
        self, x, xf, probs, aux_scale, ep_size, local_experts, train
    ):
        """Expert-choice routing: each expert takes its top-``capacity``
        tokens by router probability (Zhou et al., 2022).  Every expert is
        exactly full, so there is no balance loss to tune — a zero is still
        sown to keep the losses collection shape stable for the pipeline's
        bubble masking."""
        cfg = self.config
        n_experts = cfg.moe_experts
        tokens = xf.shape[0]
        capacity = max(1, int(cfg.moe_capacity_factor * tokens / n_experts + 0.999))
        if capacity > tokens:
            raise ValueError(
                f"expert capacity {capacity} > {tokens} tokens — lower "
                "moe_capacity_factor or use more tokens per batch"
            )
        # gates [E, C]: the chosen tokens' router probs; idx [E, C] token ids
        gates, idx = lax.top_k(probs.T, capacity)
        picked = jax.nn.one_hot(idx, tokens, dtype=jnp.float32)  # [E, C, T]
        dispatch = picked.transpose(2, 0, 1)  # [T, E, C]
        combine = (picked * gates[:, :, None]).transpose(2, 0, 1)

        del aux_scale  # EC has no balance loss to gate; the sown zero keeps
        # the losses collection shape stable for the pipeline bubble masking
        self.sow(
            "losses",
            "moe_balance",
            jnp.float32(0.0),
            reduce_fn=lambda a, b_: a + b_,
            init_fn=lambda: jnp.float32(0.0),
        )
        return self._apply_experts(
            x, xf, dispatch, combine, ep_size, local_experts, train
        )

    def _apply_experts(
        self, x, xf, dispatch, combine, ep_size, local_experts, train
    ):
        """Shared tail: slice my experts' masks, run the expert FFNs at
        1/ep cost, partial-combine, close with one psum."""
        cfg = self.config
        b, s, d = x.shape
        if ep_size > 1:
            rank = lax.axis_index(cfg.model_axis)
            dispatch = lax.dynamic_slice_in_dim(
                dispatch, rank * local_experts, local_experts, axis=1
            )
            combine = lax.dynamic_slice_in_dim(
                combine, rank * local_experts, local_experts, axis=1
            )

        x_exp = jnp.einsum("td,tec->ecd", xf.astype(jnp.float32), dispatch)
        x_exp = x_exp.astype(cfg.dtype)  # [E/ep, C, d]

        expert_stack = nn.vmap(
            ExpertFFN,
            in_axes=0,
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
        )
        if ep_size > 1:
            y_exp = ModuleShard(
                functools.partial(expert_stack, cfg),
                axis_name=cfg.model_axis,
                name="experts",
            )(x_exp)
        else:
            y_exp = expert_stack(cfg, name="experts")(x_exp)

        # --- back to tokens -------------------------------------------------
        # Partial combine over my experts; the psum sums the disjoint expert
        # contributions (TP row-parallel shape; pmean-over-model grad sync
        # keeps upstream gradients exact, see tests/test_moe.py).
        y = jnp.einsum("ecd,tec->td", y_exp.astype(jnp.float32), combine)
        if ep_size > 1:
            with jax.named_scope("moe_combine_psum"):
                y = lax.psum(y, cfg.model_axis)
        y = y.astype(cfg.dtype).reshape(b, s, d)
        if cfg.dropout_rate > 0.0:
            y = nn.Dropout(rate=cfg.dropout_rate, deterministic=not train)(y)
        return y


# --- dropless routing ---------------------------------------------------------

# collection the dropless layer sows its per-call row counts into
MOE_STATS = "moe_stats"
# below this many assignments one buffer serves; above it, a quarter-size
# buffer runs whenever the rows routed here fit it, the worst case otherwise
SMALL_BUFFER_MIN_ROWS = 2048
# an expert's matrices by ``ExpertsSpec.ffn``
FFN_MATRICES = {"swiglu": 3, "relu2": 2}


def moe_plan(spec, tokens: int, d_model: int, dtype, ep_size: int = 1) -> dict:
    """What a dropless layer does with ``tokens`` rows: experts held of how
    many, top-k, the width the experts live in (``latent``, or the model's),
    an expert's matrices and activation, the worst-case buffer (every token
    on ``min(top_k, held)`` held experts), the small buffer that runs whenever
    the rows routed here fit it, and what runs the grouped matmuls of each
    (``grouped``, ``small_grouped``): ``"streamed"`` where the buffer's rows
    an expert are few (``ops.grouped_ffn.grouped_ffn_plan`` has the rule; a
    decode step) with the kernel's window, slots, contraction blocks and VMEM
    limit beside it, ``"ragged_dot"`` where they are many (a prefill).
    :class:`RoutedExperts` sizes its buffers from this, :func:`_grouped_ffn`
    follows the same rule, and the serving engine logs it at build."""
    held = spec.held_range[1] // ep_size
    worst = tokens * min(spec.top_k, held)
    small = worst
    if worst >= SMALL_BUFFER_MIN_ROWS:
        small = -(-worst // 4 // 128) * 128
    plan = dict(
        experts=spec.n_experts, held=held, top_k=spec.top_k,
        shared=spec.shared, width=spec.width, score=spec.score,
        tokens=tokens, buffer_rows=worst, small_buffer_rows=small,
        latent=spec.latent, matrices=FFN_MATRICES[spec.ffn], ffn=spec.ffn,
    )
    buffers = {"": worst} if small == worst else {"": worst, "small_": small}
    for prefix, rows in buffers.items():
        streamed = grouped_ffn_plan(
            rows, held, spec.latent or d_model, spec.width, dtype,
            plan["matrices"],
        )
        plan[prefix + "grouped"] = "streamed" if streamed else "ragged_dot"
        plan.update({
            prefix + k: v for k, v in (streamed or {}).items()
            if k != "buffer_rows"
        })
    return plan


def expert_rows(variables):
    """``[layers, held + 1]`` rows routed to each held expert by one apply
    (last column: assignments to experts held elsewhere), from the
    ``moe_stats`` collection it returned; None for a model without a
    dropless layer."""
    leaves = jax.tree_util.tree_leaves(variables.get(MOE_STATS, {}))
    if not leaves:
        return None
    return jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in leaves])


# stacked ``[experts, in, out]`` weights, each expert drawn at its own fan-in
_EXPERT_INIT = nn.initializers.variance_scaling(
    1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
)


class _Stacked(nn.Module):
    """``[experts, in, out]`` weights as a ``kernel`` of their own: whatever
    reads a tree by name (an initialiser by fan-in) sees a matrix there."""

    shape: tuple

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", _EXPERT_INIT, self.shape)


class _HeldExperts(nn.Module):
    """The held experts' weights: ``(gate, up, down)``, each expert
    ``W_down(silu(W_gate x) * (W_up x))``, or with ``matrices=2`` ``(up,
    down)``, each ``W_down relu(W_up x)^2``; a module of its own so that
    :class:`~tpu_parallel.parallel.tp.ModuleShard` can give every rank of
    an expert axis its part."""

    n_local: int
    d_model: int
    width: int
    dtype: "jnp.dtype"
    matrices: int = 3

    @nn.compact
    def __call__(self):
        shape_in = (self.n_local, self.d_model, self.width)
        return tuple(
            _Stacked(shape, name=name)().astype(self.dtype)
            for name, shape in (
                ("gate", shape_in), ("up", shape_in),
                ("down", (self.n_local, self.width, self.d_model)),
            )[-self.matrices:]
        )


def _grouped_ffn(rows, weights, group_sizes):
    """Every expert's FFN over its own run of ``rows`` (sorted by expert):
    the streamed kernel where ``grouped_ffn_plan`` has a plan, else ragged_dot."""
    if len(weights) == 2:  # (up, down): W_down relu(W_up x)^2
        return _relu2_ffn(rows, weights, group_sizes)
    w_gate, w_up, w_down = weights
    n_local, d_model, width = w_gate.shape
    if grouped_ffn_plan(
        rows.shape[0], n_local, d_model, width, rows.dtype
    ) is not None:
        return grouped_ffn(rows, weights, group_sizes)
    gate = lax.ragged_dot(rows, w_gate, group_sizes)
    up = lax.ragged_dot(rows, w_up, group_sizes)
    return lax.ragged_dot(nn.silu(gate) * up, w_down, group_sizes)


class RoutedExperts(nn.Module):
    """Dropless top-k experts, shared experts beside them, told which experts
    it holds.

    ``y = sum_{e in top-k, e held} w_e E_e(x) + mean_i S_i(x)`` with ``w`` the
    router's scores (softmax or sigmoid over ALL ``n_experts``) renormalised
    over the token's true top-k.  What the experts held elsewhere would have
    added is left out: on one chip the layer runs without its exchange.  Under
    a bound model axis each rank holds an equal part of the held range and the
    routed sum closes with the ``psum`` the capacity layer has; router and
    shared experts are replicated and counted once.  A spec may state more
    (defaults: the above): a selection bias and a scale in the router, experts
    of two matrices (``relu2``), a ``latent`` the routed experts live in
    (``y = W_up sum w_e E_e(W_dn x) + sum_i S_i(x)``, one pair of projections
    a layer), shared experts of their own width that are summed.

    Assignments are sorted by expert with those of absent experts behind the
    held ones; the grouped matmuls do work for the rows routed here, while
    the buffer holds the worst case - every token on ``min(top_k, held)``
    held experts - so that no imbalance drops a token.  What runs them follows
    the shape (:func:`moe_plan`): few rows an expert (a decode step) go through
    the streamed kernel of ``ops/grouped_ffn.py``, many through ``ragged_dot``.
    """

    config: "TransformerConfig"  # noqa: F821
    spec: "ExpertsSpec"  # noqa: F821

    @nn.compact
    def __call__(
        self, x: jax.Array, valid: jax.Array | None = None
    ) -> jax.Array:
        """``valid`` ``[b, s]`` marks the rows that are tokens (a padded
        prefill's pad rows are routed and computed like any row, and left
        out of the row counts)."""
        cfg, es = self.config, self.spec
        first, count = es.held_range
        _check_spec(es)
        ep_size = axis_size_or_none(cfg.model_axis) or 1
        if count % ep_size:
            raise ValueError(
                f"{count} held experts not divisible by model axis {ep_size}"
            )
        n_local = count // ep_size
        if ep_size > 1:
            first = first + lax.axis_index(cfg.model_axis) * n_local
        b, s, d = x.shape
        tokens, k = b * s, es.top_k
        xf = x.reshape(tokens, d)

        with jax.named_scope("moe.router"):
            logits = nn.Dense(
                es.n_experts, use_bias=False, dtype=jnp.float32, name="router"
            )(xf.astype(jnp.float32))
            bias = None
            if es.select_bias:
                bias = self.param(
                    "select_bias", nn.initializers.zeros, (es.n_experts,)
                )
            weights, top_e = _route(es, logits, bias)
        xin, d_in = xf, es.latent or d
        if es.latent:  # the routed experts' input, projected down ONCE a layer
            with jax.named_scope("moe.latent_down"):
                xin = nn.Dense(
                    d_in, use_bias=False, dtype=cfg.dtype, name="latent_down"
                )(xf.astype(cfg.dtype))
        with jax.named_scope("moe.experts"):
            local = top_e.reshape(-1) - first  # [T * k]
            here = (local >= 0) & (local < n_local)
            key = jnp.where(here, local, n_local)  # absent behind the held
            order = jnp.argsort(key, stable=True)  # assignments by expert
            place = jnp.argsort(order)  # where each assignment's row went
            sizes = jnp.sum(
                key[:, None] == jnp.arange(n_local + 1)[None, :], axis=0,
                dtype=jnp.int32,
            )
            counted = sizes
            if valid is not None:
                real = jnp.repeat(valid.reshape(-1), k)
                counted = jnp.sum(
                    (key[:, None] == jnp.arange(n_local + 1)[None, :])
                    & real[:, None], axis=0, dtype=jnp.int32,
                )
            self.sow(
                MOE_STATS, "rows", counted,
                reduce_fn=lambda a, b_: a + b_,
                init_fn=lambda: jnp.zeros((n_local + 1,), jnp.int32),
            )
            group_sizes, routed = sizes[:n_local], tokens * k - sizes[n_local]
            expert_weights = ModuleShard(
                functools.partial(
                    _HeldExperts, n_local, d_in, es.width, cfg.dtype,
                    FFN_MATRICES[es.ffn],
                ),
                axis_name=cfg.model_axis, name="experts",
            )()
            mix = jnp.where(here, weights.reshape(-1), 0.0)

            def run(cap: int) -> jax.Array:
                """The routed sum through a buffer of ``cap`` rows (the held
                assignments are its first ``routed``).  Gathers only: no
                scatter-add, whose order of summation is not fixed."""
                rows = xin[order[:cap] // k].astype(cfg.dtype)
                out = _grouped_ffn(rows, expert_weights, group_sizes)
                # each assignment's output back at its token, weighed and
                # summed over the token's k: :func:`_combine`, at the end of
                # the file (in blocks of tokens where one float32 copy of
                # every assignment's row is over a GiB: a prompt of 8192 at
                # a width of 7680)
                return _combine(out, place, here, mix, cap, k)

            plan = moe_plan(es, tokens, d, cfg.dtype, ep_size)
            worst, small = plan["buffer_rows"], plan["small_buffer_rows"]
            if small == worst:
                y = run(worst)
            else:
                y = lax.cond(
                    routed <= small, lambda: run(small), lambda: run(worst)
                )
            if ep_size > 1:
                with jax.named_scope("moe_combine_psum"):
                    y = lax.psum(y, cfg.model_axis)

        if es.latent:  # the routed sum, projected up ONCE a layer
            with jax.named_scope("moe.latent_up"):
                y = nn.Dense(
                    d, use_bias=False, dtype=cfg.dtype, name="latent_up"
                )(y.astype(cfg.dtype)).astype(jnp.float32)
        if es.shared:
            with jax.named_scope("moe.shared"):
                y = y + _shared_experts(es, cfg.dtype, xf, d)
        return y.astype(cfg.dtype).reshape(b, s, d)


def _check_spec(es) -> None:
    """Refuse an :class:`ExpertsSpec` the layer does not build."""
    first, count = es.held_range
    if not (0 <= first and first + count <= es.n_experts and count > 0):
        raise ValueError(f"held={es.held} outside 0..{es.n_experts}")
    if not 1 <= es.top_k <= es.n_experts:
        raise ValueError(f"top_k={es.top_k} of {es.n_experts} experts")
    if es.ffn not in FFN_MATRICES:
        raise ValueError(f"ffn={es.ffn!r} ({' | '.join(FFN_MATRICES)})")


def _route(es, logits, bias):
    """``(weights [T, k], experts [T, k])`` from the router's float32
    ``logits`` over ALL experts: scores by softmax or sigmoid, the top-k
    chosen by score (plus ``bias`` where the spec has a selection bias: it
    moves the CHOICE and no weight), the chosen scores renormalised over the
    token's own top-k and times ``route_scale``."""
    k = es.top_k
    if es.score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif es.score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"score={es.score!r} (softmax | sigmoid)")
    if bias is None:
        top_s, top_e = lax.top_k(scores, k)  # [T, k]
    else:
        _, top_e = lax.top_k(scores + bias, k)
        top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    weights = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    if es.route_scale != 1.0:
        weights = weights * es.route_scale
    return weights, top_e


def _relu2_ffn(rows, weights, group_sizes):
    """:func:`_grouped_ffn` for two-matrix experts ``W_down relu(W_up x)^2``:
    the same rule, the streamed kernel's one-weight first call (float32 until
    after the square) or two ``lax.ragged_dot``."""
    w_up, w_down = weights
    n_local, d_in, width = w_up.shape
    if grouped_ffn_plan(
        rows.shape[0], n_local, d_in, width, rows.dtype, 2
    ) is not None:
        return grouped_ffn(rows, weights, group_sizes)
    up = lax.ragged_dot(
        rows, w_up, group_sizes, preferred_element_type=jnp.float32
    )
    mid = jnp.square(nn.relu(up)).astype(rows.dtype)
    return lax.ragged_dot(mid, w_down, group_sizes)


def _shared_experts(es, dtype, xf, d: int):
    """What the ``es.shared`` shared experts add, float32 ``[T, d]``: each of
    the routed experts' form (``es.ffn``) at its own width over the full-width
    input, their outputs averaged or (``shared_sum``) added.  Called inside
    :class:`RoutedExperts`' compact method."""
    width = es.shared_width or es.width
    shape_in = (es.shared, d, width)
    ws = [
        _Stacked(shape, name=name)().astype(dtype)
        for name, shape in (
            ("shared_gate", shape_in), ("shared_up", shape_in),
            ("shared_down", (es.shared, width, d)),
        )[-FFN_MATRICES[es.ffn]:]
    ]
    h = xf.astype(dtype)
    if es.ffn == "relu2":
        up = jnp.einsum(
            "td,edw->tew", h, ws[0], preferred_element_type=jnp.float32
        )
        mid = jnp.square(nn.relu(up)).astype(dtype)
    else:
        mid = nn.silu(jnp.einsum("td,edw->tew", h, ws[0])) * (
            jnp.einsum("td,edw->tew", h, ws[1])
        )
    out = jnp.einsum(
        "tew,ewd->td", mid, ws[-1], preferred_element_type=jnp.float32,
    )
    return out if es.shared_sum else out / es.shared


# what ONE temporary of the routed sum may take: a sixteenth of the memory of
# the smallest chip served (a TPU v5e's 16 GiB).  The sum's temporary is one
# float32 copy of every assignment's output row, [tokens * k, width]: up to
# this many bytes it is taken in one piece, above it in blocks of tokens of a
# quarter of it (a prompt of 8192 tokens, top-8, at a width of 7680 would be
# 2 GiB beside 9.8 GB of weights)
COMBINE_BYTES = (16 << 30) // 16


def _combine(out, place, here, mix, cap: int, k: int) -> jax.Array:
    """:class:`RoutedExperts`' routed sum ``[tokens, width]`` in float32 from
    the buffer's outputs ``out`` ``[cap, width]``: assignment ``i`` (token
    ``i // k``) reads row ``place[i]``, counts where it is ``here`` (held, and
    inside the buffer), is weighed by ``mix[i]`` and summed over its token's
    ``k``.  Gathers only.  Above ``COMBINE_BYTES`` the same sums are taken a
    block of tokens at a time (``lax.map``), so that the float32 copy is a
    block's and not the prompt's; a token's sum is the same either way."""
    width = out.shape[-1]
    tokens = place.shape[0] // k

    def some(place, here, mix):
        back = out[jnp.minimum(place, cap - 1)]  # [n * k, width]
        back = jnp.where(here[:, None], back.astype(jnp.float32), 0.0)
        n = place.shape[0] // k
        return jnp.sum(
            back.reshape(n, k, width) * mix.reshape(n, k, 1),
            axis=1,
        )

    total, blocks = tokens * k * width * 4, 1
    if total <= COMBINE_BYTES:
        return some(place, here, mix)
    while total > COMBINE_BYTES // 4 * blocks and tokens % (2 * blocks) == 0:
        blocks *= 2
    cut = lambda a: a.reshape(blocks, -1)
    parts = lax.map(lambda a: some(*a), (cut(place), cut(here), cut(mix)))
    return parts.reshape(tokens, width)
