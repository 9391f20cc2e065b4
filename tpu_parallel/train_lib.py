"""High-level trainer: config -> mesh -> model -> compiled step -> loop.

Replaces the reference's three copy-pasted script bottoms (config literals +
hardcoded loops at ``data_paral.py:255-277``, ``param_sharding.py:380-397``)
with one composable entrypoint that can express any DP x FSDP x TP x PP mesh
from a single ``ConfigDict``-style config.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from tpu_parallel.core import compute as compute_metrics
from tpu_parallel.core.state import TextBatch, TrainState, get_num_params
from tpu_parallel.data import lm_batch, seq2seq_batch
from tpu_parallel.models import GPTLM, GPTConfig, make_gpt_loss, make_mlm_loss
from tpu_parallel.models.gpt import loss_plan
from tpu_parallel.models import (
    EncoderDecoder,
    Seq2SeqConfig,
    bert_base,
    gpt2_125m,
    gpt2_350m,
    llama_1b,
    make_seq2seq_loss,
    t5_small,
    tiny_seq2seq,
    tiny_test,
)
from tpu_parallel.obs.registry import MetricRegistry
from tpu_parallel.obs.tracer import NULL_TRACER, Tracer
from tpu_parallel.parallel.spmd import TrainFunctions, build_train_functions
from tpu_parallel.runtime import MeshConfig, make_mesh
from tpu_parallel.utils.profiling import mfu

MODEL_REGISTRY: Dict[str, Callable[..., GPTConfig]] = {
    "gpt2_125m": gpt2_125m,
    "gpt2_350m": gpt2_350m,
    "llama_1b": llama_1b,
    "bert_base": bert_base,
    "tiny": tiny_test,
    # encoder-decoder family: Seq2SeqConfig factories dispatch the Trainer
    # to EncoderDecoder + make_seq2seq_loss + teacher-forced batches
    "t5_small": t5_small,
    "tiny_seq2seq": tiny_seq2seq,
}


@dataclasses.dataclass
class TrainerConfig:
    model: str = "gpt2_125m"
    model_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    global_batch_size: int = 32
    num_minibatches: int = 1
    steps: int = 20
    # "adamw" | "lion" | "sgd" — all elementwise, hence exact under any
    # parameter sharding.  (adafactor is deliberately not offered: optax's
    # FactoredState carries rank-changed/placeholder leaves that break the
    # nn.Partitioned spec-discovery pipeline; supporting it needs T5X-style
    # logical-axis metadata.)
    optimizer: str = "adamw"
    # training objective: "causal" (next-token LM) | "mlm" (masked-LM for
    # bidirectional/encoder configs — see models.make_mlm_loss)
    objective: str = "causal"
    mlm_mask_rate: float = 0.15
    # "cosine" (decay to 10% of peak) | "linear" (decay to 0) | "constant";
    # all include the linear warmup over warmup_steps
    lr_schedule: str = "cosine"
    learning_rate: float = 3e-4
    # >0 maintains an EMA (Polyak) shadow of the parameters in the train
    # state, updated every step and preferred by evaluation.  0 = off.
    ema_decay: float = 0.0
    warmup_steps: int = 10
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    seed: int = 0
    log_every: int = 10
    donate: bool = True

    @classmethod
    def from_config_dict(cls, cd) -> "TrainerConfig":
        """Build from an ``ml_collections.ConfigDict`` (CLI-facing format)."""
        d = dict(cd)
        mesh = d.pop("mesh", {})
        if not isinstance(mesh, MeshConfig):
            mesh = MeshConfig(**dict(mesh))
        overrides = dict(d.pop("model_overrides", {}))
        return cls(mesh=mesh, model_overrides=overrides, **d)


def make_lr_schedule(config: TrainerConfig) -> optax.Schedule:
    """``config.lr_schedule`` with a linear warmup over ``warmup_steps``."""
    decay_steps = max(config.steps, config.warmup_steps + 1)
    if config.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=config.learning_rate,
            warmup_steps=config.warmup_steps,
            decay_steps=decay_steps,
            end_value=config.learning_rate * 0.1,
        )
    if config.lr_schedule == "linear":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, config.learning_rate, config.warmup_steps),
                optax.linear_schedule(
                    config.learning_rate, 0.0, decay_steps - config.warmup_steps
                ),
            ],
            boundaries=[config.warmup_steps],
        )
    if config.lr_schedule == "constant":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, config.learning_rate, config.warmup_steps),
                optax.constant_schedule(config.learning_rate),
            ],
            boundaries=[config.warmup_steps],
        )
    raise ValueError(
        f"unknown lr_schedule {config.lr_schedule!r} "
        "(expected cosine | linear | constant)"
    )


def make_optimizer(config: TrainerConfig) -> optax.GradientTransformation:
    """``config.optimizer`` + warmup/cosine schedule + sharded grad clipping.

    The clip must be the sharding-aware variant: the stock optax one computes
    the norm from local shards only, giving each rank a different clip factor
    (see ``core.optim``).  adamw/lion/sgd are elementwise and therefore exact
    on partitioned parameters; adafactor's factored statistics are per-shard
    under TP/FSDP (see TrainerConfig.optimizer).
    """
    from tpu_parallel.core.optim import clip_by_global_norm_sharded

    schedule = make_lr_schedule(config)
    if config.optimizer == "adamw":
        tx = optax.adamw(schedule, weight_decay=config.weight_decay)
    elif config.optimizer == "lion":
        tx = optax.lion(schedule, weight_decay=config.weight_decay)
    elif config.optimizer == "sgd":
        # configs advertise weight_decay for every optimizer family; honor it
        tx = optax.chain(
            optax.add_decayed_weights(config.weight_decay),
            optax.sgd(schedule, momentum=0.9),
        )
    elif config.optimizer == "adafactor":
        raise ValueError(
            "adafactor is not supported: optax's FactoredState carries "
            "rank-changed placeholder leaves that break nn.Partitioned spec "
            "discovery (needs T5X-style logical-axis metadata); use "
            "adamw | lion | sgd"
        )
    else:
        raise ValueError(
            f"unknown optimizer {config.optimizer!r} "
            "(expected adamw | lion | sgd)"
        )
    return optax.chain(clip_by_global_norm_sharded(config.grad_clip), tx)


class Trainer:
    """Owns the mesh, the model, and the compiled train step.

    Telemetry (docs/11_observability.md): pass ``tracer`` (a
    :class:`~tpu_parallel.obs.tracer.Tracer`) to record per-step spans on
    the ``trainer`` track, split into ``data_wait`` (host-side batch
    fetch) and ``compute`` (dispatch + ``block_until_ready`` fence, so
    the span's width IS the device step — the fence costs pipelining,
    which is why it only runs when tracing is enabled).  ``registry`` (a
    shared :class:`~tpu_parallel.obs.registry.MetricRegistry`) receives
    ``train_mfu`` / ``train_tokens_per_sec`` / ``train_loss`` gauges at
    every log point — the same store the serving engine exports, so one
    Prometheus/JSONL snapshot covers both.
    """

    def __init__(self, config: TrainerConfig, mesh=None, *,
                 tracer: Optional[Tracer] = None,
                 registry: Optional[MetricRegistry] = None):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.flash_plan: Optional[dict] = None  # set below for attn_impl="flash"
        self.registry = registry if registry is not None else MetricRegistry()
        self.config = config
        self.mesh = mesh if mesh is not None else make_mesh(config.mesh)
        mesh_sizes = dict(self.mesh.shape)
        # None = "keep the model's default": shipped configs declare shape
        # knobs (vocab_size, n_layers, ...) as ml_collections placeholders
        # so they are CLI-addressable without pinning per-model values
        overrides = {
            k: v for k, v in dict(config.model_overrides).items() if v is not None
        }
        # the model's pipeline degree is dictated by the mesh
        overrides.setdefault("pipe_size", mesh_sizes.get("pipe", 1))
        self.model_config: GPTConfig = MODEL_REGISTRY[config.model](**overrides)
        self.is_seq2seq = isinstance(self.model_config, Seq2SeqConfig)
        if self.is_seq2seq:
            self._init_seq2seq(config)
            return
        if self.model_config.bidirectional and config.objective == "causal":
            # next-token CE on a bidirectional model: attention SEES the
            # target — loss collapses, numbers are meaningless.  (The
            # inverse, objective="mlm" on a causal model, is a legitimate
            # denoising objective: the masked position cannot see itself.)
            raise ValueError(
                "bidirectional models cannot train with objective='causal' "
                "(attention sees the next-token target); use objective='mlm'"
            )
        self.model = GPTLM(self.model_config)
        self.tx = make_optimizer(config)
        if config.objective == "mlm":
            make_loss = functools.partial(
                make_mlm_loss, mask_rate=config.mlm_mask_rate
            )
        elif config.objective == "causal":
            make_loss = make_gpt_loss
        else:
            raise ValueError(
                f"objective={config.objective!r} (causal | mlm)"
            )
        self._make_loss = make_loss
        self.loss_fn = make_loss(self.model_config)

        self.example_batch = lm_batch(
            jax.random.PRNGKey(0),
            config.global_batch_size,
            self.model_config.seq_len,
            self.model_config.vocab_size,
        )

        def model_init(rng, batch) -> TrainState:
            variables = self.model.init(
                {"params": rng},
                batch.tokens,
                positions=batch.positions,
                train=False,
            )
            return TrainState.create(
                apply_fn=self.model.apply,
                params=variables["params"],
                tx=self.tx,
                rng=rng,
            )

        self._finish_init(config, model_init)

    def _finish_init(self, config: TrainerConfig, model_init) -> None:
        """The family-independent tail of __init__: batch divisibility,
        seq-parallel wiring, and the compiled train/eval functions.  ONE
        copy — the GPT and seq2seq paths must not drift on the
        build_train_functions kwargs (grad axes, check_vma, EMA...)."""
        mesh_sizes = dict(self.mesh.shape)
        # Typo'd axis names in a config would otherwise degrade silently:
        # fold_rng_over_axis (deliberately) skips ANY unbound axis name, so
        # a 'modle' axis would quietly change dropout folding instead of
        # failing.  Validate where config meets mesh, once.
        for field in ("data_axis", "model_axis", "pipe_axis", "seq_axis"):
            ax = getattr(self.model_config, field, None)
            if ax is not None and ax not in self.mesh.axis_names:
                raise ValueError(
                    f"model config {field}={ax!r} is not a mesh axis "
                    f"(mesh has {tuple(self.mesh.axis_names)})"
                )
        if config.global_batch_size % mesh_sizes["data"] != 0:
            raise ValueError(
                f"global batch {config.global_batch_size} not divisible by "
                f"data axis {mesh_sizes['data']}"
            )
        # Sequence/context parallelism: a >1 ``seq`` axis shards the token
        # dimension of the batch (ring/Ulysses attention then communicates
        # K/V over it); gradients pick up a partial contribution per seq
        # rank, synced by pmean like the data axis.
        seq_parallel = mesh_sizes.get("seq", 1) > 1
        if seq_parallel and self.model_config.attn_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"mesh has seq={mesh_sizes['seq']} but attn_impl="
                f"{self.model_config.attn_impl!r} cannot shard the sequence "
                "axis — use attn_impl='ring' or 'ulysses'"
            )
        # exposed so data loaders can place batches in the step's layout
        # directly (no per-step reshard): train.py passes it to DataLoader
        self.batch_spec = P("data", "seq") if seq_parallel else P("data")
        grad_fn = None
        schedule = getattr(self.model_config, "pipe_schedule", "gpipe")
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pipe_schedule={schedule!r} (gpipe | 1f1b) — an unknown "
                "value would silently train with GPipe's m-proportional "
                "activation memory"
            )
        if schedule == "1f1b":
            from tpu_parallel.models.gpt import make_gpt_1f1b_grad_fn
            from tpu_parallel.models.seq2seq import Seq2SeqConfig

            if isinstance(self.model_config, Seq2SeqConfig):
                raise NotImplementedError(
                    "pipe_schedule='1f1b' for the encoder-decoder family "
                    "(two sequential pipelines need their own buffer walk)"
                )
            grad_fn = make_gpt_1f1b_grad_fn(self.model_config)
        self.funcs: TrainFunctions = build_train_functions(
            model_init,
            self.loss_fn,
            self.mesh,
            self.example_batch,
            batch_spec=self.batch_spec,
            grad_sync_axes=("data", "seq", "model") if seq_parallel else ("data", "model"),
            grad_psum_axes=("pipe",),
            num_minibatches=config.num_minibatches,
            donate=config.donate,
            eval_loss_fn=self._make_loss(self.model_config, train=False),
            ema_decay=config.ema_decay,
            # interpret-mode pallas (flash/ulysses off-TPU) trips a JAX
            # vma-inference limitation; the checker stays on everywhere else
            # (see build_train_functions docstring)
            check_vma=not (
                self.model_config.attn_impl in ("flash", "ulysses")
                and jax.default_backend() != "tpu"
            ),
            grad_fn=grad_fn,
        )
        self.state: Optional[TrainState] = None
        # what the flash kernels will do at this shape, said once: the tiles
        # of each pass, resident or streamed, one backward pass or two
        self.flash_plan = self._flash_plan()
        if self.flash_plan is not None:
            logging.getLogger(__name__).info(
                "flash_plan %s", json.dumps(self.flash_plan)
            )

        # what the head and the loss of a pass will do, said once: the form
        # (the one-pass unit, or vocab-parallel under a model axis), the
        # logits' shape and type, the residual bytes the loss keeps
        self.loss_plan = loss_plan(
            self.model_config,
            config.global_batch_size // mesh_sizes["data"] // config.num_minibatches,
            self.model_config.seq_len // mesh_sizes.get("seq", 1),
            mesh_sizes.get("model", 1),
        )
        logging.getLogger(__name__).info(
            "loss_plan %s", json.dumps(self.loss_plan)
        )

    def _flash_plan(self) -> Optional[dict]:
        cfg = self.model_config
        if cfg.attn_impl != "flash":
            return None
        from tpu_parallel.ops.flash_attention import flash_plan

        return flash_plan(
            cfg.seq_len, cfg.head_dim, cfg.n_heads // (cfg.n_kv_heads or cfg.n_heads),
            cfg.dtype, causal=not cfg.bidirectional, window=cfg.attn_window,
            block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
        )

    def _init_seq2seq(self, config: TrainerConfig) -> None:
        """Encoder-decoder family wiring: same Trainer surface, different
        model class / loss / batch shape.  Objectives other than the
        teacher-forced seq2seq CE are refused (MLM/causal are single-stack
        objectives)."""
        mesh_sizes = dict(self.mesh.shape)
        if config.objective not in ("causal", "seq2seq"):
            # "causal" is the TrainerConfig default — treat it as "the
            # family's native objective" rather than demanding every config
            # spell out objective="seq2seq"
            raise ValueError(
                f"objective={config.objective!r} is a single-stack "
                "objective; encoder-decoder models train teacher-forced"
            )
        self.model = EncoderDecoder(self.model_config)
        self.tx = make_optimizer(config)
        self._make_loss = make_seq2seq_loss
        self.loss_fn = make_seq2seq_loss(self.model_config)
        cfgm = self.model_config
        self.example_batch = seq2seq_batch(
            jax.random.PRNGKey(0),
            config.global_batch_size,
            cfgm.source_len,
            cfgm.seq_len,
            cfgm.vocab_size,
        )

        def model_init(rng, batch) -> TrainState:
            variables = self.model.init(
                {"params": rng}, batch.src_tokens, batch.tokens, train=False
            )
            return TrainState.create(
                apply_fn=self.model.apply,
                params=variables["params"],
                tx=self.tx,
                rng=rng,
            )

        self._finish_init(config, model_init)

    def init(self) -> TrainState:
        rng = jax.random.PRNGKey(self.config.seed)
        self.state = self.funcs.init_fn(rng, self.example_batch)
        return self.state

    def _publish_gauges(self, metrics: Dict[str, float]) -> None:
        """Mirror one log point's metrics into the registry as
        ``train_*`` gauges (``mfu``/``tokens_per_sec``/``loss``/...), so
        a registry export taken at any moment carries the trainer's
        latest state alongside the serving series."""
        for key, value in metrics.items():
            if isinstance(value, (int, float)):
                self.registry.gauge(f"train_{key}").set(value)

    def train(
        self,
        batch_iter=None,
        steps: Optional[int] = None,
        log_fn: Callable[[int, Dict[str, float]], None] = None,
    ) -> Dict[str, float]:
        """Run the training loop; returns the final metric means.

        ``batch_iter``: iterable of TextBatch; defaults to repeating synthetic
        data (the reference's smoke-test mode).
        """
        if self.state is None:
            self.init()
        steps = steps if steps is not None else self.config.steps
        state, metrics = self.state, None
        tokens_per_step = (
            self.config.global_batch_size * self.model_config.seq_len
        )
        last = {}
        t_start = t0 = time.perf_counter()
        timed_from = 0  # throughput covers steps AFTER this one
        tr = self.tracer
        if tr.enabled and self.flash_plan is not None:
            plan = self.flash_plan
            tr.instant(
                "flash_plan", track="trainer", fused_bwd=plan["fused_bwd"],
                **{f"{p}_{k}": v for p in ("fwd", "bwd")
                   for k, v in plan[p].items()},
            )
        if tr.enabled:
            tr.instant("loss_plan", track="trainer", **self.loss_plan)
        for step in range(1, steps + 1):
            if tr.enabled:
                with tr.span("data_wait", track="trainer", step=step):
                    batch = (
                        next(batch_iter)
                        if batch_iter is not None
                        else self.example_batch
                    )
            else:
                batch = (
                    next(batch_iter)
                    if batch_iter is not None
                    else self.example_batch
                )
            if step == 1 and self.is_seq2seq and not hasattr(batch, "src_tokens"):
                # the token-stream DataLoader yields TextBatch — refusing
                # here beats an AttributeError deep inside the jitted step
                raise ValueError(
                    "seq2seq models need Seq2SeqBatch batches (src_tokens + "
                    "teacher-forced tokens/targets); the token-stream "
                    f"DataLoader yields {type(batch).__name__} — provide a "
                    "paired-data iterator"
                )
            if tr.enabled:
                # the block_until_ready fence pins the span to the step's
                # real device time (and attributes host-side input waits
                # to data_wait above, not here) — the pipelining it costs
                # is the price of an honest trace, paid only when tracing
                with tr.span("compute", track="trainer", step=step):
                    state, metrics = self.funcs.step_fn(state, metrics, batch)
                    jax.block_until_ready(metrics)
            else:
                state, metrics = self.funcs.step_fn(state, metrics, batch)
            if step == 1:
                # steady-state timing: the first step carries compilation —
                # restart the clock so tokens_per_sec reflects the machine,
                # not the compiler (bench.py measures the same way)
                jax.block_until_ready(metrics)
                t0 = time.perf_counter()
                timed_from = 1
            if step % self.config.log_every == 0 or step == steps:
                jax.block_until_ready(metrics)
                dt = time.perf_counter() - t0
                last = compute_metrics(metrics)
                timed = step - timed_from
                if timed > 0:
                    last["tokens_per_sec"] = tokens_per_step * timed / dt
                else:
                    # a 1-step run has no steady-state window; report the
                    # compile-inclusive rate rather than dropping the key
                    last["tokens_per_sec"] = tokens_per_step * step / max(
                        time.perf_counter() - t_start, 1e-9
                    )
                # mfu's FLOPs model is the decoder-only transformer (an
                # encoder-decoder number from it would be fiction), and a
                # CPU run has no device utilization to report; any OTHER
                # device must be in the peak table (mfu raises)
                device = self.mesh.devices.flat[0]
                if not self.is_seq2seq and device.platform != "cpu":
                    last["mfu"] = mfu(
                        last["tokens_per_sec"] / self.mesh.size,
                        self.model_config,
                        device,
                    )
                self._publish_gauges(last)
                if log_fn is not None:
                    log_fn(step, last)
        jax.block_until_ready(state)
        self.state = state
        return last

    def fit(
        self,
        checkpoint_dir: str,
        *,
        data_loader=None,
        batch_iter=None,
        steps: Optional[int] = None,
        checkpoint_every: int = 100,
        max_failures: int = 3,
        max_to_keep: int = 3,
        log_fn: Callable[[int, Dict[str, float]], None] = None,
        eval_every: int = 0,
        eval_steps: int = 10,
        keep_best: bool = False,
    ) -> Dict[str, float]:
        """Fault-tolerant training: auto-resume, periodic async checkpoints.

        ``eval_every > 0`` runs :meth:`evaluate` on the held-out split every
        that many steps (requires a ``data_loader`` with ``eval_view()`` —
        a synthetic or unsplittable stream would make the eval meaningless)
        and logs ``eval_*`` metrics; with ``keep_best=True`` the
        lowest-eval-loss state is additionally saved under
        ``{checkpoint_dir}/best`` (one kept), with the best loss persisted
        beside it so a resumed run never overwrites a better snapshot with
        a worse one.

        The failure-detection / elastic-recovery layer the reference lacks
        (SURVEY.md §5): on start, restores the latest checkpoint in
        ``checkpoint_dir`` if one exists (so a preempted or crashed run
        relaunches into the same loop and continues); saves every
        ``checkpoint_every`` steps (async — compute continues while the
        previous save drains); on a step failure, rolls back to the last
        checkpoint and retries, up to ``max_failures`` times.

        Data feeding: pass ``data_loader`` (anything with
        ``batch_at(step)``, e.g. ``data.DataLoader``) for exact resume and
        rollback semantics — step ``s`` always trains on batch ``s``, across
        restarts and retries.  A plain ``batch_iter`` is also accepted but
        cannot be rewound: after a resume or rollback it continues from
        wherever it was, so data order is only approximate.
        """
        from tpu_parallel.checkpoint import Checkpointer, abstract_state_of

        import json as _json
        import os as _os

        steps = steps if steps is not None else self.config.steps
        if keep_best and not eval_every:
            raise ValueError("keep_best=True requires eval_every > 0")
        eval_iter_fn = None
        if eval_every:
            if data_loader is None or not hasattr(data_loader, "eval_view"):
                raise ValueError(
                    "eval_every > 0 needs a data_loader with eval_view() "
                    "(a held-out split); evaluating the training stream or "
                    "a synthetic batch would make the numbers meaningless"
                )
            eval_loader = data_loader.eval_view()
            eval_iter_fn = lambda: iter(eval_loader)
        ckpt = Checkpointer(checkpoint_dir, max_to_keep=max_to_keep)
        best_ckpt = None
        best_loss = float("inf")
        best_loss_path = _os.path.join(checkpoint_dir, "best", "best_loss.json")
        if keep_best:
            best_ckpt = Checkpointer(
                _os.path.join(checkpoint_dir, "best"), max_to_keep=1
            )
            if _os.path.exists(best_loss_path):
                # resumed run: never let a worse post-resume eval overwrite
                # the surviving best snapshot
                with open(best_loss_path) as fh:
                    best_loss = _json.load(fh)["loss"]
        target = None

        def restore_latest():
            nonlocal target
            if target is None:
                target = abstract_state_of(
                    self.funcs.init_fn,
                    jax.random.PRNGKey(self.config.seed),
                    self.example_batch,
                )
            # drain any in-flight async save first: the latest step may still
            # be writing when a failure triggers rollback
            ckpt.wait()
            self.state = ckpt.restore(target)
            if self.config.ema_decay and self.state.ema_params is None:
                # EMA turned on mid-run (the checkpoint predates it): seed
                # the shadow from the restored params, as init would
                self.state = self.state.replace(ema_params=self.state.params)

        try:
            if ckpt.latest_step is not None:
                restore_latest()
            elif self.state is None:
                self.init()

            failures = 0
            metrics = None
            last: Dict[str, float] = {}
            step = int(self.state.step)

            def rollback_or_reraise(exc):
                """Shared failure protocol for train and eval steps: log,
                count, roll back to the last checkpoint (re-raising when the
                budget is spent or nothing was ever saved).  Returns the
                step to continue from."""
                nonlocal failures, metrics
                from tpu_parallel.utils.logging_utils import print_exception

                print_exception(exc)
                failures += 1
                if failures > max_failures or ckpt.latest_step is None:
                    raise exc
                restore_latest()
                metrics = None
                return int(self.state.step)

            tr = self.tracer
            while step < steps:
                data_span = (
                    tr.span("data_wait", track="trainer", step=step + 1)
                    if tr.enabled
                    else None
                )
                if data_loader is not None:
                    batch = data_loader.batch_at(step)
                elif batch_iter is not None:
                    batch = next(batch_iter)
                else:
                    batch = self.example_batch
                if data_span is not None:
                    data_span.finish()
                # fit's rollback contract already fences every step
                # (block_until_ready below), so tracing adds no extra
                # synchronization here — the compute span is free
                step_span = (
                    tr.span("compute", track="trainer", step=step + 1)
                    if tr.enabled
                    else None
                )
                try:
                    new_state, metrics = self.funcs.step_fn(
                        self.state, metrics, batch
                    )
                    jax.block_until_ready(new_state)
                except Exception as exc:  # noqa: BLE001 — device/runtime failure
                    if step_span is not None:
                        # close at the failure, not at export time — an
                        # unfinished span would render as one giant
                        # rectangle over the rest of the trainer track
                        step_span.finish(failed=True)
                    step = rollback_or_reraise(exc)
                    continue
                if step_span is not None:
                    step_span.finish()
                self.state = new_state
                step += 1
                if step % checkpoint_every == 0 or step == steps:
                    ckpt.save(step, self.state, wait=False)
                if eval_every and (step % eval_every == 0 or step == steps):
                    try:
                        ev = self.evaluate(
                            batch_iter=eval_iter_fn(), steps=eval_steps
                        )
                    except Exception as exc:  # noqa: BLE001 — same contract as the step
                        step = rollback_or_reraise(exc)
                        continue
                    if log_fn is not None:
                        log_fn(step, {f"eval_{k}": v for k, v in ev.items()})
                    if best_ckpt is not None and ev["loss"] < best_loss:
                        best_loss = ev["loss"]
                        # wait=True: the loss marker below must never
                        # outlive its snapshot (a crash between an async
                        # save and the marker would block every later,
                        # worse-but-real best save after resume)
                        best_ckpt.save(step, self.state, wait=True)
                        with open(best_loss_path, "w") as fh:
                            _json.dump({"loss": best_loss, "step": step}, fh)
                if step % self.config.log_every == 0 or step == steps:
                    last = compute_metrics(metrics)
                    self._publish_gauges(last)
                    if log_fn is not None:
                        log_fn(step, last)
            ckpt.wait()
            if best_ckpt is not None:
                best_ckpt.wait()
            return last
        finally:
            ckpt.close()
            if best_ckpt is not None:
                best_ckpt.close()

    def evaluate(self, batch_iter=None, steps: int = 10) -> Dict[str, float]:
        """Mean metrics over ``steps`` eval batches (dropout off, no update)."""
        if self.state is None:
            self.init()
        metrics = None
        for _ in range(steps):
            batch = next(batch_iter) if batch_iter is not None else self.example_batch
            metrics = self.funcs.eval_fn(self.state, metrics, batch)
        return compute_metrics(metrics)

    def save_checkpoint(self, directory: str, step: int, *, wait: bool = True) -> None:
        from tpu_parallel.checkpoint import Checkpointer

        ckpt = Checkpointer(directory)
        try:
            ckpt.save(step, self.state, wait=wait)
        finally:
            ckpt.close()

    def restore_checkpoint(self, directory: str, step: Optional[int] = None):
        """Restore state sharded exactly as this trainer's mesh lays it out."""
        from tpu_parallel.checkpoint import Checkpointer, abstract_state_of

        target = abstract_state_of(
            self.funcs.init_fn, jax.random.PRNGKey(self.config.seed), self.example_batch
        )
        ckpt = Checkpointer(directory)
        try:
            self.state = ckpt.restore(target, step)
        finally:
            ckpt.close()
        return self.state

    @property
    def num_params(self) -> int:
        """Logical (unsharded) parameter count, for logging and MFU math.

        Computed from a mesh-free abstract init of the pipe_size=1 twin
        config (same logical weights; per-stage stacking removed), using the
        TP layers' unbound-axis fallback — no FLOPs, no devices touched.
        """
        import numpy as np

        # attn_impl="xla": ring/ulysses need their seq axis bound even for
        # shape inference (psum/axis_index at trace time); the attention
        # implementation never affects the parameter count
        cfg1 = dataclasses.replace(
            self.model_config, pipe_size=1, attn_impl="xla"
        )
        toks = jnp.zeros((1, 8), jnp.int32)
        if self.is_seq2seq:
            # a GPTLM built from the Seq2SeqConfig would count a decoder-only
            # twin — half the model (no encoder, no cross-attention)
            model1 = EncoderDecoder(cfg1)
            init1 = lambda r: model1.init({"params": r}, toks, toks, train=False)
        else:
            model1 = GPTLM(cfg1)
            init1 = lambda r: model1.init({"params": r}, toks, train=False)
        shapes = jax.eval_shape(init1, jax.random.PRNGKey(0))
        leaves = jax.tree_util.tree_leaves(shapes["params"])
        return int(sum(np.prod(l.shape) for l in leaves))
