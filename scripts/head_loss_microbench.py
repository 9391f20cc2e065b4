"""Time the train step's head and loss alone, on the chip: forward + backward
of ``lm_head`` + cross-entropy at one ``[rows, seq, width] -> vocab``.

The tool behind PERF.md section 6, PR 46.  A pass of the train step ends in
the head's matmul, the loss over its logits and, in the backward, the two
matmuls that take ``d logits`` (``dW`` summed into the pass accumulator,
``dX`` handed to the blocks).  This runs exactly that, as one jitted program
a form, under the profiler, and reads the DEVICE time off the trace (all
that ran there under the form's own trace).  Beside the milliseconds a pass
it prints their share of the least time the chip could take for the three
matmuls (``2 * rows * seq * width * vocab`` FLOPs each over
``benchmarks/lib/peaks.py``'s peak: 19.3 ms at the default shape on a v5e),
the program's temporaries by ``memory_analysis`` and its largest ops.

``--form`` (repeatable; default all):

- ``fused``: the program's own unit, ``core.losses.token_ce_and_argmax``;
- ``optax``: autodiff through ``optax.softmax_cross_entropy_with_integer_labels``
  of the upcast logits and a separate ``argmax``, what
  ``core.losses.token_cross_entropy`` was before PR 46;
- ``vocab_parallel``: the statistics as
  ``core.losses.vocab_parallel_cross_entropy`` writes them, at a model axis
  of ONE chip (the collectives left out): what a one-chip ``Trainer`` ran
  before PR 46, because its mesh binds the model axis at size 1.

The two older forms are reconstructed HERE, not imported from the program,
so that they can be read on the same chip call as the program's form
whatever the program has become.

It refuses to run off the TPU: a CPU time says nothing about a chip.

Usage:
    python scripts/head_loss_microbench.py
    python scripts/head_loss_microbench.py --form fused --form optax --rows 8
"""

import argparse
import collections
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import jax
import jax.numpy as jnp

REPEATS = 5
TOP_OPS = 6


def loss_fused(logits, targets):
    from tpu_parallel.core.losses import token_ce_and_argmax

    return token_ce_and_argmax(logits, targets)


def loss_optax(logits, targets):
    import optax

    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    )
    return ce, logits.argmax(-1)


def loss_vocab_parallel(logits, targets):
    lf = logits.astype(jnp.float32)
    row_max = jax.lax.stop_gradient(lf.max(axis=-1))
    lse = row_max + jnp.log(jnp.exp(lf - row_max[..., None]).sum(axis=-1))
    target_logit = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return lse - target_logit, lf.argmax(axis=-1).astype(jnp.int32)


FORMS = {
    "fused": loss_fused,
    "optax": loss_optax,
    "vocab_parallel": loss_vocab_parallel,
}


def make_pass(loss):
    """``(acc, kernel, h, targets, mask) -> (acc + dW, dX, loss_sum,
    correct)``: what one pass of the train step does with its head."""

    def head_and_loss(kernel, h, targets, mask):
        with jax.named_scope("lm_head"):
            logits = jnp.dot(h, kernel.astype(h.dtype))  # nn.Dense's cast
        ce, pred = loss(logits, targets)
        with jax.named_scope("cross_entropy"):
            return (ce * mask).sum(), ((pred == targets) * mask).sum()

    def one_pass(acc, kernel, h, targets, mask):
        (loss_sum, correct), (d_kernel, d_h) = jax.value_and_grad(
            head_and_loss, argnums=(0, 1), has_aux=True
        )(kernel, h, targets, mask)
        return acc + d_kernel, d_h, loss_sum, correct

    return jax.jit(one_pass, donate_argnums=0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--form", action="append", choices=sorted(FORMS))
    args = ap.parse_args()

    from lib import xplane
    from lib.peaks import peaks
    from tpu_parallel.runtime import require_tpu

    require_tpu()
    b, s, d, v = args.rows, args.seq, args.width, args.vocab
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(keys[0], (b, s, d), jnp.bfloat16)
    kernel = jax.random.normal(keys[1], (d, v), jnp.float32) * d ** -0.5
    targets = jax.random.randint(keys[2], (b, s), 0, v)
    mask = jnp.ones((b, s), jnp.float32)
    peak = peaks(jax.devices()[0].device_kind)
    least_ms = 3 * 2 * b * s * d * v / peak["flops"] * 1e3
    print(json.dumps({"shape": [b, s, d, v], "least_ms": round(least_ms, 3),
                      "logits_bf16_gb": round(b * s * v * 2 / 1e9, 3)}),
          flush=True)

    for form in args.form or sorted(FORMS):
        fn = make_pass(FORMS[form])
        acc = jnp.zeros((d, v), jnp.float32)
        try:
            mem = fn.lower(acc, kernel, h, targets, mask).compile().memory_analysis()
            acc, _, loss_sum, correct = fn(acc, kernel, h, targets, mask)
            jax.block_until_ready(acc)  # compile + warm up
        except Exception as exc:  # noqa: BLE001 — a form the chip refuses
            print(json.dumps({"form": form, "error": repr(exc)[:300]}), flush=True)
            continue
        logdir = tempfile.mkdtemp(prefix="head_loss_microbench_")
        jax.profiler.start_trace(logdir)
        for _ in range(REPEATS):
            acc, d_h, loss_sum, correct = fn(acc, kernel, h, targets, mask)
        jax.block_until_ready((acc, d_h))
        jax.profiler.stop_trace()
        trace = xplane.load(xplane.find_trace(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        ops = trace["devices"][min(trace["devices"])]["ops"]
        by_op = collections.Counter()
        for name, start, end in ops:
            by_op[name] += end - start
        busy = xplane.total(xplane.union([(a, z) for _, a, z in ops]))
        ms = busy / REPEATS * 1e3
        print(json.dumps({
            "form": form, "ms_per_pass": round(ms, 3),
            "least_pct": round(100 * least_ms / ms, 2) if ms else None,
            "temporaries_gb": round(mem.temp_size_in_bytes / 1e9, 3),
            "loss_mean": float(loss_sum) / (b * s), "correct": float(correct),
            "d_h_norm": float(jnp.linalg.norm(d_h.astype(jnp.float32))),
            "top_ops_ms": {
                name: round(sec / REPEATS * 1e3, 3)
                for name, sec in by_op.most_common(TOP_OPS)
            },
        }), flush=True)
        del acc, d_h


if __name__ == "__main__":
    main()
