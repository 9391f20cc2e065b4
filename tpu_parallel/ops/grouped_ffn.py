"""The routed experts' FFN where an expert sees few rows, as a Pallas TPU kernel.

At decode a held expert receives a row or two (32 slots, top-8 of 128, 16
held), so the layer's cost is reading the touched experts' three matrices
from HBM and the matrix unit has almost nothing to do.  This kernel is built
for that end of the shape: it streams each TOUCHED expert's matrices from HBM
exactly once, in large contiguous double-buffered blocks, while the rows and
the output stay resident in VMEM; an expert no row reached costs no DMA.
Where an expert can receive many rows the work is a matmul again and
``lax.ragged_dot`` runs it (``grouped_ffn_plan`` returns None there, and
``models.moe.moe_plan`` says which runs for a program shape).

One kernel, called twice a layer: ``h = silu(x @ W_gate[e]) * (x @ W_up[e])``
reads gate and up in ONE pass over the rows (two-matrix experts: ``h = relu(x
@ W_up[e])^2``, one weight), ``y = h @ W_down[e]`` is the same kernel with one
weight.  Operands stay in the dtype they come in (bf16 when served), every
product accumulates in fp32 and stays fp32 until after the ``silu`` product
or the square: nothing is rounded lower than ``ragged_dot`` rounds it.

Grid ``(slots, contraction blocks)``, both sequential.  A slot is one window
of rows under one expert's matrices; the windows of the experts that got a
row are compacted to the front of a schedule that goes in as scalar
prefetch, and the slots behind them repeat the last block fetched, which
Pallas does not fetch again, and compute nothing.  A weight block is
``[block_k, out]``: whole rows of the matrix, one contiguous stretch of HBM.
An expert's rows are a run of the sorted buffer that starts anywhere, so a
slot reads the ``window`` rows from the 16-row boundary under the run's
start, multiplies all of them, and stores only the run's own rows: a window
of matmul a block, under what the block's DMA takes.  A run longer than a
window takes further windows, each of which streams the expert's matrices
again: dropless for any imbalance, and as cheap as the bytes allow where
``grouped_ffn_plan``'s rule sends a shape here.

As with the flash kernels there is no fallback by backend: off the TPU the
same kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # importable on CPU too

from tpu_parallel.ops.flash_attention import _sds

# the height of the matrix unit, and the rows of one window: a weight tile is
# multiplied by a window in no more time than it takes to load, so the rows
# cost nothing beside the weight stream (PERF.md section 6, PR 31: at the
# expert cell's decode shape windows of 32, 64 and 128 rows take 1.897, 1.888
# and 1.888 ms a layer against 1.72 ms by bytes)
WINDOW_ROWS = 128
# rows of a packed bf16 tile: a window starts and ends on such a boundary
ROW_ALIGN = 16
# one weight block, from the same sweep: 1 MiB 2.017 ms, 2 MiB 1.905, 4 MiB
# 1.888, 8 MiB 1.893.  A step's DMA (5 us at 819 GB/s) has to dwarf the
# step's fixed cost; the first block of a call, which nothing overlaps,
# should stay under 1% of a layer's stream
WEIGHT_BLOCK_BYTES = 4 << 20
# what a call may hold in VMEM (a v5e core has 128 MiB): past it the rows no
# longer sit beside the weight blocks and ``lax.ragged_dot`` runs
VMEM_BUDGET_BYTES = 64 << 20
# the name the benchmark's reader finds the kernels by, as it finds
# ``lax.ragged_dot``'s own ops (``benchmarks/drivers/serve_moe.py``)
KERNEL_NAME = "ragged-dot-streamed"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block_k(contract: int, out: int, itemsize: int) -> Optional[int]:
    """Rows of one weight block ``[block_k, out]``: the whole contraction if
    that fits ``WEIGHT_BLOCK_BYTES``, else the largest multiple of 128 that
    divides it and fits; None when nothing does."""
    fits = WEIGHT_BLOCK_BYTES // (out * itemsize)
    if contract <= fits:
        return contract
    for block in range(fits // 128 * 128, 0, -128):
        if contract % block == 0:
            return block
    return None


def grouped_ffn_plan(
    rows: int, n_experts: int, d_model: int, width: int, dtype=jnp.bfloat16,
    matrices: int = 3,
) -> Optional[dict]:
    """What the streamed kernel does with a buffer of ``rows`` rows over
    ``n_experts`` experts of ``matrices`` matrices (3: gate, up, down; 2: up,
    down): the window, the grid's slots, the contraction block of each call,
    the VMEM limit it asks for.  None where ``lax.ragged_dot`` runs instead.

    The rule: ``rows / n_experts``, the rows an expert receives when the
    buffer is full (and so the layer's FLOPs a byte of bf16 weights), is at
    most ``WINDOW_ROWS``.  Up to there an expert is one window as a rule and
    the weight stream is the bound twice over: 128 FLOPs a byte is half the
    v5e's ridge of 240, and a window costs the matrix unit no more than the
    load of the tile it is multiplied by.  Past it the work is a matmul
    again.  It holds for any number of experts, and it sees only shapes.
    The call has to fit ``VMEM_BUDGET_BYTES``, a block to divide each width.

    The VMEM limit is what the larger call holds (the weight blocks and the
    resident rows and output double-buffered, the fp32 accumulators and as
    much again for the products on their way into them) and a quarter more,
    never under 32 MiB: the 16 MiB default does not hold two 4 MiB blocks of
    each of two matrices beside the rows.  ``d_model`` is the width the
    experts read and write (a latent's, where they live in one).
    """
    if rows > WINDOW_ROWS * n_experts:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    block_in = _block_k(d_model, width, itemsize)
    block_mid = _block_k(width, d_model, itemsize)
    if block_in is None or block_mid is None:
        return None
    padded, need = _round_up(rows, ROW_ALIGN), 0
    window = min(WINDOW_ROWS, padded)
    for n_w, block, contract, out in (
        (matrices - 1, block_in, d_model, width), (1, block_mid, width, d_model),
    ):
        need = max(need, itemsize * (
            2 * n_w * block * out + 2 * padded * contract + 2 * padded * out
        ) + 4 * 2 * n_w * window * out)
    if need > VMEM_BUDGET_BYTES:
        return None
    return {
        "window": window, "buffer_rows": padded,
        # a window a touched expert, and one more for every ``window`` rows
        # of a run and of the slack under its start
        "slots": n_experts + (padded + (ROW_ALIGN - 1) * n_experts) // window,
        "block_in": block_in, "block_mid": block_mid,
        "vmem_limit_bytes": min(max(need * 5 // 4, 32 << 20), 100 << 20),
    }


def _schedule(group_sizes: jax.Array, slots: int, window: int, buffer_rows: int):
    """``(ids, start, lo, hi)``, each ``[slots]``: slot ``i`` of the grid
    multiplies the ``window`` rows from ``start[i]`` by expert ``ids[i]``'s
    matrices and keeps rows ``[lo[i], hi[i])``, that expert's run.  An
    expert's windows follow each other from the 16-row boundary under its
    run's start (one window where the run is short, as at decode; a run of
    any length is covered, so no imbalance loses a row); experts without a
    row have none.  The slots behind the last window are empty (``lo ==
    hi``) and name its expert again, so that their weight blocks are the
    block already in VMEM."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    first = (ends - sizes) // ROW_ALIGN * ROW_ALIGN
    windows = jnp.where(sizes > 0, -(-(ends - first) // window), 0)
    last = jnp.cumsum(windows, dtype=jnp.int32)  # slots up to each expert
    slot = jnp.arange(slots, dtype=jnp.int32)
    live = slot < last[-1]
    at = jnp.minimum(slot, last[-1] - 1)
    ids = jnp.minimum(
        jnp.sum(last[None, :] <= at[:, None], axis=1, dtype=jnp.int32),
        sizes.shape[0] - 1,
    )
    nth = at - (last - windows)[ids]  # which of its expert's windows
    start = jnp.clip(first[ids] + nth * window, 0, buffer_rows - window)
    hi = jnp.where(live, ends[ids], 0)
    lo = jnp.where(live, hi - sizes[ids], 0)
    return ids, start, lo, hi


def _kernel(ids_ref, start_ref, lo_ref, hi_ref, x_ref, *refs, n_weights: int,
            window: int, block_k: int, n_k: int, square: bool = False):
    """One grid step ``(slot, contraction block)``: the slot's window of rows
    against one block of each of its expert's weights, into fp32
    accumulators; a slot's last block stores the expert's own rows."""
    del ids_ref
    w_refs, o_ref = refs[:n_weights], refs[n_weights]
    accs = refs[n_weights + 1:]
    slot, k = pl.program_id(0), pl.program_id(1)

    @pl.when((slot == 0) & (k == 0))
    def _():
        # rows past the groups come back zero
        o_ref[...] = jnp.zeros_like(o_ref)

    lo, hi = lo_ref[slot], hi_ref[slot]

    @pl.when(hi > lo)
    def _():
        start = start_ref[slot]
        rows = pl.ds(pl.multiple_of(start, ROW_ALIGN), window)
        cols = slice(None) if n_k == 1 else pl.ds(
            pl.multiple_of(k * block_k, block_k), block_k
        )
        x = x_ref[rows, cols]
        for w_ref, acc in zip(w_refs, accs):
            part = jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)

            @pl.when(k == 0)
            def _(acc=acc, part=part):
                acc[...] = part

            @pl.when(k > 0)
            def _(acc=acc, part=part):
                acc[...] += part

        @pl.when(k == n_k - 1)
        def _():
            y = accs[0][...] if not square else _relu2(accs[0][...])
            if n_weights == 2:
                y = jax.nn.silu(y) * accs[1][...]
            row = start + lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            mine = (row >= lo) & (row < hi)
            o_ref[rows, :] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[rows, :])


def _streamed(x, weights, schedule, *, window, block_k, vmem_limit_bytes,
              interpret, square=False):
    """``f(x[rows of e] @ w[e] for w in weights)`` for every expert: ``silu(a)
    * b`` of two weights, the product of one (``square``: its ``relu(a)^2``)."""
    rows, contract = x.shape
    out = weights[0].shape[-1]
    n_k = contract // block_k

    def w_map(slot, k, ids, start, lo, hi):
        # an empty slot stays on the block the last live step fetched
        return ids[slot], jnp.where(hi[slot] > lo[slot], k, n_k - 1), 0

    def resident(slot, k, *_):
        return 0, 0

    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        )
    }
    return pl.pallas_call(
        functools.partial(
            _kernel, n_weights=len(weights), window=window, block_k=block_k,
            n_k=n_k, square=square,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(schedule[0].shape[0], n_k),
            in_specs=[pl.BlockSpec((rows, contract), resident)] + [
                pl.BlockSpec((1, block_k, out), w_map) for _ in weights
            ],
            out_specs=pl.BlockSpec((rows, out), resident),
            scratch_shapes=[
                pltpu.VMEM((window, out), jnp.float32) for _ in weights
            ],
        ),
        out_shape=_sds((rows, out), x.dtype, x),
        name=KERNEL_NAME,
        interpret=interpret,
        **params,
    )(*schedule, x, *weights)


@functools.partial(jax.jit, static_argnames=(
    "window", "buffer_rows", "slots", "block_in", "block_mid",
    "vmem_limit_bytes", "interpret",
))
def _planned(rows, weights, group_sizes, *, window, buffer_rows, slots,
             block_in, block_mid, vmem_limit_bytes, interpret):
    """Both calls of one layer under a plan.  Jitted so that the layers of a
    model (and the calls of a test that runs eagerly) share one trace."""
    w_in, w_down, s = tuple(weights[:-1]), weights[-1], len(weights) == 2
    n_rows = rows.shape[0]
    if buffer_rows != n_rows:
        rows = jnp.pad(rows, ((0, buffer_rows - n_rows), (0, 0)))
    call = functools.partial(
        _streamed, schedule=_schedule(group_sizes, slots, window, buffer_rows),
        window=window, vmem_limit_bytes=vmem_limit_bytes, interpret=interpret,
    )
    # the scope holds the kernels alone: the roofline's reader sums what
    # matches ``ragged-dot`` and the gathers around them are not its work
    with jax.named_scope("ragged-dot"):
        mid = call(rows, w_in, block_k=block_in, square=s)
        out = call(mid, (w_down,), block_k=block_mid)
    return out[:n_rows]


def grouped_ffn(
    rows: jax.Array,
    weights,
    group_sizes: jax.Array,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Every expert's ``W_down(silu(W_gate x) * (W_up x))`` (three weights;
    two: ``W_down relu(W_up x)^2``) over its own run of ``rows`` (``[buffer,
    d_model]``, sorted by expert; ``group_sizes`` ``[experts]`` int32, of any
    sizes that fit the buffer), through the streamed kernel.  Rows past the
    groups come back zero.  ``ValueError`` where ``grouped_ffn_plan`` has none."""
    (n_rows, d_model), (n_experts, _, width) = rows.shape, weights[0].shape
    plan = grouped_ffn_plan(n_rows, n_experts, d_model, width, rows.dtype, len(weights))
    if plan is None:
        raise ValueError(
            f"no streamed plan for {n_rows} rows over {n_experts} experts of "
            f"{d_model} -> {width}: lax.ragged_dot runs this shape"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _planned(rows, weights, group_sizes, interpret=interpret, **plan)


def _relu2(y):
    """``relu(y)^2`` of a first call's float32 accumulator (down here: the
    kernel's bytes carry the line numbers above)."""
    return jnp.square(jnp.maximum(y, 0.0))
