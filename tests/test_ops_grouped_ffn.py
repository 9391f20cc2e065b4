"""The streamed grouped-FFN kernel (``ops/grouped_ffn.py``) in interpret mode
against ``lax.ragged_dot`` x 3, float32 against float32: the two differ in
the order of summation alone (the kernel sums contraction blocks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpu_parallel.models.layers import ExpertsSpec
from tpu_parallel.models.moe import moe_plan
from tpu_parallel.ops import grouped_ffn as gf
from tpu_parallel.ops.grouped_ffn import (
    WINDOW_ROWS,
    grouped_ffn,
    grouped_ffn_plan,
)

TOL = 2e-5


def ragged_dot_ffn(rows, weights, group_sizes):
    w_gate, w_up, w_down = weights
    gate = lax.ragged_dot(rows, w_gate, group_sizes)
    up = lax.ragged_dot(rows, w_up, group_sizes)
    return lax.ragged_dot(jax.nn.silu(gate) * up, w_down, group_sizes)


def operands(n_local, buffer_rows, d_model=32, width=48, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = jax.random.normal(keys[0], (buffer_rows, d_model), jnp.float32)
    weights = tuple(
        jax.random.normal(k, shape, jnp.float32) / shape[1] ** 0.5
        for k, shape in zip(keys[1:], (
            (n_local, d_model, width), (n_local, d_model, width),
            (n_local, width, d_model),
        ))
    )
    return rows, weights


# name -> (group sizes, buffer rows)
LAYOUTS = {
    "two_rows_an_expert": ([2] * 8, 32),
    "one_expert_has_every_row": ([0, 0, 24, 0], 24),
    "untouched_at_the_start": ([0, 0, 3, 1, 2, 2], 16),
    "untouched_in_the_middle": ([3, 0, 0, 1, 0, 2], 16),
    "untouched_at_the_end": ([2, 3, 1, 0, 0, 0], 16),
    "zero_rows_in_all": ([0] * 8, 32),
    "one_expert_held": ([5], 8),
    "eight_experts_held": ([1, 4, 0, 2, 0, 0, 3, 1], 64),
    "sixteen_experts_held": ([3, 2, 2, 3, 2, 0, 2, 2, 3, 2, 2, 0, 3, 2, 2, 2], 256),
    # runs longer than a window: one expert takes further windows
    "a_run_of_three_windows": ([5, 300, 0, 7, 40], 400),
    "every_row_on_the_last_expert": ([0, 0, 0, 512], 512),
    "a_buffer_shorter_than_a_packed_tile": ([1, 2], 4),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_streamed_kernel_matches_ragged_dot(name):
    sizes, buffer_rows = LAYOUTS[name]
    rows, weights = operands(len(sizes), buffer_rows)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(grouped_ffn)(rows, weights, group_sizes)
    want = ragged_dot_ffn(rows, weights, group_sizes)
    held = sum(sizes)
    assert got.shape == want.shape
    assert float(jnp.abs(got[:held] - want[:held]).max(initial=0.0)) < TOL
    # rows past the groups hold nothing
    assert not np.asarray(got[held:]).any()


def test_rows_past_the_groups_reach_no_output():
    """Whatever lies in the buffer behind the groups (here NaN) moves no row
    of a group and comes back zero."""
    sizes = [2, 0, 3, 1]
    rows, weights = operands(4, 16)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    want = grouped_ffn(rows, weights, group_sizes)
    got = grouped_ffn(rows.at[6:].set(jnp.nan), weights, group_sizes)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got[6:]).any()


def test_contraction_blocks_add_up(monkeypatch):
    """Widths that one weight block does not hold are walked in blocks of a
    multiple of 128 that divides them (the cell's 4096 in 512s; here 384 in
    128s and 256 in 128s), summed in fp32."""
    monkeypatch.setattr(gf, "WEIGHT_BLOCK_BYTES", 128 * 384 * 4)
    plan = grouped_ffn_plan(32, 6, 256, 384, jnp.float32)
    assert (plan["block_in"], plan["block_mid"]) == (128, 128)
    sizes = [2, 0, 4, 1, 0, 3]
    rows, weights = operands(6, 32, d_model=256, width=384)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_ffn(rows, weights, group_sizes)
    want = ragged_dot_ffn(rows, weights, group_sizes)
    assert float(jnp.abs(got[:10] - want[:10]).max()) < TOL


def test_a_width_no_block_divides_is_refused(monkeypatch):
    """REFUSED, not padded: a contraction that does not fit one weight block
    and that no multiple of 128 divides has no plan (the layer then runs
    ``lax.ragged_dot``: ``moe_plan`` says so) and the kernel called on it all
    the same raises ``ValueError``."""
    monkeypatch.setattr(gf, "WEIGHT_BLOCK_BYTES", 256 * 256 * 4)
    assert grouped_ffn_plan(16, 2, 320, 256, jnp.float32) is None
    assert grouped_ffn_plan(16, 2, 384, 256, jnp.float32)["block_in"] == 128
    rows, weights = operands(2, 16, d_model=320, width=256)
    with pytest.raises(ValueError, match="no streamed plan"):
        grouped_ffn(rows, weights, jnp.asarray([2, 1], jnp.int32))
    spec = ExpertsSpec(n_experts=8, top_k=2, width=256, score="sigmoid",
                       shared=0, held=(0, 2))
    assert moe_plan(spec, 4, 320, jnp.float32)["grouped"] == "ragged_dot"
    assert moe_plan(spec, 4, 384, jnp.float32)["grouped"] == "streamed"


def test_the_plan_of_the_expert_cells_shapes():
    """The rule (a buffer's rows an expert, at most one window's) on the
    shapes ``serve-command_a_plus_share8-longshort`` runs.  A decode step (32
    tokens, a buffer of 256 rows over 16 experts: 16 an expert) streams.  A
    prefill's worst-case buffer never does (8 x its tokens over 16 experts);
    its small buffer, a quarter of that, does under the SAME rule where it
    also fits the kernel's VMEM budget beside the weight blocks: the 512
    bucket (1024 rows, 64 an expert), not 1024 (2048 rows of 4096 bf16
    twice over, in and out) or longer.  One comparison on shapes: it holds
    for any number of held experts."""
    spec = ExpertsSpec(n_experts=128, top_k=8, width=4096, score="sigmoid",
                       shared=4, held=(0, 16))
    decode = moe_plan(spec, 32, 4096, jnp.bfloat16)
    assert decode["grouped"] == "streamed" and "small_grouped" not in decode
    assert decode["buffer_rows"] == decode["small_buffer_rows"] == 256
    assert (decode["window"], decode["slots"]) == (128, 19)
    # 4 MiB of bf16: [512, 4096]
    assert (decode["block_in"], decode["block_mid"]) == (512, 512)
    assert 32 << 20 <= decode["vmem_limit_bytes"] <= 100 << 20
    for bucket, small in ((512, "streamed"), (1024, "ragged_dot"),
                          (2048, "ragged_dot"), (8192, "ragged_dot")):
        prefill = moe_plan(spec, bucket, 4096, jnp.bfloat16)
        assert prefill["grouped"] == "ragged_dot" and "window" not in prefill
        assert prefill["small_grouped"] == small
        assert ("small_window" in prefill) == (small == "streamed")
    for held in (1, 8, 16):
        # at a width whose rows fit VMEM whatever their number
        spec = ExpertsSpec(n_experts=128, top_k=1, width=512, score="sigmoid",
                           shared=0, held=(0, held))
        at, past = (
            moe_plan(spec, t, 512, jnp.bfloat16)["grouped"]
            for t in (WINDOW_ROWS * held, WINDOW_ROWS * held + 1)
        )
        assert (at, past) == ("streamed", "ragged_dot")
    # an expert axis of 2: each rank plans for its half of the held experts
    split = moe_plan(ExpertsSpec(
        n_experts=128, top_k=8, width=4096, score="sigmoid", shared=4,
        held=(0, 16),
    ), 32, 4096, jnp.bfloat16, ep_size=2)
    assert (split["held"], split["grouped"], split["slots"]) == (8, "streamed", 10)
