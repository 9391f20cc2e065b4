"""The decode kernel over the stored stripes (``ops/decode_attention.py``).

Interpret mode, small: 2-4 slots of 256 stored positions at tile 128, heads of
128.  The kernel against ``decode_attention``'s ``jax.numpy`` body over the
three visibility rules; ragged, left-padded and holed position tables (the tile
range comes from the stored positions); rows that see nothing; tiles outside a
slot's range are not read; the rule that sends a shape to the kernel; and two
toy engines that serve through it.  That the kernel compiles for the chip at
the cells' widths is ``tests/test_chip_compile.py``'s.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_parallel.models import GPTLM, tiny_test
from tpu_parallel.models.gpt import tiny_block_diffusion
from tpu_parallel.models.layers import (
    LayerSpec,
    _score_scale,
    decode_attention,
    decode_attention_xla,
)
from tpu_parallel.ops import decode_attention as da
from tpu_parallel.serving import (
    Request,
    SchedulerConfig,
    ServingEngine,
    engine as engine_mod,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POSITIONS, TILE, DIM = 256, 128, 128
RULES = {"causal": (0, 0), "block": (0, 4), "window": (72, 0)}


def operands(dtype, new_len, group, kv_heads, lengths, seed=0, value=0.5):
    """Queries, stripes and the aligned position table of slots that hold
    ``lengths`` positions and write ``new_len`` more at this step."""
    slots, heads = len(lengths), group * kv_heads
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (slots, new_len, heads, DIM), dtype)
    k = jax.random.normal(keys[1], (slots, POSITIONS, kv_heads, DIM), dtype)
    v = value * jax.random.normal(keys[2], k.shape, dtype)
    lengths = np.asarray(lengths)
    cols = np.arange(POSITIONS)[None, :]
    k_pos = np.where(cols < (lengths + new_len)[:, None], cols, -1)
    pos = lengths[:, None] + np.arange(new_len)[None, :]
    return q, k, v, jnp.asarray(pos, jnp.int32), jnp.asarray(k_pos, jnp.int32)


def kernel(q, k, v, pos, k_pos, window=0, block_len=0, tile=TILE):
    lo, hi = da.visible_bounds(pos, window, block_len)
    return da.decode_stripes(
        q * _score_scale(None, DIM, q.dtype), k, v, lo, hi, k_pos, tile=tile
    )


def reference(q, k, v, pos, k_pos, window=0, block_len=0):
    return decode_attention_xla(
        q, k, v, pos, window=window, k_positions=k_pos, block_len=block_len
    )


def close(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


# -- (a) against the jax.numpy body ------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("new_len", [1, 4, 8])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_kernel_matches_the_jax_numpy_body(rule, new_len, dtype):
    window, block_len = RULES[rule]
    # a group of 16 on one K/V head (the expert cell's) for one row, groups
    # of 8 on 4 K/V heads (the block-diffusion cell's) for 4 and 8
    group, kv_heads = (16, 1) if new_len == 1 else (8, 4)
    lengths = [200, 36, 128 - new_len, 248]
    if block_len:
        lengths = [n // block_len * block_len for n in lengths]
    args = operands(dtype, new_len, group, kv_heads, lengths, seed=new_len)
    close(kernel(*args, window, block_len), reference(*args, window, block_len),
          dtype)


def test_two_kv_heads_share_each_word():
    """4 K/V heads are two strided rows of words, 2 are every row."""
    args = operands(jnp.bfloat16, 4, 16, 2, [100, 130])
    close(kernel(*args), reference(*args), jnp.bfloat16)


# -- (b), (c) the range comes from the stored positions ----------------------------


def test_ragged_lengths_empty_single_full_and_mid_tile():
    new_len = 4
    q, k, v, pos, k_pos = operands(jnp.float32, new_len, 8, 4, [0, 0, 252, 150])
    # slot 0 holds nothing and feeds pad rows; slot 1 holds a single position
    pos = pos.at[0].set(-1).at[1].set(jnp.array([0, -1, -1, -1]))
    k_pos = k_pos.at[0].set(-1).at[1, 1:].set(-1)
    first, count = da.tile_ranges(k_pos, *da.visible_bounds(pos), TILE)
    assert count.tolist() == [0, 1, 2, 2] and first.tolist()[1:] == [0, 0, 0]
    got, want = kernel(q, k, v, pos, k_pos), reference(q, k, v, pos, k_pos)
    live = np.asarray(pos) >= 0
    close(np.asarray(got)[live], np.asarray(want)[live], jnp.float32)
    assert not np.asarray(got)[~live].any()  # rows that see nothing: zeros
    # the single position's row returns that position's V, every head
    np.testing.assert_allclose(
        np.asarray(got[1, 0]).reshape(4, 8, DIM),
        np.broadcast_to(np.asarray(v[1, 0])[:, None], (4, 8, DIM)), atol=1e-6,
    )


@pytest.mark.parametrize("table", ["left_padded", "holes", "window_left_padded"])
def test_any_stored_position_table_gives_what_the_reference_gives(table):
    new_len, window = 4, 60 if table.startswith("window") else 0
    q, k, v, _, _ = operands(jnp.float32, new_len, 8, 4, [0, 0, 0, 0])
    held = np.array([70, 200, 254, 9])
    cols = np.arange(POSITIONS)[None, :]
    # left-padded: a slot's positions sit at the END of its stripe
    k_pos = np.where(
        cols >= POSITIONS - held[:, None], cols - (POSITIONS - held[:, None]), -1
    )
    if table == "holes":
        k_pos = np.where(np.random.RandomState(0).rand(*k_pos.shape) < 0.3, -1, k_pos)
        k_pos[:, -new_len:] = held[:, None] - new_len + np.arange(new_len)
    pos = held[:, None] - new_len + np.arange(new_len)[None, :]
    pos, k_pos = jnp.asarray(pos, jnp.int32), jnp.asarray(k_pos, jnp.int32)
    first, count = da.tile_ranges(k_pos, *da.visible_bounds(pos, window), TILE)
    # the aligned rule would start every slot at tile 0: here slot 0's
    # positions (and under the window every slot's) are in the last tile alone
    assert first.tolist()[0] == 1 and count.tolist()[0] == 1
    if window:
        assert first.tolist() == [1] * 4 and count.tolist() == [1] * 4
    close(kernel(q, k, v, pos, k_pos, window),
          reference(q, k, v, pos, k_pos, window), jnp.float32)


# -- (d) rows that see nothing ------------------------------------------------------


def test_pad_rows_and_parked_slots_are_finite_and_change_no_live_row():
    new_len = 4
    q, k, v, pos, k_pos = operands(jnp.bfloat16, new_len, 8, 4, [100, 0, 190, 64])
    pos = pos.at[1].set(-1).at[:, 3].set(-1)  # slot 1 parked, row 3 a pad
    k_pos = k_pos.at[1].set(-1)
    got = np.asarray(kernel(q, k, v, pos, k_pos, block_len=4), np.float32)
    assert np.isfinite(got).all()
    assert not got[1].any() and not got[:, 3].any()
    # other pad queries, another parked slot's content: the live rows' bits
    noise = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)
    q2 = q.at[1].set(noise[1]).at[:, 3].set(noise[:, 3])
    k2, v2 = k.at[1].set(-k[1]), v.at[1].set(7.0)
    again = np.asarray(kernel(q2, k2, v2, pos, k_pos, block_len=4), np.float32)
    np.testing.assert_array_equal(got, again)
    # and against a call without the parked slot (a live one in its place)
    keep = np.array([0, 2, 2, 3])
    without = np.asarray(
        kernel(q[keep], k[keep], v[keep], pos[keep], k_pos[keep], block_len=4),
        np.float32,
    )
    np.testing.assert_array_equal(got[keep], without)


# -- (e) the tiles outside a slot's range are not read ------------------------------


@pytest.mark.parametrize("window", [0, 60])
def test_tiles_outside_the_range_are_not_read(window):
    q, k, v, pos, k_pos = operands(jnp.bfloat16, 1, 16, 1, [40, 200, 127, 3])
    want = np.asarray(kernel(q, k, v, pos, k_pos, window), np.float32)
    first, count = da.tile_ranges(k_pos, *da.visible_bounds(pos, window), TILE)
    assert (first.tolist(), count.tolist()) == (
        ([0, 1, 0, 0], [1, 1, 1, 1]) if window else ([0, 0, 0, 0], [1, 2, 1, 1])
    )
    tile_of = np.arange(POSITIONS)[None, :] // TILE
    first, count = np.asarray(first)[:, None], np.asarray(count)[:, None]
    outside = (tile_of < first) | (tile_of >= first + count)
    poison = jnp.where(outside[:, :, None, None], jnp.nan, 1.0).astype(k.dtype)
    assert outside.any(axis=1).sum() >= 2
    got = np.asarray(kernel(q, k * poison, v * poison, pos, k_pos, window), np.float32)
    np.testing.assert_array_equal(got, want)


# -- (f) the rule -------------------------------------------------------------------


def _cell(config, workload):
    read = lambda *rel: json.load(open(os.path.join(REPO, "benchmarks", *rel)))
    model, eng = read("configs", config), read("workloads", workload)["engine"]
    return (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], eng["n_slots"], eng["slot_positions"])


@pytest.mark.parametrize("case,want", [
    ("blockgen_wide", (1024, 4, 64)), ("blockgen_narrow", (1024, 4, 32)),
    ("longshort", (1024, 8, 16)), ("toy", (128, 2, 8)),
    ("heads_of_64", None), ("bias", None), ("int8_scales", None),
    ("float32", None), ("one_tile", None), ("paged", None),
    ("too_many_rows", None), ("three_kv_heads", None),
])
def test_plan_rule(case, want):
    bf16, kwargs = jnp.bfloat16, {}
    if case.startswith("blockgen"):
        h, kv, d, n, s = _cell("sdar_30b_a3b_depth6.json",
                               "serve-sdar_30b_a3b_depth6-blockgen.json")
        shapes = (n, 8 if case.endswith("wide") else 4, h, d), (n, s, kv, d)
    elif case == "longshort":
        h, kv, d, n, s = _cell("command_a_plus_share8.json",
                               "serve-command_a_plus_share8-longshort.json")
        shapes = (n, 1, h, d), (n, s, kv, d)
    else:
        shapes = (2, 1, 8, 128), (2, 256, 1, 128)
    dtypes = (bf16, bf16)
    if case == "heads_of_64":  # the batch and hybrid cells' heads
        shapes = (8, 1, 25, 64), (8, 1024, 25, 64)
    elif case == "float32":
        dtypes = (jnp.float32, jnp.float32)
    elif case == "int8_scales":
        dtypes, kwargs = (bf16, jnp.int8), {"scales": True}
    elif case == "one_tile":
        shapes = (2, 1, 8, 128), (2, 128, 1, 128)
    elif case == "too_many_rows":
        shapes = (2, 64, 8, 128), (2, 256, 1, 128)
    elif case == "three_kv_heads":
        shapes = (2, 1, 24, 128), (2, 256, 3, 128)
    elif case in ("bias", "paged"):
        kwargs = {case: True}
    plan = da.decode_attention_plan(*shapes, *dtypes, **kwargs)
    if want is None:
        assert plan is None
        return
    assert (plan["tile"], plan["tiles"], plan["rows"]) == want
    assert plan["grid"] == [shapes[0][0], plan["tiles"]]
    assert 32 << 20 <= plan["vmem_limit_bytes"] <= 100 << 20


def test_decode_attention_takes_the_kernel_by_the_rule_alone():
    """bfloat16 heads of 128 go to the kernel (a pad row comes back zero, which
    the ``jax.numpy`` body never gives); float32, and heads of 64, do not."""
    q, k, v, pos, k_pos = operands(jnp.bfloat16, 4, 8, 4, [100, 140, 7, 30])
    pos = pos.at[0, 1].set(-1)
    out = decode_attention(q, k, v, pos, k_positions=k_pos)
    assert not np.asarray(out[0, 1], np.float32).any()
    close(out[1], reference(q, k, v, pos, k_pos)[1], jnp.bfloat16)
    # the default table is the aligned one
    aligned = decode_attention(q, k, v, pos)
    close(aligned[1], decode_attention_xla(q, k, v, pos)[1], jnp.bfloat16)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    assert np.asarray(decode_attention(*f32, pos, k_positions=k_pos)[0, 1]).any()
    q64, k64, v64 = (x[..., :64] for x in (q, k, v))
    assert np.asarray(
        decode_attention(q64, k64, v64, pos, k_positions=k_pos)[0, 1], np.float32
    ).any()


# -- (g) engines that serve through the kernel --------------------------------------


@pytest.fixture
def float32_takes_the_kernel(monkeypatch):
    """The rule judged as if float32 were bfloat16, so that a toy engine can
    be held to the ``jax.numpy`` path token for token (in bfloat16 the two
    round differently and near-ties fall both ways).  The engines' jitted
    programs are cached by model: dropped before and after."""
    real = da.decode_attention_plan

    def plan(q_shape, kv_shape, dtype, kv_dtype=None, **kwargs):
        return real(q_shape, kv_shape, jnp.bfloat16, jnp.bfloat16, **kwargs)

    def drop_programs():
        for name in dir(engine_mod):
            if hasattr(getattr(engine_mod, name), "cache_clear"):
                getattr(engine_mod, name).cache_clear()

    drop_programs()
    monkeypatch.setattr(da, "decode_attention_plan", plan)
    yield drop_programs
    drop_programs()


def _params(cfg):
    model = GPTLM(cfg)
    return jax.jit(lambda: model.init(
        {"params": jax.random.PRNGKey(3)}, jnp.zeros((1, 16), jnp.int32),
        train=False,
    )["params"])()


def _serve(cfg, params, prompts, new_tokens, **engine_kw):
    eng = ServingEngine(
        GPTLM(cfg), params, n_slots=2, decode_steps_per_tick=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2), **engine_kw,
    )
    outs = [
        eng.add_request(Request(prompt=p, max_new_tokens=new_tokens))
        for p in prompts
    ]
    eng.run()
    assert all(o.status == "finished" for o in outs)
    return eng, [o.tokens for o in outs]


def _prompt(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 250, n)]


@pytest.mark.parametrize("kind", ["block", "window"])
def test_toy_engine_serves_through_the_kernel(kind, float32_takes_the_kernel,
                                              monkeypatch):
    """A toy block-diffusion engine and a toy engine of window and full
    layers, heads of 128: the same tokens as the ``jax.numpy`` path (float32,
    so a near-tie is a gap under 1e-5 and none occurs here), ``attn_plan``
    says ``kernel``, and the walked share is what two slots that stay inside
    their tiles must read: slot A in the stripe's first tile of two, slot B
    across both (and, under the window of 64, in the second alone)."""
    if kind == "block":
        cfg = tiny_block_diffusion(
            head_dim=128, seq_len=256, n_layers=1, n_heads=4, n_kv_heads=2
        )
        want = (1 + 2) / 4
    else:
        local = LayerSpec("window", 64, "rope")
        cfg = tiny_test(
            layer_pattern=(local, LayerSpec("full", 0, "none")),
            head_dim=128, seq_len=256, n_layers=2, n_heads=4, n_kv_heads=1,
            scan_layers=False, positional="rope",
        )
        # a window layer (B walks one tile of two) and a full one
        want = ((1 + 1) + (1 + 2)) / 8
    params = _params(cfg)
    prompts, new_tokens, kw = [_prompt(20, 1), _prompt(200, 2)], 6, {}
    kw["prefill_buckets"] = (208,)
    eng, got = _serve(cfg, params, prompts, new_tokens, **kw)
    assert eng.attn_plan["decode"] == {
        "path": "kernel", "tile": 128, "tiles": 2,
        "rows": (8 if kind == "block" else 1) * cfg.n_heads // cfg.n_kv_heads,
    }
    assert eng.metrics.summary()["decode_tiles_walked_share"] == want
    # the same engine on the jax.numpy path
    monkeypatch.undo()
    float32_takes_the_kernel()
    ref_eng, want_tokens = _serve(cfg, params, prompts, new_tokens, **kw)
    assert ref_eng.attn_plan["decode"] == {"path": "xla"}
    assert ref_eng.metrics.summary()["decode_tiles_walked_share"] is None
    assert got == want_tokens


def test_attn_plan_by_the_real_rule():
    """bfloat16 heads of 128 take the kernel in every decode program shape;
    the toy GPT (float32, heads of 8) takes none."""
    cfg = tiny_block_diffusion(
        head_dim=128, seq_len=256, n_layers=1, n_heads=4, n_kv_heads=2,
        dtype=jnp.bfloat16,
    )
    eng = ServingEngine(GPTLM(cfg), _params(cfg), n_slots=2,
                        prefill_buckets=(32,))
    assert eng.attn_plan == {
        "decode": {"path": "kernel", "tile": 128, "tiles": 2, "rows": 16},
        "decode_narrow": {"path": "kernel", "tile": 128, "tiles": 2, "rows": 8},
    }
    toy = tiny_test()
    plain = ServingEngine(GPTLM(toy), _params(toy), n_slots=2)
    assert plain.attn_plan == {"decode": {"path": "xla"}}
    assert plain._walked_tiles([0]) is None
