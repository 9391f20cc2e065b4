"""What can be proven about the chip path without a chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  The flash kernels
of the main path are compiled for a ``v5e:2x2`` topology at real widths with
``interpret=False`` — interpret mode, which every other kernel test uses,
cannot see a tile the hardware refuses or a kernel that outgrows VMEM.  A
compile that passes is not a chip run: ``chip_smoke.py`` is that.

Also here: the rules this repo holds about the device — one compile cache
placeable from outside, peak FLOP/s only for known devices, and measurement
entry points that fail without a TPU instead of falling back to the CPU.
"""

import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from tpu_parallel.ops.flash_attention import (
    flash_attention,
    flash_chunk_attention,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e_chip():
    """A sharding on one chip of a described v5e:2x2, with the persistent
    compile cache off: an executable built for a described chip is written
    to the cache but cannot be read back without one, so later runs would
    warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a v5e topology here: {exc!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _bidirectional(q, k, v):
    from tpu_parallel.models.layers import bidirectional_flash_attention

    return bidirectional_flash_attention(q, k, v, block_q=512, block_k=512)


# name -> (fn(q, k, v, *extra), q shape, kv shape, extra int32 operand
# shapes[, custom calls]).  Shapes are [batch, seq, heads, head_dim], bf16.
# Every case compiles forward AND backward: two custom calls on the resident
# path (the forward and the one-pass backward), three where the backward is
# the streamed pair (fwd, dq, dkv) — about a second a case here, half a
# minute and more where a resident walk unrolls over a hundred tile bodies;
# the first pays libtpu's start-up.
KERNEL_CASES = {
    # the benchmark's train cell: tiles derived from the shape
    "gpt2_125m_derived": (
        functools.partial(flash_attention, interpret=False),
        (16, 1024, 12, 64), (16, 1024, 12, 64), (),
    ),
    # the longest resident row: 4096 x 64, VMEM limit raised from the blocks
    "seq4096_derived": (
        functools.partial(flash_attention, interpret=False),
        (2, 4096, 12, 64), (2, 4096, 12, 64), (),
    ),
    # GQA group 4 at head width 128: the group's walks share one kernel
    "gqa_16q_4kv_seq1024_derived": (
        functools.partial(flash_attention, interpret=False),
        (2, 1024, 16, 128), (2, 1024, 4, 128), (),
    ),
    "packed_window_derived": (
        lambda q, k, v, seg: flash_attention(
            q, k, v, segment_ids=seg, window=300, interpret=False
        ),
        (4, 1024, 12, 64), (4, 1024, 12, 64), ((4, 1024),),
    ),
    # an offset window chunk (ring): the empty-row guard is compiled in
    "offset_window_chunk": (
        functools.partial(
            flash_chunk_attention, causal=False, window=384, q_offset=512,
            interpret=False,
        ),
        (4, 512, 12, 64), (4, 512, 12, 64), (),
    ),
    "gpt2_125m_512x512": (
        functools.partial(
            flash_attention, block_q=512, block_k=512, interpret=False
        ),
        (16, 1024, 12, 64), (16, 1024, 12, 64), (),
    ),
    "gpt2_125m_128x128": (
        functools.partial(
            flash_attention, block_q=128, block_k=128, interpret=False
        ),
        (16, 1024, 12, 64), (16, 1024, 12, 64), (),
    ),
    # the streamed kernels, all three (``stream=True``: by itself the
    # forward of a row whose blocks fit VMEM is resident)
    "gqa_16q_4kv_seq8192_streamed": (
        functools.partial(
            flash_attention, block_q=512, block_k=512, stream=True,
            interpret=False,
        ),
        (1, 8192, 16, 128), (1, 8192, 4, 128), (), 3,
    ),
    # configs/gpt2_125m_long.py's attention as derived: the forward resident
    # at a tile of 512 (136 tile bodies), the backward the streamed pair
    "gpt2_125m_long_seq8192_derived": (
        functools.partial(flash_attention, interpret=False),
        (2, 8192, 12, 64), (2, 8192, 12, 64), (), 3,
    ),
    "packed_segment_ids": (
        lambda q, k, v, seg: flash_attention(
            q, k, v, segment_ids=seg, block_q=512, block_k=512,
            interpret=False,
        ),
        (4, 1024, 12, 64), (4, 1024, 12, 64), ((4, 1024),),
    ),
    "window_256": (
        functools.partial(
            flash_attention, block_q=512, block_k=512, window=256,
            interpret=False,
        ),
        (4, 1024, 12, 64), (4, 1024, 12, 64), (),
    ),
    # the ring partials: (out, lse), the lse cotangent included
    "noncausal_chunk_with_lse": (
        functools.partial(
            flash_chunk_attention, causal=False, block_q=512, block_k=512,
            interpret=False,
        ),
        (4, 512, 12, 64), (4, 512, 12, 64), (),
    ),
    "causal_chunk_with_lse": (
        functools.partial(
            flash_chunk_attention, causal=True, block_q=512, block_k=512,
            interpret=False,
        ),
        (4, 512, 12, 64), (4, 512, 12, 64), (),
    ),
    # layers.py's encoder path takes no ``interpret``: the test steers
    # ``jax.default_backend`` instead (below)
    "bidirectional_bert_base_seq512": (
        _bidirectional, (8, 512, 12, 64), (8, 512, 12, 64), (),
    ),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_flash_kernel_compiles_for_v5e(case, v5e_chip, monkeypatch):
    fn, q_shape, kv_shape, extra, *calls = KERNEL_CASES[case]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(q, k, v, *ints):
        outs = jax.tree_util.tree_leaves(fn(q, k, v, *ints))
        return sum(o.astype(jnp.float32).sum() for o in outs)

    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16, sharding=v5e_chip)
    ints = [
        jax.ShapeDtypeStruct(s, jnp.int32, sharding=v5e_chip) for s in extra
    ]
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv, *ints)
    assert lowered.as_text().count("tpu_custom_call") == (calls or [2])[0], (
        "expected the forward and backward kernels as custom calls (not "
        "interpreted)"
    )
    lowered.compile()  # raises what the chip's compiler would raise


@pytest.mark.parametrize("program", ["decode_tick", "prefill_8192"])
def test_expert_share_cell_compiles_for_v5e(program, v5e_chip, monkeypatch):
    """The serving cell of the parallel-block expert decoder at its published
    widths, built from the benchmark's own configuration and cell files: the
    fused decode tick over 32 slots of 8192 positions and the largest
    whole-prompt prefill (flash kernels at heads of 128, GQA 16:1, window
    4096 and none; grouped expert matmuls with both buffers, the decode
    tick's through the streamed kernel of ``ops/grouped_ffn.py``) fit one
    v5e."""
    import json

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from drivers.serve_moe import model_config

    from tpu_parallel.models import GPTLM
    from tpu_parallel.serving import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    read = lambda *rel: json.load(open(os.path.join(REPO, "benchmarks", *rel)))
    cell = read("workloads", "serve-command_a_plus_share8-longshort.json")
    cfg = model_config(
        read("configs", "command_a_plus_share8.json"), cell["engine"]
    )
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 16, 1, 128
    )
    model, n = GPTLM(cfg), cell["engine"]["n_slots"]
    on_chip = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=v5e_chip
    )
    params = jax.tree.map(
        lambda x: on_chip(x, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        ))["params"],
    )
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_234_170_368
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)
    floats = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=v5e_chip
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip)
    if program == "prefill_8192":
        width = cfg.seq_len
        lowered = jax.jit(
            lambda p, toks, pos, last, rng: engine._prefill_core(
                model, p, toks, pos, last, rng
            )
        ).lower(params, ints(1, width), ints(1, width), ints(1), key)
        # two attention kernels a layer kind... at least one call a layer
        assert lowered.as_text().count("tpu_custom_call") >= cfg.n_layers
    else:
        pool = jax.tree.map(on_chip, jax.eval_shape(
            lambda p: engine._prefill_core(
                model, p, jnp.zeros((n, 16), jnp.int32),
                jnp.zeros((n, 16), jnp.int32), jnp.zeros((n,), jnp.int32), None,
            )[1], params,
        ))
        live = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=v5e_chip)
        state = (ints(n), ints(n), ints(n), live, ints(n))
        knobs = (ints(n), floats(n), ints(n), floats(n))
        lowered = engine._fused_engine_fn(model, 8).lower(
            params, state, knobs, pool, key
        )
    compiled = lowered.compile()
    if program == "decode_tick":
        # a decode step's grouped matmuls are the streamed kernel, two calls
        # a layer, by the name and under the scope that the benchmark's
        # roofline reader finds them by; no ``lax.ragged_dot`` is left
        text = compiled.as_text()
        kernels = re.findall(r"%ragged-dot-streamed[.\d]* = .*", text)
        assert len(kernels) == 2 * cfg.n_layers
        assert all("moe.experts/jit(_planned)/ragged-dot/" in k for k in kernels)
        assert "ragged-dot-none" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 8.4e9 < held < 15.75e9, memory


@pytest.mark.parametrize("program", ["decode_tick", "prefill_768"])
def test_hybrid_cell_compiles_for_v5e(program, v5e_chip, monkeypatch):
    """The serving cell of the hybrid decoder at its published widths, whole,
    built from the benchmark's own configuration and cell files: the fused
    decode tick over 64 slots (36 float32 recurrent states of 64 x 64 x 128 a
    slot beside 4 K/V stripes of 1280 positions) and the largest
    whole-prompt prefill (the chunked scan at chunks of 256; the flash
    kernels at heads of 64, GQA 4:1, a stated score scale) fit one v5e."""
    import json

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from drivers.serve_hybrid import model_config

    from tpu_parallel.models import GPTLM
    from tpu_parallel.serving import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    read = lambda *rel: json.load(open(os.path.join(REPO, "benchmarks", *rel)))
    cell = read("workloads", "serve-granite_4_0_h_micro-shortchat.json")
    cfg = model_config(read("configs", "granite_4_0_h_micro.json"), cell["engine"])
    assert (cfg.d_model, cfg.n_layers, cfg.recurrent_layers, cfg.vocab_size) == (
        2048, 40, 36, 100352
    )
    assert (cfg.attn_scale, cfg.embed_scale, cfg.residual_scale,
            cfg.logit_scale) == (0.015625, 12.0, 0.22, 0.125)
    model, n = GPTLM(cfg), cell["engine"]["n_slots"]
    on_chip = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=v5e_chip
    )
    params = jax.tree.map(
        lambda x: on_chip(x, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        ))["params"],
    )
    assert sum(x.size for x in jax.tree.leaves(params)) == 3_191_396_096
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)
    floats = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=v5e_chip
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip)
    if program == "prefill_768":
        width = max(b for b in cell["engine"]["prefill_buckets"])
        lowered = jax.jit(
            lambda p, toks, pos, last, rng: engine._prefill_core(
                model, p, toks, pos, last, rng
            )
        ).lower(params, ints(1, width), ints(1, width), ints(1), key)
        # the four attention layers attend through the flash kernel
        assert lowered.as_text().count("tpu_custom_call") == 4
        low, high = 6.38e9, 8e9
    else:
        pool = jax.tree.map(on_chip, jax.eval_shape(
            lambda p: engine._prefill_core(
                model, p, jnp.zeros((n, 16), jnp.int32),
                jnp.zeros((n, 16), jnp.int32), jnp.zeros((n,), jnp.int32), None,
            )[1], params,
        ))
        state_bytes = sum(
            x.size * x.dtype.itemsize
            for p, x in jax.tree_util.tree_flatten_with_path(pool)[0]
            if p[-1].key == "ssm_state"
        )
        assert state_bytes == n * 36 * 64 * 64 * 128 * 4  # 4.83 GB, float32
        live = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=v5e_chip)
        state = (ints(n), ints(n), ints(n), live, ints(n))
        knobs = (ints(n), floats(n), ints(n), floats(n))
        lowered = engine._fused_engine_fn(model, 8).lower(
            params, state, knobs, pool, key
        )
        low, high = 11.9e9, 15.75e9  # 6.38 GB of weights + 5.56 GB of pool
    memory = lowered.compile().memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert low < held < high, memory


@pytest.mark.parametrize("program", ["block_tick", "prefill_3072"])
def test_blockgen_cell_compiles_for_v5e(program, v5e_chip, monkeypatch):
    """The serving cell of the block-diffusion expert decoder at its
    published widths, one pipeline stage whole, built from the benchmark's
    own configuration and cell files: the tick of 8 block forwards over 64
    slots (a wide step and a narrow one by turns: 512 rows, a slot's block and
    the completed one before it, 32 an expert, then 256 rows, 16 an expert;
    the streamed kernel in both) and the
    largest whole-prompt prefill (the flash kernels under the block rule at
    heads of 128, GQA 8:1; no head, no logits) fit one v5e."""
    import json

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from drivers.serve_blockgen import model_config

    from tpu_parallel.models import GPTLM
    from tpu_parallel.serving import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    read = lambda *rel: json.load(open(os.path.join(REPO, "benchmarks", *rel)))
    cell = read("workloads", "serve-sdar_30b_a3b_depth6-blockgen.json")
    cfg = model_config(read("configs", "sdar_30b_a3b_depth6.json"), cell["engine"])
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (2048, 6, 32, 4, 128, 151936)
    assert (cfg.block_len, cfg.mask_token_id, cfg.qk_norm,
            cfg.rope_pairing) == (4, 151669, True, "half")
    model, n = GPTLM(cfg), cell["engine"]["n_slots"]
    on_chip = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=v5e_chip
    )
    params = jax.tree.map(
        lambda x: on_chip(x, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        ))["params"],
    )
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_361_055_744
    shaped = lambda dtype, *shape: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )
    ints = lambda *shape: shaped(jnp.int32, *shape)
    prefill, tick = engine._block_engine_fns(model, 8)
    if program == "prefill_3072":
        width = max(cell["engine"]["prefill_buckets"])
        lowered = prefill.lower(params, ints(1, width), ints(1, width))
        # one flash kernel a layer; no head: no logits come out
        assert lowered.as_text().count("tpu_custom_call") >= cfg.n_layers
        assert all(151936 not in x.shape for x in jax.tree.leaves(lowered.out_info))
        low, high = 6.5e9, 12e9  # the unread head is no argument
    else:
        pool = jax.tree.map(on_chip, jax.eval_shape(
            lambda p: engine._block_prefill_core(
                model, p, jnp.zeros((n, 16), jnp.int32),
                jnp.zeros((n, 16), jnp.int32),
            )[0], params,
        ))
        kv = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool)
                 if x.dtype == jnp.bfloat16)
        assert kv == n * 4096 * 12288  # 3.22 GB
        flags, size = shaped(jnp.bool_, n), cfg.block_len
        state = (ints(n, size), shaped(jnp.bool_, n, size), ints(n, size),
                 ints(n), ints(n), ints(n), flags, ints(n), ints(n, size), flags)
        knobs = (ints(n), shaped(jnp.float32, n), ints(n),
                 shaped(jnp.float32, n))
        lowered = tick.lower(
            params, state, knobs, pool, shaped(jnp.uint32, 2)
        )
        low, high = 11.9e9, 15.75e9  # 8.72 GB of weights + 3.22 GB of pool
    compiled = lowered.compile()
    if program == "block_tick":
        text = compiled.as_text()
        kernels = re.findall(r"%ragged-dot-streamed[.\d]* = .*", text)
        # two calls a layer in each of the layer's two buffers (2048 rows
        # reach the size at which a quarter-size buffer is compiled beside
        # the worst case; with every expert held it never runs), in each of
        # the scan body's two forwards (the wide step and the narrow one)
        assert len(kernels) == 2 * 4 * cfg.n_layers
        assert "ragged-dot-none" not in text
        # the choice among a block's positions is counted, not sorted; what
        # sorts are left are the experts' (by expert id), none over the
        # vocabulary
        assert not re.search(r"sort\([^)]*151936", text)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print(program, memory.argument_size_in_bytes / 1e9,
          memory.temp_size_in_bytes / 1e9)
    assert low < held < high, memory


@pytest.mark.parametrize("program", ["decode_tick", "prefill_2048"])
def test_latent_moe_cell_compiles_for_v5e(program, v5e_chip, monkeypatch):
    """The serving cell of the one-sublayer hybrid decoder at its published
    widths, one chip's share of one stage, built from the benchmark's own
    configuration and cell files: the fused decode tick over 128 slots (5
    float32 recurrent states of 128 x 64 x 128 a slot, ONE K/V stripe of 4096
    positions, nothing for the 5 expert layers; 128 two-matrix experts a
    layer in a latent of 1024 through the streamed kernel's one-weight
    ``relu^2`` call, the decode attention through the kernel over the stored
    stripes) and the largest whole-prompt prefill fit one v5e."""
    import json

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from drivers.serve_latent_moe import model_config

    from tpu_parallel.models import GPTLM
    from tpu_parallel.serving import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    read = lambda *rel: json.load(open(os.path.join(REPO, "benchmarks", *rel)))
    cell = read("workloads", "serve-nemotron_3_super_120b_share4-reasoning.json")
    cfg = model_config(
        read("configs", "nemotron_3_super_120b_share4.json"), cell["engine"]
    )
    model, n = GPTLM(cfg), cell["engine"]["n_slots"]
    on_chip = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=v5e_chip
    )
    params = jax.tree.map(
        lambda x: on_chip(x, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        ))["params"],
    )
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_648_163_712
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)
    floats = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=v5e_chip
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip)
    if program == "prefill_2048":
        width = max(cell["engine"]["prefill_buckets"])
        lowered = jax.jit(
            lambda p, toks, pos, last, rng: engine._prefill_core(
                model, p, toks, pos, last, rng
            )
        ).lower(params, ints(1, width), ints(1, width), ints(1), key)
        # the one attention layer attends through the flash kernel; 45056
        # rows of 22 assignments a token go through lax.ragged_dot
        assert lowered.as_text().count("tpu_custom_call") == 1
        low, high = 9.3e9, 11.5e9
    else:
        pool = jax.tree.map(on_chip, jax.eval_shape(
            lambda p: engine._prefill_core(
                model, p, jnp.zeros((n, 16), jnp.int32),
                jnp.zeros((n, 16), jnp.int32), jnp.zeros((n,), jnp.int32), None,
            )[1], params,
        ))
        by_name = {}
        for p, x in jax.tree_util.tree_flatten_with_path(pool)[0]:
            by_name.setdefault(p[-1].key, []).append(x.size * x.dtype.itemsize)
        assert len(by_name["ssm_state"]) == len(by_name["conv_state"]) == 5
        assert len(by_name["cached_key"]) == 1
        assert sum(by_name["ssm_state"]) == n * 5 * 128 * 64 * 128 * 4  # 2.68 GB
        assert sum(by_name["cached_key"]) * 2 == n * 4096 * 1024  # 0.54 GB
        live = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=v5e_chip)
        state = (ints(n), ints(n), ints(n), live, ints(n))
        knobs = (ints(n), floats(n), ints(n), floats(n))
        lowered = engine._fused_engine_fn(model, 8).lower(
            params, state, knobs, pool, key
        )
        low, high = 12.5e9, 15.75e9  # 9.30 GB of weights + 3.26 GB of pool
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert low < held < high, memory
    if program == "decode_tick":
        text = compiled.as_text()
        # two calls a layer in each of the layer's two buffers (2816 rows
        # reach the size at which a quarter-size buffer is compiled beside
        # the worst case), 5 expert layers; no lax.ragged_dot in the tick
        assert len(re.findall(r"%ragged-dot-streamed[.\d]* = ", text)) == 2 * 2 * 5
        assert "ragged-dot-none" not in text
        assert len(re.findall(r"%attn\.decode_stripes[.\d]* = ", text)) == 1


@pytest.mark.parametrize("seq", [4096, 6144, 8192])
def test_latent_flash_forward_compiles_for_v5e(seq, v5e_chip, monkeypatch):
    """The forward flash kernels at latent attention's real widths: 128 heads
    that score at 192 (no multiple of the 128 lanes: a block's full last axis)
    and sum values at 128, one prompt of 4096, 6144 and 8192, as the prefill
    calls them: at ``flash_plan``'s tiles, as every caller of the kernels."""
    from tpu_parallel.ops.flash_attention import flash_attention_fwd_bhsd, flash_plan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the derived plan: the resident kernel at a tile of 512 while the row's
    # blocks fit VMEM, which the compile below holds (PERF.md section 6, PR 48)
    plan = flash_plan(seq, 192)["fwd"]
    assert (plan["variant"], plan["block_q"], plan["block_k"]) == (
        "resident", 512, 512
    )
    spec = lambda width: jax.ShapeDtypeStruct(
        (1, 128, seq, width), jnp.bfloat16, sharding=v5e_chip
    )
    lowered = jax.jit(
        functools.partial(flash_attention_fwd_bhsd, interpret=False)
    ).lower(spec(192), spec(192), spec(128))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert lowered.out_info.shape == (1, 128, seq, 128)
    lowered.compile()  # raises what the chip's compiler would raise


@pytest.mark.parametrize("window", [0, 4096])
def test_longest_grouped_forward_compiles_for_v5e(window, v5e_chip, monkeypatch):
    """The expert cell's last rung: one prompt of 8192, 16 query heads on ONE
    K/V head of 128, causal and under the 4096 window of three layers in four,
    forward only as the serving prefill calls it: resident at a tile of 512,
    the window's tiles classified at trace time (the streamed kernel it took
    before masked every one of its 108)."""
    from tpu_parallel.ops.flash_attention import flash_plan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = flash_plan(8192, 128, 16, window=window)["fwd"]
    assert plan == {
        "block_q": 512, "block_k": 512, "variant": "resident",
        "tiles_computed": 108 if window else 136,
        "tiles_masked": 16 + 8 if window else 16,
    }
    spec = lambda heads: jax.ShapeDtypeStruct(
        (1, 8192, heads, 128), jnp.bfloat16, sharding=v5e_chip
    )
    lowered = jax.jit(
        functools.partial(flash_attention, window=window, interpret=False)
    ).lower(spec(16), spec(1), spec(1))
    assert lowered.as_text().count("tpu_custom_call") == 1
    lowered.compile()


@pytest.mark.parametrize("program", ["decode_tick", "prefill_8192"])
def test_latent_attn_cell_compiles_for_v5e(program, v5e_chip, monkeypatch):
    """The serving cell of the latent-attention expert decoder at its
    published widths, one chip's share of the first stage, built from the
    benchmark's own configuration and cell files: the fused decode tick over
    the cell's slots (ONE row of 576 a position and layer, no K/V heads; the
    absorbed form through XLA; 16 held experts a layer through the streamed
    kernel) and the largest whole-prompt prefill (five flash kernels at 192 /
    128; the routed sum in blocks of tokens, ``moe._combine``) fit one v5e
    BESIDE the pool."""
    import json

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from drivers.serve_latent_attn import model_config

    from tpu_parallel.models import GPTLM
    from tpu_parallel.serving import cache_pool, engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    read = lambda *rel: json.load(open(os.path.join(REPO, "benchmarks", *rel)))
    cell = read("workloads", "serve-openpangu_ultra_moe_718b_share16-longdoc.json")
    cfg = model_config(
        read("configs", "openpangu_ultra_moe_718b_share16.json"), cell["engine"]
    )
    model, n = GPTLM(cfg), cell["engine"]["n_slots"]
    on_chip = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=v5e_chip
    )
    params = jax.tree.map(
        lambda x: on_chip(x, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 16), jnp.int32),
            train=False,
        ))["params"],
    )
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_919_139_840
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)
    floats = lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=v5e_chip
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip)
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda p: cache_pool._pool_cache_shapes(model, p, n), params
    ))
    by_name = {}
    for p, x in jax.tree_util.tree_flatten_with_path(pool)[0]:
        by_name.setdefault(p[-1].key, []).append(x.size * x.dtype.itemsize)
    assert set(by_name) == {"cached_latent", "cached_pos", "cache_index"}
    assert sum(by_name["cached_latent"]) == n * 8192 * 5 * 576 * 2
    pool_bytes = sum(sum(v) for v in by_name.values())
    if program == "prefill_8192":
        lowered = jax.jit(
            lambda p, toks, pos, last, rng: engine._prefill_core(
                model, p, toks, pos, last, rng
            )
        ).lower(params, ints(1, 8192), ints(1, 8192), ints(1), key)
        # one flash kernel a layer; the prompt's 65536 assignments go through
        # lax.ragged_dot
        assert lowered.as_text().count("tpu_custom_call") == 5
        beside = pool_bytes  # the pool stays on the chip while a prompt runs
    else:
        live = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=v5e_chip)
        state = (ints(n), ints(n), ints(n), live, ints(n))
        knobs = (ints(n), floats(n), ints(n), floats(n))
        lowered = engine._fused_engine_fn(model, 8).lower(
            params, state, knobs, pool, key, None
        )
        beside = 0  # the pool is an argument
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes + beside
    print(program, memory.argument_size_in_bytes / 1e9,
          memory.temp_size_in_bytes / 1e9, beside / 1e9)
    # 9.84 GB of weights and the pool, and half a GB of the chip's 15.75 left
    assert 9.84e9 + pool_bytes < held < 15.25e9, memory
    if program == "decode_tick":
        text = compiled.as_text()
        assert len(re.findall(r"%ragged-dot-streamed[.\d]* = ", text)) >= 4
        assert "attn.decode_stripes" not in text  # attn_plan says xla


# name -> (slots, rows a slot, heads, K/V heads, stored positions, window,
# block rule): the decode steps of the two serving cells with heads of 128
DECODE_CASES = {
    "blockgen_wide": (64, 8, 32, 4, 4096, 0, 4),
    "blockgen_narrow": (64, 4, 32, 4, 4096, 0, 4),
    "longshort_window": (32, 1, 16, 1, 8192, 4096, 0),
    "longshort_full": (32, 1, 16, 1, 8192, 0, 0),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_kernel_compiles_for_v5e(case, v5e_chip, monkeypatch):
    """``layers.decode_attention`` at a cell's decode shape, behind the
    scatter that writes the step's K/V into the donated pool as the model
    does: ONE kernel (``attn.decode_stripes``) that reads the pool as it is
    stored.  The compiled step holds no copy of a stripe: its temporaries
    stay under a tenth of one (the ``[slots, positions, kv_heads *
    head_dim]`` view of 4 K/V heads re-tiled both stripes, 540 MB)."""
    from tpu_parallel.models.layers import decode_attention

    n, new, heads, kv, positions, window, block_len = DECODE_CASES[case]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shaped = lambda dtype, *shape: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )

    def step(q, k_all, v_all, k_pos, k, v, pos):
        rows = jnp.arange(n)[:, None]
        k_all, v_all = k_all.at[rows, pos].set(k), v_all.at[rows, pos].set(v)
        k_pos = k_pos.at[rows, pos].set(pos)
        out = decode_attention(
            q, k_all, v_all, pos, window=window, k_positions=k_pos,
            block_len=block_len,
        )
        return out, k_all, v_all, k_pos

    bf16 = jnp.bfloat16
    lowered = jax.jit(step, donate_argnums=(1, 2, 3)).lower(
        shaped(bf16, n, new, heads, 128), shaped(bf16, n, positions, kv, 128),
        shaped(bf16, n, positions, kv, 128), shaped(jnp.int32, n, positions),
        shaped(bf16, n, new, kv, 128), shaped(bf16, n, new, kv, 128),
        shaped(jnp.int32, n, new),
    )
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    assert len(re.findall(r"%attn\.decode_stripes[.\d]* = ", compiled.as_text())) == 1
    stripe = n * positions * kv * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < stripe // 10


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX already uses it and the code
    sets no directory of its own."""
    from tpu_parallel.runtime import enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch):
    from tpu_parallel.runtime import COMPILE_CACHE_DIR, enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compilation_cache() == COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == COMPILE_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert COMPILE_CACHE_DIR == os.path.join(REPO, ".xla_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".xla_cache/" in fh.read().split()


def test_peak_flops_is_exact_and_refuses_unknown_devices():
    from types import SimpleNamespace

    from tpu_parallel.utils.profiling import mfu, peak_flops

    v5e = SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert peak_flops(v5e) == 197e12
    # no substring catch-all: a kind the table does not hold is an error
    for kind in ("TPU v5 lite (new)", "tpu v5 lite", "TPU v7", "cpu"):
        with pytest.raises(ValueError, match="no peak FLOPs/s"):
            peak_flops(SimpleNamespace(device_kind=kind, platform="tpu"))
    from tpu_parallel.models import gpt2_125m

    with pytest.raises(ValueError, match="no peak FLOPs/s"):
        mfu(1e5, gpt2_125m(), jax.devices()[0])  # the CPU has no entry either


def test_chip_smoke_fails_without_a_tpu():
    """The contract, literally: under ``JAX_PLATFORMS=cpu`` the script exits
    non-zero and prints no result — at the device check, before any model is
    built, so this is quick."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert "no TPU" in proc.stderr


def test_bench_fails_without_a_tpu(capsys):
    """bench.py is one process that measures on a TPU or fails: no CPU
    fallback, no retry, no older result (same check as above, in-process)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    with pytest.raises(RuntimeError, match="no TPU"):
        bench.main()
    assert capsys.readouterr().out == ""
