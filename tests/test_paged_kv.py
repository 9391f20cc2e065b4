"""Block-paged KV cache tests: BlockAllocator invariants (refcounts,
double-free, fragmentation round-trip), copy-on-write under concurrent
sharers, greedy bitwise parity with the fixed-slot engine across every
serving path (per-step / fused / speculative / chunked / int8 / cluster
crash-replay), zero-copy prefix sharing, admission-by-blocks, the
donation pin (the compile-count pin is the ``paged`` case of
``test_serving.py::test_fused_tick_compile_count_pin``), one program
family for both pools.  (The ``check_blocks`` mutation fence moved to
``tests/test_checkers.py``, the single entry point over the
``scripts/check_all.py`` registry.)"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_parallel.cluster import FaultPlan, Frontend, ReplicaHandle
from tpu_parallel.cluster.replica import DEAD
from tpu_parallel.models import GPTLM, tiny_test
from tpu_parallel.serving import (
    FINISHED,
    REJECTED,
    BlockAllocator,
    PagedCachePool,
    Request,
    SchedulerConfig,
    ServingEngine,
)
from tpu_parallel.serving.request import REJECT_CAPACITY

BT = 8  # block_tokens used throughout (divides tiny_test's seq_len=32)


@pytest.fixture(scope="module")
def env():
    """One tiny float32 model + mixed-length prompts with a long shared
    header (so prefix sharing and COW paths actually exercise)."""
    cfg = tiny_test(dtype=jnp.float32, remat=False)
    model = GPTLM(cfg)
    rng = jax.random.PRNGKey(11)
    shared = [
        int(t)
        for t in np.asarray(
            jax.random.randint(rng, (20,), 1, cfg.vocab_size)
        )
    ]
    prompts = [
        shared[:9],
        shared[:17] + [3, 1, 4],
        shared[:17] + [5, 9],
        [int(t) for t in np.asarray(
            jax.random.randint(
                jax.random.fold_in(rng, 1), (6,), 1, cfg.vocab_size
            )
        )],
    ]
    probe = jax.random.randint(rng, (1, 20), 1, cfg.vocab_size)
    params = model.init(
        {"params": jax.random.PRNGKey(1)}, probe, train=False
    )["params"]
    return cfg, model, params, prompts


def _run_engine(env, paged, n_new=8, stagger=False, **kw):
    cfg, model, params, prompts = env
    kwargs = dict(
        n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=4),
        decode_steps_per_tick=1,
    )
    kwargs.update(kw)
    if paged:
        kwargs.setdefault("kv_block_tokens", BT)
    else:
        kwargs.pop("kv_block_tokens", None)
    eng = ServingEngine(model, params, **kwargs)
    outs = []
    for i, p in enumerate(prompts):
        outs.append(
            eng.add_request(Request(request_id=str(i), prompt=p,
                                    max_new_tokens=n_new))
        )
        if stagger:
            eng.step()
    eng.run(max_ticks=500)
    assert all(o.status == FINISHED for o in outs)
    return [o.tokens for o in outs], eng


# -- BlockAllocator invariants ----------------------------------------------


def test_allocator_refcounts_and_double_free():
    """Refcounts never go negative: freeing an unreferenced block raises
    (the double-free guard), as does sharing one; a shared block only
    returns to the free list when the LAST reference drops."""
    al = BlockAllocator(4)
    a = al.alloc()
    assert al.refcount(a) == 1 and al.in_use == 1
    al.share(a)
    assert al.refcount(a) == 2
    assert al.free(a) is False  # one sharer left: stays allocated
    assert al.free(a) is True  # last reference: back on the free list
    with pytest.raises(ValueError, match="double free"):
        al.free(a)
    with pytest.raises(ValueError, match="share of unallocated"):
        al.share(a)
    with pytest.raises(ValueError):
        al.free(99)
    al.check()
    assert al.n_free == 4


def test_allocator_exhaustion_raises():
    al = BlockAllocator(2)
    al.alloc(), al.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        al.alloc()


def test_allocator_fragmentation_round_trip():
    """Seeded alloc/share/free storm: every intermediate state passes the
    refcount/free-list audit and the storm ends with the free list
    holding exactly the pool capacity (no leak, no double-entry)."""
    rng = np.random.RandomState(0)
    al = BlockAllocator(16)
    held = []  # (block, refs_held)
    for _ in range(600):
        op = rng.randint(3)
        if op == 0 and al.n_free:
            held.append([al.alloc(), 1])
        elif op == 1 and held:
            ent = held[rng.randint(len(held))]
            al.share(ent[0])
            ent[1] += 1
        elif held:
            i = rng.randint(len(held))
            blk, refs = held[i]
            al.free(blk)
            if refs == 1:
                held.pop(i)
            else:
                held[i][1] -= 1
        al.check()
    for blk, refs in held:
        for _ in range(refs):
            al.free(blk)
    al.check()
    assert al.n_free == 16 and al.in_use == 0


# -- engine parity with the fixed-slot layout --------------------------------


@pytest.mark.parametrize(
    "mode",
    ["per_step", "fused", "spec", "chunked", "bucketed_prefix",
     "fused_prefix"],
)
def test_paged_greedy_parity(env, mode):
    """Acceptance: greedy output bitwise identical to the fixed-slot
    engine under every serving path — the paged gather/scatter is a pure
    relayout."""
    kw = dict(
        per_step=dict(),
        fused=dict(decode_steps_per_tick=4),
        spec=dict(draft_tokens=4),
        chunked=dict(
            prefill_chunk_tokens=8, prefill_buckets=(8, 16, 32),
            prefix_cache_size=4,
        ),
        bucketed_prefix=dict(
            prefill_buckets=(8, 16, 32), prefix_cache_size=4,
        ),
        fused_prefix=dict(
            decode_steps_per_tick=4, prefill_buckets=(8, 16, 32),
            prefix_cache_size=4,
        ),
    )[mode]
    fixed, _ = _run_engine(env, paged=False, stagger=True, **kw)
    paged, eng = _run_engine(env, paged=True, stagger=True, **kw)
    assert fixed == paged, f"paged {mode} diverged from fixed-slot"
    eng.pool.allocator.check()


def test_paged_int8_parity(env):
    import dataclasses

    cfg, model, params, prompts = env
    m8 = GPTLM(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    env8 = (m8.config, m8, params, prompts)
    fixed, _ = _run_engine(env8, paged=False, stagger=True)
    paged, eng = _run_engine(env8, paged=True, stagger=True)
    assert fixed == paged, "paged int8 decode diverged from fixed-slot"
    eng.pool.allocator.check()


def test_paged_cluster_crash_replay_exact(env):
    """The cluster crash guarantee holds over the paged pool: a replica
    dying mid-request is replayed forced-prefix on the survivor, greedy
    output bitwise equal to a no-fault paged baseline (itself pinned to
    the fixed-slot engine by the parity suite)."""
    cfg, model, params, prompts = env

    def mk():
        return ServingEngine(
            model, params, n_slots=4, decode_steps_per_tick=1,
            kv_block_tokens=BT,
            scheduler=SchedulerConfig(max_prefills_per_tick=4),
        )

    baseline = mk()
    base_outs = [
        baseline.add_request(Request(prompt=p, max_new_tokens=8))
        for p in prompts
    ]
    baseline.run(max_ticks=500)
    assert all(o.status == FINISHED for o in base_outs)

    h0 = ReplicaHandle(0, mk(), fault_plan=FaultPlan(crash_at_tick=3))
    h1 = ReplicaHandle(1, mk())
    fe = Frontend([h0, h1], router="rr")
    outs = [
        fe.submit(Request(prompt=p, max_new_tokens=8)) for p in prompts
    ]
    fe.run(max_ticks=500)
    assert h0.health == DEAD and fe.summary()["retries"] > 0
    for i, (out, base) in enumerate(zip(outs, base_outs)):
        assert out.status == FINISHED
        np.testing.assert_array_equal(
            np.asarray(out.tokens), np.asarray(base.tokens),
            err_msg=f"request {i} diverged after paged failover",
        )


# -- prefix sharing and copy-on-write ----------------------------------------


def test_paged_prefix_hit_zero_copies(env):
    """A paged prefix hit is a table pointer write + refcount bump —
    counter-verified: shared blocks were mapped, NO copy-on-write ran
    (block-aligned buckets), and the paged pool doesn't even expose the
    fixed layout's row-copy surface."""
    _, eng = _run_engine(
        env, paged=True, stagger=True,
        prefill_buckets=(8, 16, 32), prefix_cache_size=4,
    )
    assert eng.metrics.prefix_hits > 0
    assert eng.metrics.prefix_shared_blocks > 0
    assert eng.pool.shared_block_maps > 0
    # aligned sharing: remainders start at block boundaries, so the hit
    # path never copies a single block
    assert eng.pool.cow_copies == 0
    for name in ("copy_prefix", "stack_prefix", "extract", "insert"):
        assert not hasattr(eng.pool, name), (
            f"PagedCachePool.{name} exists — the O(prefix) row-copy "
            "economy leaked back into the paged layout"
        )


def test_paged_cow_under_concurrent_sharers(env):
    """With a block size COARSER than the bucket quantum, stored prefixes
    end mid-block, so the owner's decode and every hitter's remainder
    write land in SHARED blocks: each sharer copy-on-writes its own copy
    of that one block and greedy output still matches the fixed-slot
    engine bitwise."""
    kw = dict(
        kv_block_tokens=16,  # bucket 8 ends mid-block -> shared tails
        prefill_buckets=(8, 16, 32), prefix_cache_size=4,
    )
    cfg, model, params, prompts = env
    fixed, _ = _run_engine(env, paged=False, stagger=True,
                           prefill_buckets=(8, 16, 32),
                           prefix_cache_size=4)
    paged, eng = _run_engine(env, paged=True, stagger=True, **kw)
    assert fixed == paged, "COW path diverged from fixed-slot"
    assert eng.pool.cow_copies > 0, (
        "mid-block sharing never copy-on-wrote — the COW path is dead "
        "and sharers are scribbling on each other"
    )
    eng.pool.allocator.check()


def test_paged_pool_cow_isolates_sharers(env):
    """Pool-level COW: two slots mapping one shared block diverge on
    first write — the writer gets a fresh block, the other sharer (and
    the stored entry) keep reading the original bytes."""
    cfg, model, params, _ = env
    import dataclasses

    pm = GPTLM(
        dataclasses.replace(cfg, kv_block_tokens=BT, kv_pool_blocks=8)
    )
    pool = PagedCachePool(pm, params, n_slots=2)
    assert pool.acquire() == 0 and pool.acquire() == 1
    pool.begin_slot(0, 2 * BT)
    pool.ensure_writable(0, 0, BT)
    blocks = pool.snapshot_blocks(0, BT)  # entry holds one reference
    pool.map_prefix(1, blocks, BT)  # slot 1 shares the same block
    shared = int(pool.block_table[1, 0])
    assert shared == int(pool.block_table[0, 0])
    assert pool.allocator.refcount(shared) == 3  # owner + entry + sharer
    pool.ensure_writable(1, 0, BT)  # slot 1's first write: COW
    assert pool.cow_copies == 1
    assert int(pool.block_table[1, 0]) != shared
    assert pool.allocator.refcount(shared) == 2
    pool.release(1)
    pool.release(0)
    pool.free_stored(blocks)
    pool.allocator.check()
    assert pool.allocator.n_free == 8


def test_paged_release_returns_all_blocks(env):
    """Fragmentation round-trip at the engine level: after a full run the
    only live blocks are the prefix cache's refcounted entries; dropping
    those returns the free list to capacity."""
    _, eng = _run_engine(
        env, paged=True, stagger=True,
        prefill_buckets=(8, 16, 32), prefix_cache_size=4,
    )
    held = {
        b
        for blocks, _ in eng._prefix._entries.values()
        for b in blocks
    }  # distinct: a short key's blocks are a prefix of a longer key's
    assert eng.pool.blocks_in_use == len(held)
    for blocks, _ in list(eng._prefix._entries.values()):
        eng.pool.free_stored(blocks)
    eng.pool.allocator.check()
    assert eng.pool.blocks_in_use == 0


# -- admission by blocks ------------------------------------------------------


def test_paged_capacity_decoupled_from_seq_len(env):
    """Acceptance: at EQUAL pool bytes, paged admits >= 2x the concurrent
    short requests — a fixed pool buys whole seq_len rows (2 here), the
    paged pool buys blocks (one per short request)."""
    cfg, model, params, _ = env
    short = [[7, 3, 5]] * 8  # 3 prompt + 4 new = 7 tokens = 1 block
    fixed = ServingEngine(
        model, params, n_slots=2, decode_steps_per_tick=1,
        scheduler=SchedulerConfig(max_prefills_per_tick=8),
    )
    # same K/V bytes: 2 slots x seq_len 32 == 8 blocks x 8 tokens
    paged = ServingEngine(
        model, params, n_slots=8, kv_block_tokens=BT, kv_pool_blocks=8,
        decode_steps_per_tick=1,
        scheduler=SchedulerConfig(max_prefills_per_tick=8),
    )
    for eng in (fixed, paged):
        for i, p in enumerate(short):
            out = eng.add_request(
                Request(request_id=str(i), prompt=p, max_new_tokens=4)
            )
            assert out.status != REJECTED
        eng.step()
    assert fixed.in_flight == 2  # slot-bound
    assert paged.in_flight == 8  # block-bound: 4x the same bytes
    assert paged.in_flight >= 2 * fixed.in_flight
    paged.run(max_ticks=200)
    fixed.run(max_ticks=200)
    paged.pool.allocator.check()
    assert paged.pool.blocks_free == 8


def test_paged_block_gate_holds_head_until_blocks_free(env):
    """Transient block exhaustion QUEUES (head-of-line) instead of
    rejecting: the queued head admits once a running request retires its
    blocks, and everything finishes."""
    cfg, model, params, _ = env
    eng = ServingEngine(
        model, params, n_slots=4, kv_block_tokens=BT, kv_pool_blocks=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=4),
    )
    outs = [
        eng.add_request(
            Request(request_id=str(i), prompt=[5, 3], max_new_tokens=12)
        )  # 14 tokens = 2 blocks: exactly one fits at a time
        for i in range(3)
    ]
    eng.step()
    assert eng.in_flight == 1 and eng.scheduler.depth == 2
    eng.run(max_ticks=500)
    assert all(o.status == FINISHED for o in outs)
    eng.pool.allocator.check()
    assert eng.pool.blocks_free == 2


def test_paged_cow_cannot_exhaust_pool_midtick(env):
    """Regression (post-review): with buckets NOT aligned to the block
    size, prefix sharing lands mid-block and sharers' writes
    copy-on-write — each COW claims a fresh block the plain
    ceil(total/bt) admission estimate cannot see.  Un-reserved, two
    admitted requests' COWs exhausted a tight pool MID-TICK
    (RuntimeError out of step(), every in-flight request killed).  The
    admission gate now carries a COW reserve per non-aligned bucket AND
    evicts LRU prefix entries under block pressure instead of starving
    the queue head behind blocks that stored prefixes hold forever."""
    cfg, model, params, _ = env
    eng = ServingEngine(
        model, params, n_slots=2, decode_steps_per_tick=1,
        prefill_buckets=(12, 24), prefix_cache_size=4,
        kv_block_tokens=8, kv_pool_blocks=5,
    )
    assert eng._cow_reserve > 0
    rng = np.random.RandomState(0)
    outs = [
        eng.add_request(
            Request(
                request_id=str(i),
                prompt=list(rng.randint(1, cfg.vocab_size, 14)),
                max_new_tokens=n_new,
            )
        )
        for i, n_new in enumerate((10, 2, 6))
    ]
    eng.run(max_ticks=800)  # un-fixed: RuntimeError 'block pool exhausted'
    assert all(o.status == FINISHED for o in outs)
    assert eng.pool.cow_copies > 0  # the hazard actually exercised
    assert eng._prefix.evictions > 0  # the pressure valve actually opened
    eng.pool.allocator.check()


def test_paged_first_token_finish_seeds_prefix(env):
    """Regression (post-review): a request finishing on its very first
    token (max_new_tokens=1 / immediate EOS) retires its slot inside the
    admission tick's _activate, and release() wipes the paged slot's
    block table — the prefix store must snapshot BEFORE activation or
    step() dies with ValueError 'cannot snapshot' (the fixed-slot path
    only survived the old ordering because extract() on a released slot
    still read intact row bytes)."""
    cfg, model, params, prompts = env
    eng = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        decode_steps_per_tick=1, kv_block_tokens=BT,
        prefill_buckets=(8, 16), prefix_cache_size=4,
    )
    one = eng.add_request(Request(prompt=prompts[0], max_new_tokens=1))
    eng.run(max_ticks=50)  # un-fixed: ValueError out of step()
    assert one.status == FINISHED and len(one.tokens) == 1
    hits0 = eng.metrics.prefix_hits
    again = eng.add_request(Request(prompt=prompts[0], max_new_tokens=4))
    eng.run(max_ticks=100)
    assert again.status == FINISHED
    assert eng.metrics.prefix_hits > hits0  # the 1-token run seeded it
    assert list(again.tokens[:1]) == list(one.tokens)  # greedy parity
    eng.pool.allocator.check()
    # same immediate retirement through the chunked completion path
    engc = ServingEngine(
        model, params, n_slots=2,
        scheduler=SchedulerConfig(max_prefills_per_tick=2),
        decode_steps_per_tick=1, kv_block_tokens=BT,
        prefill_buckets=(8, 16), prefix_cache_size=4,
        prefill_chunk_tokens=6,
    )
    outc = engc.add_request(Request(prompt=prompts[1], max_new_tokens=1))
    engc.run(max_ticks=50)
    assert outc.status == FINISHED and len(outc.tokens) == 1
    engc.pool.allocator.check()


def test_paged_prefix_pin_survives_same_tick_eviction(env):
    """Regression (post-review): _admit_batch_paged looks up every hit
    up front but maps per group — with a size-1 prefix cache, an
    earlier-processed miss group's store LRU-evicts the hit entry
    (free_stored, refcount to zero) before the later group's map_prefix,
    raising 'share of unallocated block' — or silently attending another
    request's K/V if the freed block was re-allocated first.  The
    admission pin keeps looked-up blocks alive until mapped."""
    cfg, model, params, prompts = env
    eng = ServingEngine(
        model, params, n_slots=4,
        scheduler=SchedulerConfig(max_prefills_per_tick=4),
        decode_steps_per_tick=1, kv_block_tokens=4,
        prefill_buckets=(4, 8), prefix_cache_size=1,
    )
    P = prompts[0][:5]
    seed = eng.add_request(Request(prompt=P, max_new_tokens=6))
    eng.run(max_ticks=100)
    assert seed.status == FINISHED
    # same tick: A (miss -> group (0, w) first; its store evicts P's
    # entry) + B (hit on P's entry -> group (4, w') second)
    a = eng.add_request(Request(prompt=[7, 7, 5, 2, 9], max_new_tokens=6))
    b = eng.add_request(Request(prompt=P, max_new_tokens=6))
    eng.run(max_ticks=100)  # un-fixed: ValueError out of step()
    assert a.status == FINISHED and b.status == FINISHED
    assert list(b.tokens) == list(seed.tokens)  # greedy, same prompt
    eng.pool.allocator.check()


def test_paged_admission_rejects_impossible_request(env):
    """A request whose worst case exceeds the WHOLE pool can never admit
    — typed capacity reject at submit, same vocabulary the cluster
    frontend already understands."""
    cfg, model, params, _ = env
    eng = ServingEngine(
        model, params, n_slots=2, kv_block_tokens=BT, kv_pool_blocks=2,
    )
    out = eng.add_request(Request(prompt=[1] * 20, max_new_tokens=10))
    assert out.status == REJECTED
    assert out.finish_reason == REJECT_CAPACITY
    assert "KV blocks" in out.detail


# -- donation and compile pins ------------------------------------------------


def test_paged_fused_tick_donation_invalidates_old_buffers(env):
    """The paged pool rides the same donation-and-ownership contract as
    the fixed-slot pool: after a fused tick (and a per-step tick) the
    previous tick's cache and device-state buffers are DELETED — no
    second pool copy exists, stale references raise on use (mirrors
    ``test_fused_tick_donation_invalidates_old_buffers``)."""
    cfg, model, params, prompts = env
    for steps in (1, 4):
        eng = ServingEngine(
            model, params, n_slots=2, decode_steps_per_tick=steps,
            kv_block_tokens=BT,
        )
        out = eng.add_request(Request(prompt=prompts[0], max_new_tokens=12))
        eng.step()  # admit + first decode tick
        old_cache = jax.tree_util.tree_leaves(eng.pool.cache)
        old_state = (
            jax.tree_util.tree_leaves(eng._dev_state) if steps > 1 else []
        )
        eng.step()  # decode-only tick: donates cache (and fused state)
        assert all(leaf.is_deleted() for leaf in old_cache), (
            f"T={steps}: old paged pool buffers survived the tick "
            "(donation regressed — a second full pool copy is alive)"
        )
        assert all(leaf.is_deleted() for leaf in old_state)
        # the block table is NOT donated: the host mirror stays the
        # authority and the device copy is reused across ticks
        assert eng._dev_table is not None
        assert not eng._dev_table.is_deleted()
        eng.run(max_ticks=200)
        assert out.status == FINISHED and len(out.tokens) == 12


def test_pools_share_one_program_family(env):
    """A fixed-slot and a paged engine over one base model draw their tick
    programs from the same factories, one cache entry per pool's model
    (the paged one carries ``kv_block_tokens``); the block table is their
    last operand and on the fixed-slot pool no parameter at all: the
    fused program lowers from its five operands."""
    from tpu_parallel.serving import engine as engine_mod

    engine_mod._engine_fns.cache_clear()
    engine_mod._fused_engine_fn.cache_clear()
    cfg, model, params, _ = env
    kw = dict(n_slots=2, decode_steps_per_tick=4)
    fixed = ServingEngine(model, params, **kw)
    assert engine_mod._fused_engine_fn.cache_info().currsize == 1
    paged = ServingEngine(model, params, kv_block_tokens=BT, **kw)
    assert engine_mod._fused_engine_fn.cache_info().currsize == 2
    assert engine_mod._engine_fns.cache_info().currsize == 2
    assert fixed.model is model and paged.model.config.kv_block_tokens == BT
    assert fixed._device_table() is None
    assert paged._device_table().shape == (2, cfg.seq_len // BT)
    fixed._upload_slot_state()
    operands = (
        params, fixed._dev_state, fixed._dev_knobs, fixed.pool.cache,
        jax.random.PRNGKey(0),
    )
    five = fixed._fused_fn.lower(*operands)
    six = fixed._fused_fn.lower(*operands, fixed._device_table())
    assert five.as_text() == six.as_text()
    n_leaves = len(jax.tree_util.tree_leaves(operands))
    assert len(jax.tree_util.tree_leaves(five.in_avals)) == n_leaves


# (The block-table mutation fence — and every other AST contract gate —
# is wired tier-1 through the single scripts/check_all.py registry entry
# point in tests/test_checkers.py.)
