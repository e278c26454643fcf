# Paged KV cache (ISSUE 14): the block-pool allocator's invariants
# (property tests over random alloc/free/pin/release sequences), the
# paged attention op's parity with the contiguous reference, and the
# engine-level greedy f32 CPU bit-identity gates — paged-on vs
# paged-off across the plain, prefix-cache (zero-copy pointer
# admission), spec-decode, chunked-prefill, chaos-replay, and
# journal-warm-restart paths — plus the capacity claim: a pool smaller
# than slots x max_len still serves every stream.
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.engine.kv_pool import (
    BLOCK_TABLE_DTYPE,
    BlockPool,
    KVPoolExhausted,
)
from copilot_for_consensus_tpu.engine.prefix_cache import PrefixCache
from copilot_for_consensus_tpu.models.configs import decoder_config

CFG = decoder_config("tiny")


def _params():
    from copilot_for_consensus_tpu.models import decoder

    return decoder.init_params(jax.random.PRNGKey(7), CFG,
                               dtype=jnp.float32)


def _engine(params, paged_blocks=0, **kw):
    from copilot_for_consensus_tpu.engine.generation import (
        GenerationEngine,
    )

    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 256)
    kw.setdefault("prefill_buckets", (64, 128, 192))
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("kv_dtype", jnp.float32)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("decode_window", 4)
    kw.setdefault("prefill_chunk", 64)
    return GenerationEngine(CFG, params, kv_pool_blocks=paged_blocks,
                            **kw)


# ---------------------------------------------------------------------------
# BlockPool allocator invariants (property tests)
# ---------------------------------------------------------------------------


def _pool(n=16, blk=4):
    return BlockPool(CFG, num_blocks=n, block_size=blk,
                     kv_dtype=jnp.float32)


def test_alloc_is_exclusive_and_free_returns():
    p = _pool(8)
    a = p.alloc(3)
    b = p.alloc(2)
    assert len(set(a) | set(b)) == 5          # never double-assigned
    assert p.free_blocks == 3
    p.free(a)
    assert p.free_blocks == 6
    c = p.alloc(6)
    assert len(set(c) | set(b)) == 8


def test_double_free_and_oob_free_raise():
    p = _pool(4)
    a = p.alloc(2)
    p.free(a)
    with pytest.raises(ValueError, match="double free"):
        p.free([a[0]])
    with pytest.raises(ValueError, match="out-of-range"):
        p.free([99])


def test_pinned_blocks_cannot_be_freed_and_pins_are_counted():
    p = _pool(4)
    a = p.alloc(1)
    p.pin(a)
    p.pin(a)
    assert p.pinned_blocks == 1
    assert p.pins(a[0]) == 2
    with pytest.raises(ValueError, match="pinned"):
        p.free(a)
    p.release(a)
    with pytest.raises(ValueError, match="pinned"):
        p.free(a)
    p.release(a)
    p.free(a)
    with pytest.raises(ValueError, match="underflow"):
        p.release(a)


def test_pin_of_free_block_raises():
    p = _pool(4)
    with pytest.raises(ValueError, match="pin of free"):
        p.pin([0])


def test_exhaustion_is_all_or_nothing_and_classified():
    from copilot_for_consensus_tpu.engine.supervisor import (
        is_resource_exhaustion,
    )

    p = _pool(4)
    p.alloc(3)
    with pytest.raises(KVPoolExhausted) as ei:
        p.alloc(2)
    assert p.free_blocks == 1                 # nothing partially taken
    assert is_resource_exhaustion(ei.value)


def test_random_sequences_never_leak_or_alias():
    """Property: under arbitrary interleavings of alloc/free/pin/
    release, every block is in exactly one place and the count books
    balance."""
    rng = np.random.default_rng(0)
    p = _pool(12)
    held: list[int] = []
    pinned: list[int] = []
    for _ in range(2000):
        op = rng.integers(0, 4)
        if op == 0 and p.free_blocks:
            n = int(rng.integers(1, p.free_blocks + 1))
            got = p.alloc(n)
            assert not (set(got) & set(held))
            held += got
        elif op == 1 and held:
            i = int(rng.integers(0, len(held)))
            bid = held[i]
            if bid not in pinned:
                held.pop(i)
                p.free([bid])
        elif op == 2 and held:
            bid = held[int(rng.integers(0, len(held)))]
            p.pin([bid])
            pinned.append(bid)
        elif op == 3 and pinned:
            i = int(rng.integers(0, len(pinned)))
            p.release([pinned.pop(i)])
        assert p.free_blocks + len(held) == p.num_blocks
        assert p.pinned_blocks == len(set(pinned))


def test_rebuild_free_list_reclaims_unowned_blocks():
    p = _pool(8)
    a = p.alloc(4)
    p.pin(a[:1])
    changed = p.rebuild_free_list(owned=set(a[:2]))
    assert sorted(changed) == sorted(a[2:])
    assert p.free_blocks == 6
    assert p.pins(a[0]) == 1                  # owned keeps its pin


# ---------------------------------------------------------------------------
# shared-pool PrefixCache: refcounted adopt handoff
# ---------------------------------------------------------------------------


def _shared_prefix(pool):
    return PrefixCache(CFG, num_blocks=1, block_size=pool.block,
                       shared=pool)


def test_adopt_blocks_hands_off_without_copy_and_pins():
    pool = _pool(8)
    pc = _shared_prefix(pool)
    tokens = list(range(10, 26))                       # 4 blocks of 4
    table = pool.alloc(4)
    adopted = pc.adopt_blocks(tokens, table, owned_from=0)
    assert adopted == set(table)
    assert pool.pinned_blocks == 4                     # trie pins
    # the adopted blocks are NOT freeable (pinned) — "refcounted
    # publish keeps pinned blocks out of the free list"
    with pytest.raises(ValueError, match="pinned"):
        pool.free(table)
    # dedup: a second slot retiring the same prefix adopts nothing
    table2 = pool.alloc(4)
    adopted2 = pc.adopt_blocks(tokens, table2, owned_from=0)
    assert adopted2 == set()
    pool.free(table2)                                  # caller frees
    # a match pins nodes; eviction cannot touch them
    m = pc.lookup(tokens + [1])
    assert m.tokens == 16
    assert pc.evictable_blocks == 0
    pc.release(m)
    assert pc.evictable_blocks == 4


def test_shared_eviction_returns_blocks_to_the_pool():
    pool = _pool(8)
    pc = _shared_prefix(pool)
    tokens = list(range(10, 26))
    pc.adopt_blocks(tokens, pool.alloc(4), owned_from=0)
    assert pool.free_blocks == 4
    got = pc.reclaim(2)
    assert got == 2
    assert pool.free_blocks == 6
    assert pc.node_count == 2
    # flush returns the rest
    pc.flush()
    assert pool.free_blocks == 8
    assert pool.pinned_blocks == 0


def test_adopt_blocks_is_transactional_on_corrupt_tables():
    """A corrupted table entry (a free block id where an owned one
    should be) must adopt NOTHING and pin nothing — the caller frees
    the slot's owned blocks right after, so a partial adoption would
    turn _retire's publish-failure containment into an uncontained
    free-of-pinned-block error."""
    pool = _pool(8)
    pc = _shared_prefix(pool)
    tokens = list(range(10, 26))                       # 4 blocks of 4
    table = pool.alloc(4)
    bad = list(table)
    bad[2] = pool.alloc(1)[0]
    pool.free([bad[2]])                                # free mid-table
    adopted = pc.adopt_blocks(tokens, bad, owned_from=0)
    assert adopted == set()
    assert pool.pinned_blocks == 0                     # nothing pinned
    assert pc.node_count == 0                          # nothing created
    assert pc.stats.publish_skips == 1
    pool.free(table)                                   # caller-safe


def test_shared_mode_guards_copy_publish_and_alloc():
    pool = _pool(8)
    pc = _shared_prefix(pool)
    with pytest.raises(RuntimeError, match="adopt_blocks"):
        pc.publish([1, 2, 3, 4], {"k": None, "v": None}, 0)
    owned = PrefixCache(CFG, num_blocks=4, block_size=4,
                        kv_dtype=jnp.float32)
    with pytest.raises(RuntimeError, match="publish"):
        owned.adopt_blocks([1, 2, 3, 4], [0], 0)


# ---------------------------------------------------------------------------
# paged attention op: reference parity
# ---------------------------------------------------------------------------


def test_paged_xla_route_is_bitwise_the_gathered_reference():
    from copilot_for_consensus_tpu.ops.attention import decode_attention
    from copilot_for_consensus_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_gather_layer,
    )

    rng = np.random.default_rng(0)
    b, hq, hkv, d, blk, nbtot, nb = 3, 8, 2, 16, 8, 10, 4
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((nbtot, hkv, blk, d)),
                     jnp.float32)
    pv = jnp.asarray(rng.standard_normal((nbtot, hkv, blk, d)),
                     jnp.float32)
    tables = jnp.asarray(rng.integers(0, nbtot, (b, nb)),
                         BLOCK_TABLE_DTYPE)
    lengths = jnp.asarray([5, 0, 29], jnp.int32)
    for window in (0, 7):
        k, v = paged_gather_layer(pk, pv, tables)
        ref = decode_attention(q, k, v, lengths, window=window)
        got = paged_decode_attention(q, pk, pv, tables, lengths,
                                     window=window, impl="xla")
        assert bool(jnp.all(ref == got))
    # fully-masked row (length 0) emits exact zeros
    got = paged_decode_attention(q, pk, pv, tables, lengths,
                                 impl="xla")
    assert bool(jnp.all(got[1] == 0.0))


def test_paged_pallas_kernel_matches_reference_in_interpret_mode():
    """The TPU kernel route, run through the Pallas interpreter on
    CPU: GQA + sliding window + fp8 dequant parity against the
    bit-exact XLA reference (online-softmax reassociation keeps this
    approximate, not bitwise)."""
    from copilot_for_consensus_tpu.ops.attention import decode_attention
    from copilot_for_consensus_tpu.ops.paged_attention import (
        paged_decode_attention_pallas,
        paged_gather_layer,
    )

    rng = np.random.default_rng(1)
    b, hq, hkv, d, blk, nbtot, nb = 4, 8, 2, 16, 8, 12, 4
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((nbtot, hkv, blk, d)),
                     jnp.float32)
    pv = jnp.asarray(rng.standard_normal((nbtot, hkv, blk, d)),
                     jnp.float32)
    tables = jnp.asarray(rng.integers(0, nbtot, (b, nb)),
                         BLOCK_TABLE_DTYPE)
    lengths = jnp.asarray([1, 9, 0, 31], jnp.int32)
    for kp, vp in ((pk, pv),
                   (pk.astype(jnp.float8_e4m3fn),
                    pv.astype(jnp.float8_e4m3fn))):
        for window in (0, 5):
            k, v = paged_gather_layer(kp, vp, tables)
            ref = decode_attention(q, k, v, lengths, window=window)
            got = paged_decode_attention_pallas(
                q, kp, vp, tables, lengths, window=window,
                interpret=True)
            np.testing.assert_allclose(np.asarray(ref),
                                       np.asarray(got), atol=1e-5)


# ---------------------------------------------------------------------------
# engine construction guards
# ---------------------------------------------------------------------------


def test_paged_constructor_guards():
    params = _params()
    with pytest.raises(ValueError, match="divide 128"):
        _engine(params, paged_blocks=16, prefill_chunk=48,
                max_len=192, prefill_buckets=(48,))
    with pytest.raises(ValueError, match="max_len"):
        _engine(params, paged_blocks=16, max_len=200,
                prefill_buckets=(64,))
    with pytest.raises(ValueError, match="cannot hold"):
        _engine(params, paged_blocks=3, max_len=256)


# ---------------------------------------------------------------------------
# engine e2e: greedy f32 CPU bit-identity, paged-on vs paged-off
# ---------------------------------------------------------------------------


def test_paged_plain_decode_bit_identical_and_books_balance():
    params = _params()
    plain = _engine(params)
    paged = _engine(params, paged_blocks=12)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, CFG.vocab_size, size=70).tolist()
               for _ in range(6)]
    want = plain.generate(prompts, max_new_tokens=10)
    got = paged.generate(prompts, max_new_tokens=10)
    for w, g in zip(want, got):
        assert w.tokens == g.tokens
        assert w.finish_reason == g.finish_reason
    st = paged.kv_pool_stats()
    assert st["free_blocks"] == st["num_blocks"]   # all blocks returned
    assert st["paged_admits"] == 6
    assert st["peak_active"] == 4                  # num_slots bound


def test_paged_prefix_cache_zero_copy_bit_identical():
    """The tentpole's hit path: admission appends the matched block
    ids (pinned) — no pool→slot gather, no publish copy — and greedy
    outputs stay bit-identical to the contiguous engine."""
    params = _params()
    plain = _engine(params)
    paged = _engine(params, paged_blocks=16, prefix_cache_blocks=8)
    rng = np.random.default_rng(1)
    shared = rng.integers(3, CFG.vocab_size, size=128).tolist()
    prompts = [shared + rng.integers(3, CFG.vocab_size,
                                     size=30).tolist()
               for _ in range(6)]
    for _round in range(2):
        want = plain.generate(prompts, max_new_tokens=6)
        got = paged.generate(prompts, max_new_tokens=6)
        for w, g in zip(want, got):
            assert w.tokens == g.tokens
    st = paged.kv_pool_stats()
    ps = paged.prefix_stats()
    assert st["zero_copy_admits"] > 0
    assert st["zero_copy_hit_rate"] > 0
    assert ps["prefill_tokens_saved"] >= 6 * 128   # second round all hits
    # the published prefix stays resident (pinned by the trie), the
    # rest of the pool drained back to the allocator
    assert st["pinned_blocks"] == 2                # 128 tokens / 64
    assert st["free_blocks"] == st["num_blocks"] - 2


def test_paged_capacity_exceeds_contiguous_equivalent_ceiling():
    """The capacity claim: a pool holding 8 blocks x 64 = 512 cache
    positions is the contiguous equivalent of TWO max_len=256 slots —
    yet the paged engine runs SIX short streams concurrently on it,
    because slots stop reserving max_len each."""
    params = _params()
    eng = _engine(params, paged_blocks=8, num_slots=6)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, CFG.vocab_size, size=20).tolist()
               for _ in range(6)]
    comps = eng.generate(prompts, max_new_tokens=6)
    assert len(comps) == 6
    st = eng.kv_pool_stats()
    contiguous_equiv_slots = (st["num_blocks"] * st["block_size"]
                              // eng.max_len)
    assert contiguous_equiv_slots == 2
    assert st["peak_active"] == 6 > contiguous_equiv_slots
    assert st["free_blocks"] == st["num_blocks"]


def test_paged_admission_blocks_on_pool_pressure_not_slots():
    """Free-BLOCK accounting: with worst-case footprints that cannot
    all fit, admission holds requests back (no KVPoolExhausted ever
    reaches the dispatch path) and serves them as blocks free."""
    params = _params()
    eng = _engine(params, paged_blocks=10, num_slots=4)
    rng = np.random.default_rng(3)
    # each request's worst case: 128 prompt + 100 new + margin ≈ 4
    # blocks; 10 blocks admit at most 2 at once
    prompts = [rng.integers(3, CFG.vocab_size, size=128).tolist()
               for _ in range(4)]
    rids = [eng.submit(list(p), 100) for p in prompts]
    eng.step()
    assert 0 < len(eng._active) <= 2
    results = {}
    for _ in range(400):
        for c in eng.step():
            results[c.request_id] = c
        if len(results) == len(rids):
            break
    assert len(results) == len(rids)
    st = eng.kv_pool_stats()
    assert st["free_blocks"] == st["num_blocks"]


def test_write_maps_drop_columns_past_max_len():
    """A verify dispatch's global width can overhang max_len for
    near-cap rows; those columns are dead padding (the contiguous
    merge drops them OOB) and must map to the OOB block id instead of
    indexing past the slot's table or allocating a block beyond the
    admission-time reservation."""
    params = _params()
    eng = _engine(params, paged_blocks=8, num_slots=2)   # max_len 256
    eng._tables[0] = eng._pool.alloc(4)                  # full table
    bids, offs = eng._write_maps([(0, eng._tables[0], 250, 9)], 9, 2)
    assert (bids[0, :6] != eng._pool.num_blocks).all()   # 250..255
    assert (bids[0, 6:] == eng._pool.num_blocks).all()   # >= max_len
    assert (bids[1] == eng._pool.num_blocks).all()       # no row: OOB
    eng._pool.free(eng._tables[0])
    eng._tables[0] = []


@pytest.mark.slow
def test_paged_spec_decode_bit_identical(copy_cycle):
    _cfg, params, prompt = copy_cycle     # drafts always hit
    prompts = [prompt, prompt[3:] + prompt[:3]]
    plain = _engine(params, spec_decode=True)
    paged = _engine(params, paged_blocks=16, spec_decode=True)
    want = plain.generate(prompts, max_new_tokens=16)
    got = paged.generate(prompts, max_new_tokens=16)
    for w, g in zip(want, got):
        assert w.tokens == g.tokens
    assert paged.spec_stats()["verify_dispatches"] > 0
    st = paged.kv_pool_stats()
    assert st["free_blocks"] == st["num_blocks"]


@pytest.mark.slow
def test_paged_chunked_prefill_bit_identical():
    from copilot_for_consensus_tpu.engine.scheduler import (
        Scheduler,
        SchedulerConfig,
    )

    params = _params()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, CFG.vocab_size, size=180).tolist()
               for _ in range(3)]
    plain = _engine(params,
                    scheduler=Scheduler(SchedulerConfig(
                        chunk_tokens=64)))
    paged = _engine(params, paged_blocks=16,
                    scheduler=Scheduler(SchedulerConfig(
                        chunk_tokens=64)))
    want = plain.generate(prompts, max_new_tokens=8)
    got = paged.generate(prompts, max_new_tokens=8)
    for w, g in zip(want, got):
        assert w.tokens == g.tokens
    assert paged.chunk_dispatches > 0
    st = paged.kv_pool_stats()
    assert st["free_blocks"] == st["num_blocks"]


@pytest.mark.slow
def test_paged_chaos_replay_bit_identical_and_pool_repaired():
    """PR-7 containment over the paged layout: injected dispatch
    faults evacuate slots (owned blocks freed), the runner replays,
    survivors are bit-identical, and the allocator's books balance
    after the storm."""
    from copilot_for_consensus_tpu.engine.async_runner import (
        AsyncEngineRunner,
    )
    from copilot_for_consensus_tpu.engine.faults import (
        FaultPlan,
        FaultSpec,
    )
    from copilot_for_consensus_tpu.engine.supervisor import (
        SupervisorConfig,
    )

    params = _params()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(3, CFG.vocab_size, size=40).tolist()
               for _ in range(6)]
    base = _engine(params).generate(prompts, max_new_tokens=8)
    plan = FaultPlan(specs=[FaultSpec(kind="prefill", at=2, count=1),
                            FaultSpec(kind="decode", at=3, count=2)])
    eng = _engine(params, paged_blocks=16, faults=plan)
    runner = AsyncEngineRunner(
        eng, supervisor=SupervisorConfig(replay_budget=4)).start()
    try:
        handles = [runner.submit(list(p), 8) for p in prompts]
        outs = [h.result(timeout=120.0).tokens for h in handles]
        for w, g in zip(base, outs):
            assert w.tokens == g
        rec = runner.recovery_stats()
        assert rec["replayed"] >= 1
        assert rec["failed"] == 0
    finally:
        runner.stop()
    st = eng.kv_pool_stats()
    assert st["free_blocks"] + st["blocks_in_use"] == st["num_blocks"]
    assert st["free_blocks"] == st["num_blocks"]


def test_paged_journal_warm_restart_rebuilds_block_tables(tmp_path):
    """PR-12 journal replay over the paged layout: a process 'crash'
    mid-decode warm-restarts, continuations rebuild their block
    tables through normal admission, and the stitched outputs are
    bit-identical to the uninterrupted run."""
    params = _params()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(3, CFG.vocab_size, size=40).tolist()
               for _ in range(4)]
    base = _engine(params).generate(prompts, max_new_tokens=24)
    jp = str(tmp_path / "journal.sqlite")
    e1 = _engine(params, paged_blocks=16, journal=jp)
    for p in prompts:
        e1.submit(list(p), 24)
    e1.step()                                  # admit + first window
    del e1                                     # SIGKILL stand-in
    e2 = _engine(params, paged_blocks=16, journal=jp)
    assert e2.journal_replayed == len(prompts)
    results = {}
    for _ in range(200):
        for c in e2.step():
            results[c.request_id] = c
        if len(results) == len(prompts):
            break
    got = [results[r].tokens for r in sorted(results)]
    for w, g in zip(base, got):
        assert w.tokens == g
    # every continuation's table was rebuilt and released at retire
    st = e2.kv_pool_stats()
    assert st["free_blocks"] == st["num_blocks"]
    assert all(not t for t in e2._tables)


# ---------------------------------------------------------------------------
# supervisor: block-table audit + containment
# ---------------------------------------------------------------------------


def test_audit_repairs_block_table_overlap_and_freelist_drift():
    from copilot_for_consensus_tpu.engine.supervisor import (
        EngineSupervisor,
    )

    params = _params()
    eng = _engine(params, paged_blocks=12, num_slots=4)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(3, CFG.vocab_size, size=40).tolist()
               for _ in range(2)]
    for p in prompts:
        eng.submit(list(p), 32)
    eng.step()
    assert len(eng._active) == 2
    sup = EngineSupervisor(eng)
    assert sup.audit(repair=False) == {}        # healthy: no findings
    # corrupt: both slots claim the same owned block
    slots = sorted(eng._active)
    eng._tables[slots[1]][0] = eng._tables[slots[0]][0]
    findings = sup.audit(repair=True)
    assert set(findings["block_table_overlap"]) == set(slots)
    # both conflicted slots quarantined, allocator rebuilt: every
    # block accounted for exactly once
    assert set(sup.quarantined) == set(slots)
    assert eng._pool.free_blocks == eng._pool.num_blocks
    assert all(not t for t in eng._tables)


def test_contain_releases_paged_state_and_replays_clean():
    """contain() on a real failure: evacuate frees slot-owned blocks
    BEFORE the prefix flush frees the trie's — the pool ends fully
    free with zero pins."""
    from copilot_for_consensus_tpu.engine.supervisor import (
        EngineSupervisor,
    )

    params = _params()
    eng = _engine(params, paged_blocks=16, prefix_cache_blocks=8)
    rng = np.random.default_rng(9)
    shared = rng.integers(3, CFG.vocab_size, size=128).tolist()
    prompts = [shared + rng.integers(3, CFG.vocab_size,
                                     size=20).tolist()
               for _ in range(3)]
    eng.generate(prompts, max_new_tokens=4)    # publish the prefix
    for p in prompts:
        eng.submit(list(p), 32)
    eng.step()                                 # seeded actives (borrow)
    assert eng.kv_pool_stats()["pinned_blocks"] > 0
    sup = EngineSupervisor(eng)
    plan = sup.contain(RuntimeError("device fell over"))
    assert plan.evacuated
    st = eng.kv_pool_stats()
    assert st["free_blocks"] == st["num_blocks"]
    assert st["pinned_blocks"] == 0
    assert eng._prefix.node_count == 0


# ---------------------------------------------------------------------------
# scheduler: free-block accounting signal
# ---------------------------------------------------------------------------


def test_scheduler_sheds_on_kv_pool_pressure():
    from copilot_for_consensus_tpu.engine.scheduler import Scheduler

    s = Scheduler()
    sig = s.observe(queued=0, active=2, num_slots=4,
                    free_blocks=100, total_blocks=1000)
    assert s.overload_level == 0
    assert sig["kv_headroom_ratio"] == 0.1
    s.observe(queued=0, active=2, num_slots=4,
              free_blocks=50, total_blocks=1000)
    assert s.overload_level == 1               # under kv_low_ratio
    s.observe(queued=0, active=2, num_slots=4,
              free_blocks=10, total_blocks=1000)
    assert s.overload_level == 2               # under kv_critical_ratio
    s.observe(queued=0, active=2, num_slots=4)
    assert s.overload_level == 0               # non-paged engines: off
