# Multi-chip paged serving (ISSUE 15): the mesh-sharded block pool and
# engine dispatches, and the disaggregated prefill/decode role split.
#
# Gates, all on the 8-virtual-device CPU mesh (tests/conftest.py):
# greedy f32 SHARDED-paged output bit-identical to the single-device
# paged engine across plain / prefix-hit (zero-copy) / spec-decode /
# chunked-prefill paths; per-shard allocator locality (a slot's blocks
# never leave its dp shard); DisaggregatedEngine bit-identity with
# real block-granular KV handoffs; role-aware scheduler shedding.
# The fast (host-only) tests run in tier-1; the compile-heavy engine
# oracles are @slow and enforced by the CI multichip arm.
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.engine.kv_pool import BlockPool
from copilot_for_consensus_tpu.engine.roles import (
    DisaggregatedEngine,
    RoleConfig,
)
from copilot_for_consensus_tpu.engine.scheduler import (
    Scheduler,
    SchedulerConfig,
)
from copilot_for_consensus_tpu.models import decoder
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.parallel import MeshConfig, build_mesh

CFG = decoder_config("tiny")
PARAMS = decoder.init_params(jax.random.PRNGKey(7), CFG,
                             dtype=jnp.float32)


def _engine(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("prefill_chunk", 8)
    return GenerationEngine(CFG, kw.pop("params", PARAMS), **kw)


def _mesh():
    return build_mesh(MeshConfig(dp=2, tp=4))


PROMPTS = [[5, 9, 13], [40, 41, 42, 43, 44, 45, 46],
           [7, 8, 9, 10], [20, 21, 22], [11, 12, 13, 14, 15]]


# ---------------------------------------------------------------------------
# sharded-paged bit-identity oracles (slow: XLA compiles on the mesh)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_paged_plain_bit_identity():
    want = [c.tokens for c in _engine(kv_pool_blocks=20).generate(
        PROMPTS, max_new_tokens=6)]
    eng = _engine(mesh=_mesh(), kv_pool_blocks=24)
    got = [c.tokens for c in eng.generate(PROMPTS, max_new_tokens=6)]
    assert got == want
    assert eng.kv_pool_stats()["dp_shards"] == 2
    # every block was returned: nothing leaked across the run
    assert eng._pool.free_blocks == eng._pool.num_blocks


@pytest.mark.slow
def test_sharded_paged_prefix_hit_zero_copy_bit_identity():
    rng = np.random.default_rng(0)
    common = rng.integers(3, CFG.vocab_size, size=16).tolist()
    prompts = [common + rng.integers(3, CFG.vocab_size, size=6).tolist()
               for _ in range(4)]
    ref = _engine(kv_pool_blocks=20, prefix_cache_blocks=8)
    want = [[c.tokens for c in ref.generate(prompts, max_new_tokens=5)]
            for _ in range(2)]
    eng = _engine(mesh=_mesh(), kv_pool_blocks=32,
                  prefix_cache_blocks=8)
    got = [[c.tokens for c in eng.generate(prompts, max_new_tokens=5)]
           for _ in range(2)]
    assert got == want
    st = eng.kv_pool_stats()
    assert st["zero_copy_admits"] > 0       # pointer admissions fired
    ps = eng.prefix_stats()
    assert ps["hits"] > 0
    # the per-shard tries hold shard-local blocks only
    for shard, pc in enumerate(eng._prefixes):
        for node in pc._nodes:
            assert eng._pool.shard_of(node.block_id) == shard


@pytest.mark.slow
def test_sharded_paged_spec_decode_bit_identity(copy_cycle):
    # copy-cycle weights: prompt-lookup drafts always hit, so the
    # verify dispatch really runs sharded
    _cfg, params, prompt = copy_cycle
    kw = dict(params=params, decode_window=4, spec_decode=True,
              spec_draft_lens=(0, 2, 4))
    want = _engine(kv_pool_blocks=20, **kw).generate(
        [prompt], max_new_tokens=24)[0]
    eng = _engine(mesh=_mesh(), kv_pool_blocks=24, **kw)
    got = eng.generate([prompt], max_new_tokens=24)[0]
    assert got.tokens == want.tokens
    assert eng.spec_dispatches > 0          # the sharded verify ran
    assert eng.spec_stats()["accepted_tokens"] > 0


@pytest.mark.slow
def test_sharded_paged_chunked_prefill_bit_identity():
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, CFG.vocab_size, size=30).tolist()
               for _ in range(3)]
    sched = SchedulerConfig(chunk_tokens=8, prefill_wave_tokens=64)
    want = [c.tokens for c in _engine(
        kv_pool_blocks=20, scheduler=sched).generate(
        prompts, max_new_tokens=5)]
    eng = _engine(mesh=_mesh(), kv_pool_blocks=32, scheduler=sched)
    got = [c.tokens for c in eng.generate(prompts, max_new_tokens=5)]
    assert got == want
    assert eng.chunk_dispatches > 0         # the sharded chunk ran


@pytest.mark.slow
def test_sharded_paged_blocks_stay_in_slot_shard():
    eng = _engine(mesh=_mesh(), kv_pool_blocks=24)
    for p in PROMPTS[:4]:
        eng.submit(p, max_new_tokens=40)
    for _ in range(2):
        eng.step()
    assert eng._active, "nothing admitted"
    for slot in eng._active:
        shard = eng._slot_shard(slot)
        for bid in eng._tables[slot]:
            assert eng._pool.shard_of(bid) == shard, (slot, bid)
    # drain so the pool balance check stays meaningful
    for _ in range(40):
        if not eng._active and not eng.queue_depth:
            break
        eng.step()
    assert eng._pool.free_blocks == eng._pool.num_blocks


# ---------------------------------------------------------------------------
# disaggregated prefill/decode roles (slow: two meshes, two engines)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_disaggregated_roles_bit_identity_with_real_handoffs():
    kw = dict(num_slots=4, max_len=64, prefill_buckets=(16, 32),
              dtype=jnp.float32, attn_impl="xla", prefill_chunk=8,
              kv_pool_blocks=24)
    want = [c.tokens for c in GenerationEngine(
        CFG, PARAMS, **{**kw, "kv_pool_blocks": 20}).generate(
        PROMPTS, max_new_tokens=6)]
    dis = DisaggregatedEngine(CFG, PARAMS,
                              roles=RoleConfig(prefill_dp=2, tp=2),
                              engine_kw=kw)
    got = [c.tokens for c in dis.generate(PROMPTS, max_new_tokens=6)]
    assert got == want
    st = dis.stats()
    assert st["handoffs"] == len(PROMPTS)
    assert st["handoff_blocks"] >= len(PROMPTS)
    assert st["pending_handoffs"] == 0
    # both role pools returned every block
    assert dis.prefill._pool.free_blocks == dis.prefill._pool.num_blocks
    assert dis.decode._pool.free_blocks == dis.decode._pool.num_blocks
    # the handoff telemetry series moved on the prefill instance
    rendered = dis.prefill.telemetry.metrics.render_prometheus()
    assert "copilot_engine_role_handoff_blocks_total" in rendered
    assert "copilot_engine_role_handoff_wait_seconds" in rendered
    assert "copilot_engine_role_occupancy" in rendered


@pytest.mark.slow
def test_disaggregated_backpressure_reparks_when_decode_full():
    kw = dict(num_slots=4, max_len=64, prefill_buckets=(16, 32),
              dtype=jnp.float32, attn_impl="xla", prefill_chunk=8,
              kv_pool_blocks=24)
    # decode side gets only 2 slots: at most 2 streams decode at once,
    # the rest of the handoffs re-park until capacity frees
    dis = DisaggregatedEngine(
        CFG, PARAMS, roles=RoleConfig(prefill_dp=2, tp=2),
        engine_kw=kw, decode_kw={"num_slots": 2,
                                 "kv_pool_blocks": 20})
    comps = dis.generate(PROMPTS, max_new_tokens=6)
    assert len(comps) == len(PROMPTS)
    assert all(c.finish_reason in ("eos", "length") for c in comps)
    assert dis.handoffs == len(PROMPTS)


# ---------------------------------------------------------------------------
# fast host-only contracts (tier-1)
# ---------------------------------------------------------------------------


def test_role_requires_paged_engine():
    with pytest.raises(ValueError, match="kv_pool_blocks"):
        _engine(role="prefill")


def test_sharded_pool_requires_divisible_geometry():
    mesh = _mesh()
    with pytest.raises(ValueError, match="divisible by dp"):
        _engine(mesh=mesh, kv_pool_blocks=24, num_slots=3)
    with pytest.raises(ValueError, match="divide evenly"):
        BlockPool(CFG, num_blocks=25, block_size=8, mesh=mesh)


def test_sharded_allocator_per_shard_ranges_and_exhaustion():
    mesh = _mesh()
    pool = BlockPool(CFG, num_blocks=24, block_size=8, mesh=mesh)
    assert pool.num_shards == 2 and pool.blocks_per_shard == 12
    a = pool.alloc(3, shard=0)
    b = pool.alloc(3, shard=1)
    assert all(pool.shard_of(x) == 0 for x in a)
    assert all(pool.shard_of(x) == 1 for x in b)
    assert all(pool.local_id(x) < 12 for x in a + b)
    assert pool.free_blocks_shard(0) == 9
    # per-shard exhaustion: shard 0 running dry must not borrow from 1
    from copilot_for_consensus_tpu.engine.kv_pool import KVPoolExhausted

    with pytest.raises(KVPoolExhausted):
        pool.alloc(10, shard=0)
    assert pool.free_blocks_shard(1) == 9
    # frees route home by global id
    pool.free(a)
    assert pool.free_blocks_shard(0) == 12
    pool.free(b)
    assert pool.free_blocks == pool.num_blocks


def test_scheduler_handoff_backlog_raises_shed_levels():
    cfg = SchedulerConfig(handoff_shed_depth=8)
    s = Scheduler(cfg)
    sig = s.observe(queued=0, active=0, num_slots=4,
                    handoff_backlog=2)
    assert s.overload_level == 0
    assert sig["handoff_backlog"] == 2
    s.observe(queued=0, active=0, num_slots=4, handoff_backlog=8)
    assert s.overload_level == 1           # batch lane sheds
    s.observe(queued=0, active=0, num_slots=4, handoff_backlog=16)
    assert s.overload_level == 2           # everything sheds
    s.observe(queued=0, active=0, num_slots=4, handoff_backlog=0)
    assert s.overload_level == 0           # decode caught up


def test_role_config_resolve():
    rc = RoleConfig(prefill_dp=2, tp=2).resolve(8)
    assert (rc.prefill_dp, rc.decode_dp, rc.tp) == (2, 2, 2)
    with pytest.raises(ValueError, match="nothing left"):
        RoleConfig(prefill_dp=4, tp=2).resolve(8)
    with pytest.raises(ValueError, match="do not divide"):
        RoleConfig(prefill_dp=1, tp=3).resolve(8)


def test_handoff_deadline_and_backpressure_plumbing():
    """Code-review regressions: a handed-off deadline must arm the
    decode engine's expiry sweep (submit() never runs on that path),
    and the prefill hold threshold must be REACHABLE (parked handoffs
    are slot-keyed, so the old 2x-slots default could never fire)."""
    pre = _engine(kv_pool_blocks=20, role="prefill")
    assert pre._handoff_high == pre.num_slots // 2
    dec = _engine(kv_pool_blocks=20, role="decode")
    pre.submit([5, 9, 13], max_new_tokens=8, deadline_s=60.0)
    handoffs = []
    for _ in range(10):
        pre.step()
        handoffs = pre.take_prefilled()
        if handoffs:
            break
    assert len(handoffs) == 1
    assert not dec._deadlines_in_use
    rid = dec.admit_prefilled(handoffs[0])
    assert rid is not None
    assert dec._deadlines_in_use     # the expiry sweep is armed
    # the external-backlog report feeds the release hold's comparison
    pre.set_handoff_external(7)
    assert pre._handoff_external == 7
    pre.set_handoff_external(-3)
    assert pre._handoff_external == 0
