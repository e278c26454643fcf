# The Pallas paged kernel as the production decode route (ISSUE 16):
# interpret-mode parity of the partial kernel + combine_partials fold
# against the XLA reference across GQA ratios, sliding windows, fp8
# pools, mixed fill levels, and parked rows; the kv_kernel constructor
# guards; the no-materialization gate (now an hlo-materialize contract
# on the lowered StableHLO of every kernel-route paged dispatch — this
# file keeps the tripwire proving the hlo lane turns red when the
# materializing gather is re-introduced); and engine-level greedy token
# equality between the kernel and reference routes across the plain,
# prefix-cache, spec-decode, and chunked-prefill paths.
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from copilot_for_consensus_tpu.engine.kv_pool import BLOCK_TABLE_DTYPE
from copilot_for_consensus_tpu.models.configs import decoder_config

CFG = decoder_config("tiny")


def _params():
    from copilot_for_consensus_tpu.models import decoder

    return decoder.init_params(jax.random.PRNGKey(7), CFG,
                               dtype=jnp.float32)


def _engine(params, route, **kw):
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
    kw.setdefault("kv_pool_blocks", 12)
    return GenerationEngine(CFG, params, kv_kernel=route, **kw)


# ---------------------------------------------------------------------------
# partial kernel: interpret-mode parity against the XLA reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4), (8, 1)])
@pytest.mark.parametrize("fp8", [False, True])
@pytest.mark.parametrize("window", [0, 5])
def test_partial_kernel_decode_parity(hq, hkv, fp8, window):
    """The kernel route's decode shape: the pool partial alone IS the
    whole kv prefix, so combine_partials of one piece must match the
    gathered reference — across GQA ratios, sliding window, fp8
    dequant-on-load, mixed fill levels, and a parked (length-0) row
    that must emit exact zeros."""
    from copilot_for_consensus_tpu.ops.attention import (
        combine_partials,
        decode_attention,
    )
    from copilot_for_consensus_tpu.ops.paged_attention import (
        paged_attention_partial_pallas,
        paged_gather_layer,
    )

    rng = np.random.default_rng(2)
    b, d, blk, nbtot, nb, nl, li = 4, 16, 8, 12, 4, 3, 2
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((nl, nbtot, hkv, blk, d)),
                     jnp.float32)
    pv = jnp.asarray(rng.standard_normal((nl, nbtot, hkv, blk, d)),
                     jnp.float32)
    if fp8:
        pk = pk.astype(jnp.float8_e4m3fn)
        pv = pv.astype(jnp.float8_e4m3fn)
    tables = jnp.asarray(rng.integers(0, nbtot, (b, nb)),
                         BLOCK_TABLE_DTYPE)
    # parked row, single token, full table, mid-block fill
    lengths = jnp.asarray([0, 1, blk * nb, 17], jnp.int32)

    k, v = paged_gather_layer(pk[li], pv[li], tables)
    ref = decode_attention(q, k, v, lengths, window=window)
    part = paged_attention_partial_pallas(
        q.reshape(b, hkv, hq // hkv, d), pk, pv,
        jnp.asarray([li], jnp.int32), tables, lengths, lengths - 1,
        window=window, interpret=True)
    got = combine_partials([part], jnp.float32).reshape(b, hq, d)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               atol=1e-5)
    assert bool(jnp.all(got[0] == 0.0))        # parked row: exact zeros


def test_partial_kernel_seeded_rows_parity():
    """The seeded shape (R = group * S query rows): pool partial from
    the kernel + the XLA causal-suffix partial folded by
    combine_partials must match a dense joint softmax over
    [pool prefix | causal suffix] — including a zero-prefix row whose
    pool piece is fully masked."""
    from copilot_for_consensus_tpu.ops.attention import (
        causal_suffix_partial,
        combine_partials,
    )
    from copilot_for_consensus_tpu.ops.paged_attention import (
        paged_attention_partial_pallas,
        paged_gather_layer,
    )

    rng = np.random.default_rng(3)
    b, hkv, g, d, blk, nbtot, nb, s = 2, 2, 2, 16, 8, 10, 3, 4
    hq = hkv * g
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((2, nbtot, hkv, blk, d)),
                     jnp.float32)
    pv = jnp.asarray(rng.standard_normal((2, nbtot, hkv, blk, d)),
                     jnp.float32)
    ks = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, nbtot, (b, nb)),
                         BLOCK_TABLE_DTYPE)
    pref = jnp.asarray([13, 0], jnp.int32)     # mid-block + no prefix

    qr = q.reshape(b, hkv, g, s, d).reshape(b, hkv, g * s, d)
    pool_part = paged_attention_partial_pallas(
        qr, pk, pv, jnp.asarray([1], jnp.int32), tables, pref,
        pref - 1, window=0, interpret=True)
    suf_part = causal_suffix_partial(q, ks, vs)
    got = combine_partials([pool_part, suf_part], jnp.float32)

    # dense reference: joint softmax over pool positions < pref[b] and
    # suffix positions t <= s (row-major (g, s) rows, like the kernel)
    kp, vp = paged_gather_layer(pk[1], pv[1], tables)   # [b,hkv,P,d]
    qg = q.reshape(b, hkv, g, s, d)
    lp = jnp.einsum("bhgsd,bhpd->bhgsp", qg, kp) * (d ** -0.5)
    lp = jnp.where(jnp.arange(nb * blk)[None, None, None, None]
                   < pref[:, None, None, None, None], lp, -jnp.inf)
    ls = jnp.einsum("bhgsd,bhtd->bhgst", qg, ks) * (d ** -0.5)
    ls = jnp.where(jnp.arange(s)[None, None, None, None]
                   <= jnp.arange(s)[None, None, None, :, None],
                   ls, -jnp.inf)
    probs = jax.nn.softmax(jnp.concatenate([lp, ls], axis=-1), axis=-1)
    ref = jnp.einsum("bhgsp,bhpd->bhgsd", probs,
                     jnp.concatenate([vp, vs], axis=-2))
    np.testing.assert_allclose(
        np.asarray(ref.reshape(b, hkv, g * s, d)), np.asarray(got),
        atol=1e-5)


# ---------------------------------------------------------------------------
# engine construction: the kv_kernel knob's guards and resolution
# ---------------------------------------------------------------------------


def test_kv_kernel_constructor_guards_and_resolution():
    params = _params()
    with pytest.raises(ValueError, match="kv_kernel"):
        _engine(params, "cuda")
    with pytest.raises(ValueError, match="paged"):
        _engine(params, "pallas", kv_pool_blocks=0)
    # contiguous engine: no paged dispatches, no route
    assert _engine(params, "auto", kv_pool_blocks=0)._kv_route == ""
    # pinned routes resolve as pinned; auto picks the reference route
    # on CPU (this suite's backend — the kernel would only interpret)
    assert _engine(params, "pallas")._kv_route == "kernel"
    assert _engine(params, "reference")._kv_route == "reference"
    assert _engine(params, "auto")._kv_route == "reference"


# ---------------------------------------------------------------------------
# no-materialization gate: the kernel route must never gather the pool.
# The PROD gate is the hlo lane now — the kernel-route contract cases in
# generation.py declare ``HloSpec(forbid_ops=...)`` and hlocheck scans
# the real lowered StableHLO of every paged dispatch (strictly stronger
# than the runtime trace spy this file used to carry: a gather inlined
# WITHOUT calling paged_gather_kv is invisible to a spy, but not to the
# lowering). What stays here is the tripwire proving the lane turns red
# when the materializing gather is re-introduced.
# ---------------------------------------------------------------------------


def test_reintroduced_pool_gather_turns_the_hlo_lane_red(tmp_path):
    """Re-introduce a ``paged_gather_kv`` of the whole committed pool
    working set into ``_decode_paged_kernel``'s body (the exact shape
    of the pre-ISSUE-16 reference route) on a COPY of generation.py:
    hlocheck's hlo-materialize rule must flag the lowered gather. The
    unmutated file is the negative control — same case, same rule,
    clean."""
    from copilot_for_consensus_tpu.analysis import hlocheck
    from copilot_for_consensus_tpu.engine import generation

    gen = pathlib.Path(generation.__file__)
    src = gen.read_text()
    # anchor 1: the decode variant's partial_fn (the seeded/verify/
    # chunk variants bind `lns`, so this needle is unique to decode)
    anchor = "                    def partial_fn(li, q_rows, lengths, q_pos):\n"
    assert src.count(anchor) == 1, "decode body moved; update the test"
    gather = ("                    mk_ws, mv_ws = paged_gather_kv("
              "pool_k, pool_v, tables)\n")
    # anchor 2: decode's pool scatter (unique: only decode scatters
    # k_all). The gathered working set must be USED — a dead gather is
    # DCE'd before lowering and would never reach the StableHLO.
    scatter = ("                    pool_k, pool_v = scatter(\n"
               "                        pool_k, pool_v, k_all, v_all, "
               "sbids, soffs)")
    assert src.count(scatter) == 1, "decode scatter moved; update the test"
    use = ("                    k_all = k_all + 0.0 * mk_ws"
           "[:, :, :, :k_all.shape[3], :].astype(k_all.dtype)\n")
    mutated = tmp_path / "generation_gather_mutated.py"
    mutated.write_text(src.replace(anchor, gather + anchor, 1)
                       .replace(scatter, use + scatter, 1))
    findings, _, skips = hlocheck.check_modules(
        [str(mutated)], labels={"decode-paged-kernel"},
        only_rules={"hlo-materialize"})
    assert skips == [], skips
    assert any(f.rule == "hlo-materialize"
               and "decode-paged-kernel" in f.context
               for f in findings), [f.render() for f in findings]
    clean, _, _ = hlocheck.check_modules(
        [str(gen)], labels={"decode-paged-kernel"},
        only_rules={"hlo-materialize"})
    assert clean == [], [f.render() for f in clean]


# ---------------------------------------------------------------------------
# engine e2e: greedy f32 CPU token equality, kernel vs reference route
# ---------------------------------------------------------------------------


def test_kernel_route_plain_decode_tokens_match_reference():
    params = _params()
    ref = _engine(params, "reference")
    ker = _engine(params, "pallas")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, CFG.vocab_size, size=70).tolist()
               for _ in range(6)]
    want = ref.generate(prompts, max_new_tokens=10)
    got = ker.generate(prompts, max_new_tokens=10)
    for w, g in zip(want, got):
        assert w.tokens == g.tokens
        assert w.finish_reason == g.finish_reason
    st = ker.kv_pool_stats()
    assert st["free_blocks"] == st["num_blocks"]   # books still balance


def test_kernel_route_prefix_zero_copy_tokens_match_reference():
    """Seeded admission through the kernel's R > 1 rows: zero-copy
    prefix hits produce the same greedy streams as the reference
    route's gather-and-run seeded program."""
    params = _params()
    ref = _engine(params, "reference", kv_pool_blocks=16,
                  prefix_cache_blocks=8)
    ker = _engine(params, "pallas", kv_pool_blocks=16,
                  prefix_cache_blocks=8)
    rng = np.random.default_rng(1)
    shared = rng.integers(3, CFG.vocab_size, size=128).tolist()
    prompts = [shared + rng.integers(3, CFG.vocab_size,
                                     size=30).tolist()
               for _ in range(6)]
    for _round in range(2):
        want = ref.generate(prompts, max_new_tokens=6)
        got = ker.generate(prompts, max_new_tokens=6)
        for w, g in zip(want, got):
            assert w.tokens == g.tokens
    assert ker.kv_pool_stats()["zero_copy_admits"] > 0


@pytest.mark.slow
def test_kernel_route_spec_decode_tokens_match_reference(copy_cycle):
    _cfg, params, prompt = copy_cycle     # drafts always hit
    prompts = [prompt, prompt[3:] + prompt[:3]]
    ref = _engine(params, "reference", kv_pool_blocks=16,
                  spec_decode=True)
    ker = _engine(params, "pallas", kv_pool_blocks=16,
                  spec_decode=True)
    want = ref.generate(prompts, max_new_tokens=16)
    got = ker.generate(prompts, max_new_tokens=16)
    for w, g in zip(want, got):
        assert w.tokens == g.tokens
    assert ker.spec_stats()["verify_dispatches"] > 0


@pytest.mark.slow
def test_kernel_route_chunked_prefill_tokens_match_reference():
    from copilot_for_consensus_tpu.engine.scheduler import (
        Scheduler,
        SchedulerConfig,
    )

    params = _params()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, CFG.vocab_size, size=180).tolist()
               for _ in range(3)]
    ref = _engine(params, "reference", kv_pool_blocks=16,
                  scheduler=Scheduler(SchedulerConfig(chunk_tokens=64)))
    ker = _engine(params, "pallas", kv_pool_blocks=16,
                  scheduler=Scheduler(SchedulerConfig(chunk_tokens=64)))
    want = ref.generate(prompts, max_new_tokens=8)
    got = ker.generate(prompts, max_new_tokens=8)
    for w, g in zip(want, got):
        assert w.tokens == g.tokens
    assert ker.chunk_dispatches > 0
