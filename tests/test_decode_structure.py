# The decode program's handling of the slot cache (engine/generation.py
# `_decode`, models/decoder.py `cache_prefix` / `merge_window`): the
# prefix is cut once per dispatch, fresh KV goes in as one slab per slot,
# and the compiled program holds no cache-sized copy. Three kinds of
# check: the merge against the per-column scatter it replaced, served
# tokens pinned from the commit before the change, and the structure of
# the traced and of the compiled program (the latter for a described
# v5e, no chip needed), each shown to trip on the old formulation.
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.models import decoder, xing
from copilot_for_consensus_tpu.models.configs import (
    DecoderConfig,
    decoder_config,
)
from copilot_for_consensus_tpu.ops import (
    grouped_matmul,
    latent_prefill_attention,
)


def merge_window_by_column(cache, k_win, v_win, positions0, steps):
    """The reference: `merge_window` as it was through PR 26, a scatter
    with one index per (slot, column); out-of-range columns drop."""
    b = k_win.shape[1]
    w = k_win.shape[3]
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, w))
    pidx = positions0[:, None] + jnp.arange(w)[None, :]
    if steps < w:
        k_win = k_win[:, :, :, :steps]
        v_win = v_win[:, :, :, :steps]
        bidx, pidx = bidx[:, :steps], pidx[:, :steps]
    k_upd = k_win.transpose(1, 3, 0, 2, 4)     # [B, W, L, H, D]
    v_upd = v_win.transpose(1, 3, 0, 2, 4)
    k = cache["k"].at[:, bidx, :, pidx, :].set(
        k_upd.astype(cache["k"].dtype), mode="drop")
    v = cache["v"].at[:, bidx, :, pidx, :].set(
        v_upd.astype(cache["v"].dtype), mode="drop")
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# merge_window == the per-column scatter
# ---------------------------------------------------------------------------

S_MAX = 48


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "fp8"])
@pytest.mark.parametrize("slots", [4, 64])
@pytest.mark.parametrize("w,steps", [(8, 8), (8, 5), (24, 24), (5, 5)],
                         ids=["window8", "steps5of8", "chunk24",
                              "verify5"])
def test_merge_window_equals_column_scatter(kv_dtype, slots, w, steps):
    n_l, h, d = 2, 2, 8
    rng = np.random.default_rng(slots * 100 + w)

    def rand(shape, dtype):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    cache = {n: rand((n_l, slots, h, S_MAX, d), kv_dtype) for n in "kv"}
    k_win = rand((n_l, slots, h, w, d), jnp.bfloat16)
    v_win = rand((n_l, slots, h, w, d), jnp.bfloat16)
    pos = rng.integers(0, S_MAX - w, size=slots)       # in range
    pos[0] = S_MAX                  # parked: a free or prefilling slot
    pos[1] = S_MAX - 3              # live, within w of the extent
    pos[2] = S_MAX - w              # the last slab that fits whole
    pos[3] = 0
    pos = jnp.asarray(pos, jnp.int32)
    want = jax.jit(merge_window_by_column, static_argnames="steps")(
        cache, k_win, v_win, pos, steps=steps)
    got = jax.jit(decoder.merge_window, static_argnames="steps")(
        cache, k_win, v_win, pos, steps=steps)
    for n in "kv":
        assert got[n].dtype == kv_dtype
        np.testing.assert_array_equal(
            np.asarray(got[n].astype(jnp.float32)),
            np.asarray(want[n].astype(jnp.float32)))
    # and something was written: slot 3's first column is the window's
    assert np.array_equal(
        np.asarray(got["k"][:, 3, :, 0].astype(jnp.float32)),
        np.asarray(k_win[:, 3, :, 0].astype(kv_dtype).astype(jnp.float32)))


def test_merge_window_wider_than_the_cache_keeps_what_fits():
    """A view narrower than the window (the paged reference route hands
    `_prefill_chunk` such views): columns past the extent drop."""
    n_l, slots, h, d, s_max, w = 1, 4, 1, 4, 6, 8
    cache = {n: jnp.zeros((n_l, slots, h, s_max, d), jnp.float32)
             for n in "kv"}
    win = jnp.arange(1, w + 1, dtype=jnp.float32)[None, None, None, :, None] \
        * jnp.ones((n_l, slots, h, w, d), jnp.float32)
    pos = jnp.asarray([0, 2, 6, 5], jnp.int32)
    want = merge_window_by_column(cache, win, win, pos, w)
    got = decoder.merge_window(cache, win, win, pos, w)
    np.testing.assert_array_equal(np.asarray(got["k"]),
                                  np.asarray(want["k"]))
    assert np.asarray(got["v"][0, 1, 0, :, 0]).tolist() == [0, 0, 1, 2, 3, 4]


# ---------------------------------------------------------------------------
# served tokens, pinned from the parent commit
# ---------------------------------------------------------------------------

# Greedy tokens of the engine below at commit 7d9e1fb (PR 26), where the
# prefix was cut inside the token scan and the merge was the per-column
# scatter. Four decode dispatches of 8 steps; the longer request's cache
# prefix passes 128 after the second, so the program's kv_len goes
# 128, 128, 256, 256. The smallest gap between the best and the second
# logit along both paths is 0.009 (naive f32 forward), far above what a
# different CPU's rounding moves.
PINNED = [
    [9, 477, 209, 430, 450, 291, 56, 465, 9, 477, 209, 103, 353, 65, 407,
     398, 291, 257, 291, 257, 9, 136, 108, 9, 477, 371, 313, 494, 53, 477],
    [113, 123, 303, 412, 9, 477, 97, 110, 123, 478, 9, 414, 325, 477, 123,
     60, 97, 65, 477, 293, 291, 497, 65, 477, 313, 65, 477, 65, 477, 65],
]


def test_greedy_tokens_across_a_kv_len_boundary_match_the_parent():
    cfg = decoder_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(11), cfg,
                                 dtype=jnp.float32)
    eng = GenerationEngine(cfg, params, num_slots=4, max_len=512,
                           prefill_buckets=(64, 128), dtype=jnp.float32,
                           attn_impl="xla", eos_id=-1, decode_window=8)
    kv_lens = []
    decode_fn = eng._decode_fn

    def spy(*args, **kw):
        kv_lens.append(kw["kv_len"])
        return decode_fn(*args, **kw)

    eng._decode_fn = spy
    prompts = [[3 + (i * 7) % 50 for i in range(118)],
               [5 + (i * 3) % 40 for i in range(41)]]
    comps = eng.generate(prompts, max_new_tokens=30)
    assert kv_lens == [128, 128, 256, 256]
    assert [c.tokens for c in comps] == PINNED


# ---------------------------------------------------------------------------
# structure of the decode program
# ---------------------------------------------------------------------------

# large enough that the chip's compiler treats the cache as it does the
# served one (a half under ~20 MB is prefetched whole into fast memory,
# which reads as a cache-sized copy): 2 x 8 x 2 x 4096 x 128 bf16 = 33.5 MB
STRUCT_CFG = DecoderConfig(name="structure", vocab_size=512, d_model=256,
                           n_layers=2, n_heads=2, n_kv_heads=2, d_ff=512,
                           max_seq_len=8192)
SLOTS, MAX_LEN, KV_LEN = 8, 4096, 512


@pytest.fixture(scope="module")
def struct_engine():
    return GenerationEngine(STRUCT_CFG, num_slots=SLOTS, max_len=MAX_LEN,
                            prefill_buckets=(64,), dtype=jnp.bfloat16,
                            attn_impl="xla", eos_id=-1)


@pytest.fixture
def old_program(monkeypatch):
    """The decode program as it was: the prefix cut inside the step
    function, which the token scan calls, and the per-column merge."""
    step, cut = decoder.decode_step_windowed, decoder.cache_prefix

    def cutting_step(params, tok, pos, w, cfg, cache, k_win, v_win, **kw):
        return step(params, tok, pos, w, cfg, cut(cache, KV_LEN), k_win,
                    v_win, **kw)

    monkeypatch.setattr(decoder, "decode_step_windowed", cutting_step)
    monkeypatch.setattr(decoder, "cache_prefix",
                        lambda cache, kv_len: cache)
    monkeypatch.setattr(decoder, "merge_window", merge_window_by_column)
    # the engine's jit has the new program's trace cached, under the
    # function it wraps: trace through a function of this test's own,
    # which also leaves nothing of the old program cached behind
    def rejit(eng):
        def _decode(*args, **kw):
            return eng._decode_fn.__wrapped__(*args, **kw)

        return jax.jit(_decode, donate_argnums=(3,),
                       static_argnames=("kv_len", "n_windows"))

    return rejit


def _decode_args(eng, wrap):
    i32 = wrap(jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    return (jax.tree.map(wrap, eng.params), i32, i32,
            jax.tree.map(wrap, eng._cache), wrap(jax.random.PRNGKey(0)))


def _is_cache_like(shape) -> bool:
    """A cache half, or its prefix: [L, B, Hkv, >= kv_len, Dh]."""
    cfg = STRUCT_CFG
    return (len(shape) == 5
            and tuple(shape[:3]) == (cfg.n_layers, SLOTS, cfg.n_kv_heads)
            and shape[3] >= KV_LEN and shape[4] == cfg.head_dim)


def _sub_jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def cache_traffic_faults(jaxpr, in_loop=False):
    """What the traced decode program may not do with a cache-sized
    array: read a part of it inside a loop (a copy per iteration that
    XLA does not hoist), or scatter into it by column (the sequence axis
    must be inside the update window, or XLA:TPU relayouts the operand
    for the scatter and back)."""
    faults = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        operand = eqn.invars[0].aval.shape if eqn.invars else ()
        if (in_loop and name in ("slice", "dynamic_slice", "gather")
                and _is_cache_like(operand)):
            faults.append(f"{name} of {operand} inside a loop body")
        if name.startswith("scatter") and _is_cache_like(operand):
            dn = eqn.params["dimension_numbers"]
            if 3 in dn.inserted_window_dims + dn.operand_batching_dims:
                faults.append(f"{name} into {operand} by column")
        loops = in_loop or name in ("scan", "while")
        for sub in _sub_jaxprs(eqn.params):
            faults += cache_traffic_faults(sub, loops)
    return faults


def _traced(eng, decode_fn=None):
    fn = functools.partial(decode_fn or eng._decode_fn, kv_len=KV_LEN,
                           n_windows=1)
    return jax.make_jaxpr(fn)(*_decode_args(eng, lambda a: a)).jaxpr


def test_traced_decode_keeps_the_cache_out_of_the_token_loop(struct_engine):
    assert cache_traffic_faults(_traced(struct_engine)) == []


def test_traced_guard_trips_on_the_old_program(struct_engine, old_program):
    faults = cache_traffic_faults(
        _traced(struct_engine, old_program(struct_engine)))
    assert sum("slice" in f and "inside a loop" in f for f in faults) == 2
    assert sum("by column" in f for f in faults) == 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compiled_cache_ops(eng, sharding, decode_fn=None):
    """Every op of the decode program compiled for the chip whose result
    is a cache half or a prefix of one, as (opcode, is it inside a while
    loop, is it the whole extent)."""
    def wrap(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    text = (decode_fn or eng._decode_fn).lower(
        *_decode_args(eng, wrap), kv_len=KV_LEN,
        n_windows=1).compile().as_text()
    calls, found, comp = {}, [], None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
        calls.setdefault(comp, set()).update(re.findall(
            r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line))
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and m.group(2) not in ("parameter", "get-tuple-element",
                                    "bitcast"):
            shape = tuple(int(x) for x in m.group(1).split(","))
            if _is_cache_like(shape):
                found.append((m.group(2), comp, shape[3] == MAX_LEN))
    # what runs inside some while loop: the bodies, and whatever they call
    looped = frontier = set(re.findall(r"body=%?([\w.\-]+)", text))
    while frontier:
        frontier = {c for f in frontier for c in calls.get(f, ())} - looped
        looped = looped | frontier
    return [(op, comp in looped, whole) for op, comp, whole in found]


def test_compiled_decode_holds_no_cache_sized_copy(struct_engine, one_chip):
    ops = compiled_cache_ops(struct_engine, one_chip)
    # the prefix: one slice a half, once per dispatch
    assert sorted(o for o in ops if not o[2]) == [("slice", False, False)] * 2
    # the cache itself: only updated where it lies (the expanded scatter)
    assert {o[0] for o in ops if o[2]} == {"dynamic-update-slice"}


def test_compiled_guard_trips_on_the_old_program(struct_engine, one_chip,
                                                 old_program):
    ops = compiled_cache_ops(struct_engine, one_chip,
                             old_program(struct_engine))
    assert ("slice", True, False) in ops        # re-cut every token
    assert sum(o == ("copy", False, True) for o in ops) == 4


# ---------------------------------------------------------------------------
# the same for attention="eva" (models/eva.py): its window buffers are
# the cache-sized arrays, and `merge_dispatch` writes them by the scatter
# `merge_window` uses. Here, and not in tests/test_eva_engine.py: one
# file describes the chip (a second could land on another worker and
# find the TPU library taken).
# ---------------------------------------------------------------------------

EVA_CFG = decoder_config("tiny-eva", d_model=256, n_heads=2, n_kv_heads=2,
                         d_ff=512, window_size=4096, chunk_size=16,
                         max_seq_len=8192)
EVA_MAX = 8192     # a window half: 2 x 8 x 2 x 4104 x 128 bf16 = 33.6 MB


@pytest.mark.parametrize("may_close", [False, True],
                         ids=["plain", "may-close"])
def test_compiled_eva_decode_updates_the_window_buffers_in_place(
        one_chip, may_close):
    """The only ops whose result has a window buffer's shape are the
    in-place updates the merge's scatter expands to: no copy and no
    slice, inside a loop or out of it, in either decode program."""
    eng = GenerationEngine(EVA_CFG, num_slots=SLOTS, max_len=EVA_MAX,
                           prefill_buckets=(64,), dtype=jnp.bfloat16,
                           attn_impl="xla", eos_id=-1)

    def wrap(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    i32 = wrap(jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    text = eng._decode_eva_fn.lower(
        jax.tree.map(wrap, eng.params), i32, i32,
        jax.tree.map(wrap, eng._cache), wrap(jax.random.PRNGKey(0)),
        may_close=may_close).compile().as_text()
    window = tuple(eng._cache["k"].shape)
    ops = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\](\S*) "
                     r"([\w\-]+)\(", line)
        # (a result in memory space S(1) is a prefetch of a buffer this
        # small into fast memory, not a relayout; the served buffers
        # are 2 GB a half and are never moved there)
        if m and m.group(3) not in ("parameter", "get-tuple-element",
                                    "bitcast") \
                and tuple(int(x) for x in m.group(1).split(",")) == window \
                and "S(1)" not in m.group(2):
            ops.add(m.group(3))
    assert ops == {"dynamic-update-slice"}


# ---------------------------------------------------------------------------
# and on a TPU, where decode attention reads the cache through the
# kernel that walks a slot's live blocks (ops/eva_attention.py): the
# layer scan closes over the cache, the kernel takes the four halves
# whole, by pointer, and nothing else under `attn` touches them. The
# trace sees a TPU here (`on_tpu`), as it does on the chip.
# ---------------------------------------------------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def eva_decode_faults(one_chip, may_close):
    """What the compiled EVA decode program may not hold: no kernel (or
    more than the one); an op, other than the compaction's reads of a
    closing window, whose result is one layer of a window or summary
    half; an op that produces a whole half other than the merge's
    in-place update (a copy, a relayout); an op under `attn` other than
    the kernel that takes a whole half as an operand (a fusion that
    reads all of it and masks)."""
    eng = GenerationEngine(EVA_CFG, num_slots=SLOTS, max_len=EVA_MAX,
                           prefill_buckets=(64,), dtype=jnp.bfloat16,
                           attn_impl="xla", eos_id=-1)

    def wrap(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    i32 = wrap(jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    text = eng._decode_eva_fn.lower(
        jax.tree.map(wrap, eng.params), i32, i32,
        jax.tree.map(wrap, eng._cache), wrap(jax.random.PRNGKey(0)),
        may_close=may_close).compile().as_text()
    halves = {tuple(eng._cache[n].shape) for n in ("k", "ks")}
    one_layer = {s[1:] for s in halves} | {(1,) + s[1:] for s in halves}
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    faults = []
    if len(kernels) != 1 or not re.search(
            r'op_name="[^"]*/attn/eva_decode_attention/', kernels[0]):
        faults.append(f"{len(kernels)} kernels")
    shape_of = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\](\S*) "
                     r"([\w\-]+)\(([^)]*)", line)
        if not m:
            continue
        name, dims, layout, op, operands = m.groups()
        shape = shape_of[name] = tuple(int(x) for x in dims.split(","))
        if op in ("parameter", "get-tuple-element", "bitcast"):
            continue
        if shape in one_layer and "/kv_compact/" not in line:
            faults.append(f"{op} makes one layer of a half {shape}")
        # (S(1): a prefetch of a buffer this small into fast memory)
        if shape in halves and op != "dynamic-update-slice" \
                and "S(1)" not in layout:
            faults.append(f"{op} makes a whole half {shape}")
        if "/attn/" in line and any(
                shape_of.get(o) in halves
                for o in re.findall(r"%([\w.\-]+)", operands)):
            faults.append(f"{op} under attn reads a whole half")
    return faults


@pytest.mark.parametrize("may_close", [False, True],
                         ids=["plain", "may-close"])
def test_compiled_eva_decode_reads_the_cache_through_the_kernel_alone(
        one_chip, on_tpu, may_close):
    assert eva_decode_faults(one_chip, may_close) == []


@pytest.mark.parametrize("may_close", [False, True],
                         ids=["plain", "may-close"])
def test_eva_guard_trips_on_joint_attention_over_whole_pieces(
        one_chip, on_tpu, monkeypatch, may_close):
    from copilot_for_consensus_tpu.models import eva

    monkeypatch.setattr(eva, "_reads_live_blocks", lambda: False)
    faults = eva_decode_faults(one_chip, may_close)
    assert "0 kernels" in faults
    assert sum("under attn reads a whole half" in f for f in faults) >= 2


# ---------------------------------------------------------------------------
# the dense cache on a TPU (ops/dense_attention.py): `_decode` takes no
# cut of the cache; the layer scan closes over the two halves and the
# kernel reads each slot's live blocks in place. Compiled for the
# described v5e with the trace seeing a TPU (`on_tpu`), and served here
# through the interpreter with the route forced (`kernel_route`).
# ---------------------------------------------------------------------------


def dense_decode_faults(one_chip):
    """What the compiled dense decode program may not hold on the
    kernel's route: no kernel in the layer loop (or more than the one);
    an op whose result is a cache half or a prefix of one other than the
    merge's in-place update (a slice, a copy, a relayout); an op that
    makes one layer of a half; an op under `attn` other than the kernel
    that takes a whole half as an operand (a fusion that reads all of
    it and masks)."""
    eng = GenerationEngine(STRUCT_CFG, num_slots=SLOTS, max_len=MAX_LEN,
                           prefill_buckets=(64,), dtype=jnp.bfloat16,
                           attn_impl="xla", eos_id=-1)

    def wrap(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    kv_len = MAX_LEN if eng._reads_live_blocks() else KV_LEN
    text = eng._decode_fn.lower(*_decode_args(eng, wrap), kv_len=kv_len,
                                n_windows=1).compile().as_text()
    half = tuple(eng._cache["k"].shape)
    one_layer = {half[1:], (1,) + half[1:]}
    comp, kernels, faults, shape_of, calls = None, [], [], {}, {}
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
        calls.setdefault(comp, set()).update(re.findall(
            r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line))
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels.append((comp, line))
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\](\S*) "
                     r"([\w\-]+)\(([^)]*)", line)
        if not m:
            continue
        name, dims, layout, op, operands = m.groups()
        shape = shape_of[name] = tuple(int(x) for x in dims.split(","))
        if op in ("parameter", "get-tuple-element", "bitcast"):
            continue
        if shape in one_layer:
            faults.append(f"{op} makes one layer of a half {shape}")
        if _is_cache_like(shape) and op != "dynamic-update-slice":
            faults.append(f"{op} makes a half or a prefix of one {shape}")
        if "/attn/" in line and "tpu_custom_call" not in line and any(
                shape_of.get(o) == half
                for o in re.findall(r"%([\w.\-]+)", operands)):
            faults.append(f"{op} under attn reads a whole half")
    looped = frontier = set(re.findall(r"body=%?([\w.\-]+)", text))
    while frontier:
        frontier = {c for f in frontier for c in calls.get(f, ())} - looped
        looped = looped | frontier
    if len(kernels) != 1 or kernels[0][0] not in looped or not re.search(
            r'op_name="[^"]*/attn/dense_decode_attention/', kernels[0][1]):
        faults.append(f"{len(kernels)} kernels")
    return faults


def test_compiled_dense_decode_reads_the_cache_through_the_kernel_alone(
        one_chip, on_tpu):
    assert dense_decode_faults(one_chip) == []


def test_dense_guard_trips_on_the_xla_prefix_route(one_chip, on_tpu,
                                                   monkeypatch):
    from copilot_for_consensus_tpu.ops import dense_attention

    monkeypatch.setattr(dense_attention, "serves", lambda extent: False)
    faults = dense_decode_faults(one_chip)
    assert "0 kernels" in faults
    assert sum(f.startswith("slice makes a half or a prefix")
               for f in faults) == 2


@pytest.fixture
def kernel_route(monkeypatch):
    """The kernel's route on this backend: through the interpreter."""
    from copilot_for_consensus_tpu.ops import dense_attention

    monkeypatch.setattr(dense_attention, "serves", lambda extent: True)


def _pinned_engine(**kw):
    cfg = decoder_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(11), cfg,
                                 dtype=jnp.float32)
    eng = GenerationEngine(cfg, params, num_slots=4, max_len=512,
                           prefill_buckets=(64, 128), dtype=jnp.float32,
                           attn_impl="xla", eos_id=-1, decode_window=8,
                           **kw)
    prompts = [[3 + (i * 7) % 50 for i in range(118)],
               [5 + (i * 3) % 40 for i in range(41)]]
    comps = eng.generate(prompts, max_new_tokens=30)
    decodes = [r for r in eng.telemetry.recorder.records()
               if r.kind == "decode"]
    return eng, [c.tokens for c in comps], decodes


def test_the_kernel_route_serves_the_pinned_tokens_with_one_decode_program(
        kernel_route):
    """The engine of the pinned test above, on the kernel's route: the
    same greedy tokens across the 128-token boundary, and requests whose
    lengths lie in different 128-stretches load ONE decode program where
    the XLA route loads one a stretch."""
    eng, tokens, decodes = _pinned_engine()
    assert eng._reads_live_blocks()
    assert tokens == PINNED
    assert [r.first_use for r in decodes] == [True, False, False, False]
    assert ("decode", 512, 1) in eng.programs_seen
    assert sum(k[0] == "decode" for k in eng.programs_seen) == 1
    assert all(r.state_tokens_read > 0 for r in decodes)


def test_off_the_kernel_route_a_decode_program_per_stretch():
    eng, tokens, decodes = _pinned_engine()
    assert not eng._reads_live_blocks()
    assert tokens == PINNED
    assert [r.first_use for r in decodes] == [True, False, True, False]
    assert [r.state_tokens_read for r in decodes] == [0] * 4


def test_an_engine_with_a_mesh_keeps_the_xla_route(kernel_route):
    """Where the kernel would serve a single device, a sharded cache
    keeps the prefix route, its extents and its programs."""
    from copilot_for_consensus_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    eng, tokens, decodes = _pinned_engine(
        mesh=build_mesh(MeshConfig(dp=2, tp=4)))
    assert not eng._reads_live_blocks()
    assert [r.first_use for r in decodes] == [True, False, True, False]
    assert sorted(k[1] for k in eng.programs_seen
                  if k[0] == "decode") == [128, 256]
    assert tokens == PINNED


# ---------------------------------------------------------------------------
# the same for attention="mla" (models/xing.py): the latent cache is one
# array per stack of layers, positions along the minor axis; admission
# writes a piece's rows where they lie, decode merges its rows by the
# scatter `merge_window` uses, and neither program turns the cache over
# or cuts a layer out of it. On a TPU the experts' grouped matmuls are a
# kernel (ops/grouped_matmul.py) that takes the stack of all layers'
# experts whole, by pointer, with the layer's index: a layer's experts
# are never cut out of the stack (a copy of every expert, chosen or
# not, every step).
# ---------------------------------------------------------------------------

MLA_CFG = decoder_config(
    "tiny-xing", d_model=512, d_ff=512, q_lora_rank=128, kv_lora_rank=128,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    n_routed_experts=64, experts_per_token=4, moe_intermediate_size=512,
    max_seq_len=8192)
# the expert layers' rows: 2 x 8 x 144 x 8192 bf16 = 38 MB; one layer's
# experts 16.8 MB a matrix: neither is small enough to be moved whole
# into fast memory
MLA_MAX = 8192


def mla_program_faults(one_chip, which, cfg=None):
    """What a compiled latent-attention program may not hold: an op
    whose result is a stack of the cache, or one layer of it, other
    than the in-place updates; a float32 array of a score for every
    head and cached column of every slot (the decode program: what
    scoring the extent whole in XLA makes) or for every head, query and
    column of an admission round (the admission program: what folding
    a round in XLA makes, `ADMIT`); on the kernel route, any number of
    kernels but the three grouped matmuls of the one scanned expert
    layer and the attention's one a stack of layers, absorbed in the
    decode program, a round's fold in the admission program
    (`MLA_KERNELS`), or an op that makes a stack of experts or one
    layer's."""
    cfg = cfg or MLA_CFG
    eng = GenerationEngine(cfg, num_slots=SLOTS, max_len=MLA_MAX,
                           prefill_buckets=(64,), dtype=jnp.bfloat16,
                           eos_id=-1, quantize="int8")

    def wrap(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params, cache = jax.tree.map(wrap, eng.params), \
        jax.tree.map(wrap, eng._cache)
    key = wrap(jax.random.PRNGKey(0))
    i32 = wrap(jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    if which == "decode":
        text = eng._decode_mla_fn.lower(params, i32, i32, cache,
                                        key).compile().as_text()
    else:
        two = wrap(jax.ShapeDtypeStruct((ADMIT[0],), jnp.int32))
        text = eng._admit_mla_fn.lower(
            params, wrap(jax.ShapeDtypeStruct(ADMIT, jnp.int32)), two,
            two, two, cache, key).compile().as_text()
    stacks_ = {tuple(a.shape) for a in eng._cache.values()}
    layers = {s[1:] for s in stacks_} | {(1,) + s[1:] for s in stacks_}
    round_ = min(xing.KV_BLOCK, MLA_MAX)     # columns an admission round scores
    # one layer's experts (the stack whole may be laid out anew once a
    # dispatch, outside the loops)
    experts = {lead + tuple(eng.params["moe"][k]["q"].shape[1:])
               for k in ("we_gate", "we_up", "we_down")
               for lead in ((), (1,))}
    # computations that only re-index (a dynamic-slice, a bitcast): as
    # a fusion inside the dot that reads it such a one is the dot's
    # operand, not a copy
    views, comp = set(), None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            views.add(comp)
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if m and m.group(1) not in ("parameter", "dynamic-slice",
                                    "bitcast", "constant"):
            views.discard(comp)
    faults = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\](\S*) "
                     r"([\w\-]+)\(", line)
        # (copy-/slice-start and -done: the compiler's own prefetch of
        # something this small into fast memory)
        if not m or m.group(3) in ("parameter", "get-tuple-element",
                                   "bitcast", "copy-start", "copy-done",
                                   "slice-start", "slice-done"):
            continue
        shape = tuple(int(x) for x in m.group(1).split(","))
        # a layer's experts cut out of the stack, wherever to: every
        # expert is read to make it
        if shape in experts:
            faults.append(f"{m.group(3)} makes experts {shape}")
        # (S(1): a prefetch of a buffer this small into fast memory;
        # the served cache is never moved there)
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if "S(1)" in m.group(2) or m.group(3) == "dynamic-slice" or (
                m.group(3) == "fusion" and called
                and called.group(1) in views):
            continue
        if shape in stacks_ | layers \
                and m.group(3) != "dynamic-update-slice":
            faults.append(f"{m.group(3)} makes cache rows {shape}")
        if which == "decode" and " = f32[" in line and MLA_MAX in shape \
                and math.prod(shape) >= SLOTS * cfg.n_heads * MLA_MAX:
            faults.append(f"{m.group(3)} makes scores {shape}")
        if which == "admit" and " = f32[" in line and round_ in shape \
                and math.prod(shape) >= math.prod(ADMIT) * cfg.n_heads \
                * round_:
            faults.append(f"{m.group(3)} makes scores {shape}")
        # the fold's empty carry as a literal: megabytes of every
        # admission program's binary, read again at every load
        if which == "admit" and m.group(3) == "constant" \
                and math.prod(shape) >= math.prod(ADMIT) * cfg.n_heads \
                * latent_prefill_attention.STAT_ROWS:
            faults.append(f"constant holds a carry {shape}")
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    return faults, kernels, text


# the kernels of a compiled latent-attention program, by the scope and
# name in their `op_name`: the three grouped matmuls of the scanned
# expert layer in both; in decode also the absorbed attention over the
# live latent blocks, one call site a stack of layers (the dense stack's
# one layer and the expert stack's loop: 1 + 11 launches a token at the
# served depth); in admission the fold of a round of expanded attention
# (ops/latent_prefill_attention.py), one call site a stack of layers
# too, inside the loop over the live rounds
GROUPED = ["moe_experts/grouped_qmatmul"] * 3
MLA_KERNELS = {"admit": ["attn/mla_prefill_attention"] * 2 + GROUPED,
               "decode": ["attn/mla_decode_attention"] * 2 + GROUPED}

# an admission wave of the compiled programs: rows x bucket
ADMIT = (2, 64)


def mla_kernel_names(kernels):
    return sorted(
        "/".join(re.search(
            r'op_name="[^"]*/(attn|moe_experts|select)/(?:[^"]*/)?'
            r'(mla_decode_attention|mla_prefill_attention|grouped_qmatmul'
            r'|select_threshold)',
            k).groups())
        for k in kernels)


@pytest.mark.parametrize("which", ["decode", "admit"])
def test_compiled_mla_programs_hold_cache_and_experts_in_place(
        one_chip, on_tpu, which):
    faults, kernels, _text = mla_program_faults(one_chip, which)
    assert faults == []
    assert mla_kernel_names(kernels) == MLA_KERNELS[which]
    # the three grouped matmuls are call sites of ONE scanned body
    bodies = {re.search(r'op_name="([^"]*)/moe_experts/', k).group(1)
              for k in kernels if "grouped_qmatmul" in k}
    assert len(bodies) == 1 and "/while/body/" in bodies.pop() + "/"


# a decode step's grouped matmul at the served shapes (m, groups, k, n):
# the tiles, the grid (the visits are counted on the device) and the
# kernel's refs as they stood before an admission wave got tiles of its
# own (PR 42), which no decode program was to notice
DECODE_GROUPED = {
    "xing-gate-up": ((32, 64, 1024, 3584), (32, 1024, 896), (4, None, 1)),
    "xing-down": ((32, 64, 3584, 1024), (32, 1792, 1024), (1, None, 2)),
    "glm-gate-up": ((64, 32, 6144, 2048), (64, 1024, 1024), (2, None, 6)),
    "glm-down": ((64, 32, 2048, 6144), (64, 1024, 1024), (6, None, 2)),
}


@pytest.mark.parametrize("case", list(DECODE_GROUPED))
def test_a_decode_steps_grouped_matmul_is_tiled_as_it_was(case):
    (m, groups, k, n), (tm, tk, tn), grid = DECODE_GROUPED[case]
    assert grouped_matmul.tiling(m, groups, k, n) == (tm, tk, tn, False)
    shapes = (((m, k), jnp.bfloat16), ((2, groups, k, n), jnp.int8),
              ((2, groups, 1, n), jnp.float32), ((groups,), jnp.int32),
              ((), jnp.int32))
    jaxpr = jax.make_jaxpr(functools.partial(
        grouped_matmul.grouped_qmatmul.__wrapped__, interpret=False))(
            *(jax.ShapeDtypeStruct(*s) for s in shapes))
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(d if isinstance(d, int) else None
                 for d in call.params["grid_mapping"].grid) == grid
    # rows, the int8 tile, its scales, the product; the accumulator
    refs = [v.aval for v in call.params["jaxpr"].invars[5:]]
    assert [r.shape for r in refs] == [
        (tm, tk), (tk, tn), (1, tn), (tm, tn), (tm, tn)]
    assert refs[-1].dtype == jnp.float32


# the same two programs for a config that SELECTS what attention reads
# (cfg.index_topk; GLM-5 class): a second stack of rows a stack of
# layers (the index keys), a share of the experts, one residual stream.
# The latent stacks are still read through the kernel alone, now with
# the selection's mask beside its blocks, and still never cut or turned
# over; the index keys ride the decode scan as scanned inputs (the
# indexer scores a layer of them whole, in XLA: fewer heads than
# attention has, so no array of the size the guard above names). An
# admission piece's threshold is a kernel too, one call site a stack of
# layers (ops/select_threshold.py: its rounds of counting over keys in
# VMEM); a decode step's counts in XLA
SELECTED_KERNELS = {"admit": sorted(MLA_KERNELS["admit"]
                                    + ["select/select_threshold"] * 2),
                    "decode": MLA_KERNELS["decode"]}
GLM_CFG = decoder_config(
    "tiny-glm", d_model=512, d_ff=512, q_lora_rank=128, kv_lora_rank=128,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    n_routed_experts=64, held_experts=(16, 16), experts_per_token=4,
    moe_intermediate_size=512, index_n_heads=2, index_head_dim=64,
    index_topk=1024, max_seq_len=8192)


@pytest.mark.parametrize("which", ["decode", "admit"])
def test_compiled_selected_mla_programs_hold_both_caches_in_place(
        one_chip, on_tpu, which):
    faults, kernels, text = mla_program_faults(one_chip, which, GLM_CFG)
    assert faults == []
    assert mla_kernel_names(kernels) == SELECTED_KERNELS[which]
    # the selection's counting is there, under its own scope
    assert "/select/" in text and "/indexer/" in text


@pytest.mark.parametrize("rows,bucket", [(2, 2048), (1, 256)])
def test_the_threshold_kernel_compiles_at_the_served_shapes(
        one_chip, rows, bucket):
    """An admission wave's sort keys at the GLM cell's sizes (a buffer
    of 32,768 columns, rounds of 1,024): two halves of a query tile's
    keys are the kernel's VMEM, which the interpreter does not hold it
    to and the chip's compiler does."""
    from copilot_for_consensus_tpu.ops import select_threshold

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)

    text = jax.jit(functools.partial(
        select_threshold.piece_threshold, blk=1024, interpret=False)).lower(
            shape(rows, bucket, 32768), shape(rows, bucket),
            shape(rows)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_mla_guard_trips_when_the_extent_is_scored_whole_in_xla(
        one_chip, on_tpu, monkeypatch):
    """The formulation this replaced: the cache among the layer scans'
    scanned leaves, every slot's whole extent scored and masked."""
    from copilot_for_consensus_tpu.ops import latent_attention

    monkeypatch.setattr(latent_attention, "serves", lambda extent: False)
    faults, kernels, _text = mla_program_faults(one_chip, "decode")
    assert mla_kernel_names(kernels) == GROUPED
    assert any("makes scores" in f for f in faults)


@pytest.mark.parametrize("cfg", [MLA_CFG, GLM_CFG], ids=["xing", "glm"])
def test_mla_guard_trips_when_a_round_is_folded_in_xla(
        one_chip, on_tpu, monkeypatch, cfg):
    """The formulation this replaced, and the CPU's route: a round's
    float32 scores `[rows, heads, bucket, round]` made, masked, and read
    again for the maximum, the exponentials and the sums."""
    monkeypatch.setattr(latent_prefill_attention, "serves",
                        lambda block: False)
    faults, kernels, _text = mla_program_faults(one_chip, "admit", cfg)
    assert mla_kernel_names(kernels) == GROUPED
    assert any("makes scores" in f for f in faults)
    assert not any("makes cache rows" in f or "makes experts" in f
                   for f in faults)


def round_operand_ops(text, cfg):
    """The ops of a compiled admission program, inside its loop over
    the live rounds and under ``latent_expand``, whose result is as
    large as a round's values ``[rows, heads, round, dv]`` or larger,
    by the last part of their ``op_name``: what is written to make the
    fold kernel's operands."""
    least = ADMIT[0] * cfg.n_heads * min(xing.KV_BLOCK, MLA_MAX) \
        * cfg.v_head_dim
    made, fused = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():
            # (a fusion's body: its ops are the fusion's, counted there)
            fused = line.startswith("%fused_computation")
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if fused or not m or not name or m.group(2) in (
                "parameter", "get-tuple-element", "bitcast"):
            continue
        parts = name.group(1).split("/")
        if "latent_expand" in parts and parts[parts.index(
                "kv_prefix") - 2:parts.index("kv_prefix")] \
                == ["while", "body"] \
                and math.prod(int(x) for x in m.group(1).split(",")) \
                >= least:
            made.append(parts[-1])
    return sorted(made)


def test_a_rounds_expansion_writes_the_kernels_operands_and_no_more(
        one_chip, on_tpu):
    """Where the rotary key rides in the keys' weight (these widths, 32
    + 16 in a tile of 128, as the served 192 + 64): two einsums a round
    (in each of the two scanned stacks of layers) write the keys and
    values where the fold kernel reads them; no slice of a wider
    activation, no transpose, no key repeated a head beside them."""
    assert xing._rotary_in_weight(GLM_CFG)
    faults, _kernels, text = mla_program_faults(one_chip, "admit", GLM_CFG)
    assert faults == []
    assert round_operand_ops(text, GLM_CFG) == ["dot_general"] * 4


@pytest.mark.parametrize("cfg", [MLA_CFG, GLM_CFG], ids=["xing", "glm"])
def test_the_expansion_guard_trips_on_the_turned_over_form(
        one_chip, on_tpu, monkeypatch, cfg):
    """The formulation this replaced, and the route of widths whose
    no-position part fills its tiles (a lane tile of 32 stands in for
    128 at these widths, as the served 128 + 64): ``expand``'s one
    einsum a round, its output cut at the keys' width, the rotary key
    repeated a head, keys and values turned heads-first for the
    kernel."""
    monkeypatch.setattr(xing, "LANE_TILE", 32)
    assert not xing._rotary_in_weight(cfg)
    faults, _kernels, text = mla_program_faults(one_chip, "admit", cfg)
    assert faults == []
    made = round_operand_ops(text, cfg)
    assert len(made) > 4 and set(made) - {"dot_general"}


def test_mla_guard_trips_when_the_empty_carry_is_a_literal(
        one_chip, on_tpu, monkeypatch):
    """Zeros with a row set to ``-inf`` are folded into a literal of the
    whole array (8.4 MB a call site at GLM's served shape: PERF.md §6,
    PR 38); from an iota the compiler makes them where they are used."""

    def folded(n, h, s, dv):
        return jnp.zeros((n, h, s, dv), jnp.float32), jnp.zeros(
            (n, h, latent_prefill_attention.STAT_ROWS, s),
            jnp.float32).at[:, :, 0].set(-jnp.inf)

    monkeypatch.setattr(latent_prefill_attention, "empty_carry", folded)
    faults, kernels, _text = mla_program_faults(one_chip, "admit")
    assert mla_kernel_names(kernels) == MLA_KERNELS["admit"]
    assert faults and all("constant holds a carry" in f for f in faults)


def test_mla_guard_trips_when_the_layer_scan_cuts_the_experts_out(
        one_chip, on_tpu, monkeypatch):
    """The formulation this replaced: the expert stacks among the
    scanned leaves, a layer's handed to the kernel."""
    from copilot_for_consensus_tpu.models import xing

    def scanned_experts(stack):
        return stack, {}

    real = xing.routed_experts

    def cut_out(hid, layer, experts, li, cfg, live, held=None, dtype=None):
        mats = {k: jax.tree.map(lambda a: a[None], layer[k])
                for k in xing.EXPERTS}
        return real(hid, layer, mats, jnp.int32(0), cfg, live, held, dtype)

    monkeypatch.setattr(xing, "_split", scanned_experts)
    monkeypatch.setattr(xing, "routed_experts", cut_out)
    faults, _kernels, _text = mla_program_faults(one_chip, "decode")
    assert any("makes experts" in f for f in faults)


# ---------------------------------------------------------------------------
# attention="mixed" (models/mixed.py): two kinds of cache in one dict.
# The compiled programs hold both in place: a ring is laid in two slabs
# a slot and never copied to be laid, a full extent as the dense
# engine's; decode attention reads either through the dense kernel (a
# ring's range in its two runs), admission through the flash kernel,
# and no array of a score for every head, query and cached column is
# made. A layer's matrices are read out of the one stack where they lie
# (a period's slice cut again member by member copied them every step).
# ---------------------------------------------------------------------------

MIXED_CFG = decoder_config(
    "tiny-mixed", d_model=512, n_heads=16, n_kv_heads=2,
    head_dim_override=128, sliding_window=2048, n_routed_experts=16,
    moe_intermediate_size=512, max_seq_len=8192)
MIXED_MAX, MIXED_PIECE = 8192, 256


def mixed_program(one_chip, which):
    eng = GenerationEngine(MIXED_CFG, num_slots=SLOTS, max_len=MIXED_MAX,
                           prefill_buckets=(MIXED_PIECE,),
                           dtype=jnp.bfloat16, eos_id=-1, quantize="int8")
    assert eng._reads_ring_blocks()

    def wrap(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params, cache = jax.tree.map(wrap, eng.params), \
        jax.tree.map(wrap, eng._cache)
    key = wrap(jax.random.PRNGKey(0))
    i32 = wrap(jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    if which == "decode":
        text = eng._decode_mixed_fn.lower(params, i32, i32, cache,
                                          key).compile().as_text()
    else:
        two = wrap(jax.ShapeDtypeStruct((2,), jnp.int32))
        text = eng._admit_mixed_fn.lower(
            params, wrap(jax.ShapeDtypeStruct((2, MIXED_PIECE), jnp.int32)),
            two, two, two, cache, key).compile().as_text()
    return eng, text


@pytest.mark.parametrize("which", ["decode", "admit"])
def test_compiled_mixed_programs_hold_both_caches_in_place(
        one_chip, on_tpu, which):
    eng, text = mixed_program(one_chip, which)
    stacks_ = {tuple(a.shape) for a in eng._cache.values()}
    ring = eng._cache["window_k"].shape[3]
    assert ring == 2048 + MIXED_PIECE and len(stacks_) == 2
    matrices = {tuple(v["q"].shape[1:])
                for k, v in eng.params["layers"].items()
                if isinstance(v, dict)}
    faults = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]+)\](\S*) "
                     r"([\w\-]+)\(", line)
        if not m or m.group(4) in ("parameter", "get-tuple-element",
                                   "bitcast"):
            continue
        shape = tuple(int(x) for x in m.group(2).split(","))
        if shape in stacks_ and m.group(4) not in (
                "dynamic-update-slice", "scatter", "while", "fusion"):
            faults.append(f"{m.group(4)} makes a cache {shape}")
        if shape in stacks_ and m.group(4) == "fusion" \
                and "dynamic-update-slice" not in line \
                and "scatter" not in line:
            faults.append(f"a fusion makes a cache {shape}: {line[:120]}")
        # a period's matrices cut out of the stack
        if len(shape) >= 3 and shape[0] == MIXED_CFG.layer_period \
                and shape[1:] in matrices and m.group(4) != "dynamic-slice":
            faults.append(f"{m.group(4)} makes a period's matrix {shape}")
        if m.group(1) == "f32" and (MIXED_MAX in shape or ring in shape) \
                and math.prod(shape) >= MIXED_CFG.n_heads * MIXED_MAX * (
                    SLOTS if which == "decode" else MIXED_PIECE):
            faults.append(f"{m.group(4)} makes scores {shape}")
    assert faults == []
    kernels = [re.search(r'op_name="([^"]*)"', ln).group(1)
               for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    sites = {"window": sum("attn_window/" in k for k in kernels),
             "full": sum("attn_full/" in k for k in kernels),
             "experts": sum("moe_experts/" in k and "grouped_qmatmul" in k
                            for k in kernels)}
    # one period's four layers unrolled in ONE scanned body: three
    # window layers (decode: a ring's two runs each) and a full one
    assert sites == {"window": 6 if which == "decode" else 3, "full": 1,
                     "experts": 12}
    assert len(kernels) == sum(sites.values())


# ---------------------------------------------------------------------------
# and nothing of this architecture reaches the programs of the two
# configurations the benchmark had: no op of theirs stands under one of
# its scopes or calls its kernel (compiled at the tiny sizes, where the
# scope is in every op's ``op_name``). (That their lowered text
# equals the parent's, hash for hash, was checked once, when the
# architecture came: PERF.md section 6, PR 33.)
# ---------------------------------------------------------------------------

OTHER_PROGRAMS = ("jit__decode", "jit__admit_fused", "jit__admit_eva",
                  "jit__decode_eva[False]", "jit__decode_eva[True]")


@pytest.fixture(scope="module")
def lowered_others():
    """The other configurations' programs at the tiny sizes, lowered
    (not yet compiled)."""
    key = jax.random.PRNGKey(0)
    i32 = jnp.zeros((4,), jnp.int32)
    rows = (jnp.zeros((2, 32), jnp.int32), jnp.ones((2,), jnp.int32))
    two = jnp.zeros((2,), jnp.int32)
    args = dict(num_slots=4, max_len=256, dtype=jnp.bfloat16,
                attn_impl="xla", eos_id=-1, quantize="int8")
    eng = GenerationEngine(decoder_config("tiny"), None,
                           prefill_buckets=(32, 64), **args)
    evb = GenerationEngine(decoder_config("tiny-eva"), None,
                           prefill_buckets=(32,), **args)
    texts = {
        "jit__decode": eng._decode_fn.lower(
            eng.params, i32, i32, eng._cache, key, kv_len=128,
            n_windows=1),
        "jit__admit_fused": eng._admit_fn.lower(
            eng.params, *rows, eng._cache, two, key),
        "jit__admit_eva": evb._admit_eva_fn.lower(
            evb.params, *rows, two, two, evb._cache, key),
    }
    for may_close in (False, True):
        texts[f"jit__decode_eva[{may_close}]"] = evb._decode_eva_fn.lower(
            evb.params, i32, i32, evb._cache, key, may_close=may_close)
    return texts


@pytest.fixture(scope="module")
def lowered_programs(lowered_others):
    return {k: v.compile().as_text() for k, v in lowered_others.items()}


@pytest.mark.parametrize("program", OTHER_PROGRAMS)
def test_the_other_configurations_programs_hold_nothing_of_this_one(
        lowered_programs, program):
    from copilot_for_consensus_tpu.obs.profile import (
        MIXED_SCOPES,
        SCOPES,
        XING_SCOPES,
    )

    names = re.findall(r'op_name="([^"]*)"', lowered_programs[program])
    under = {part for name in names for part in name.split("/")}
    assert "attn" in under and "ffn" in under    # the scopes are there
    assert not under & (set(XING_SCOPES + MIXED_SCOPES) - set(SCOPES))
    assert not any("grouped_qmatmul" in name
                   or "mla_decode_attention" in name
                   or "mla_prefill_attention" in name for name in names)


# ---------------------------------------------------------------------------
# and a change to one route leaves the other programs' text alone. PR 48
# rewrote the expansion on the KERNEL's route of the latent admission
# (a TPU's); the decode programs (the absorbed form, which expands
# nothing), the other architectures' admission programs and, on the
# CPU, the latent admission programs themselves (the XLA rounds: the
# oracle) were to stay the parent's, hash for hash. The hashes below are
# the parent's (commit 6099b46), taken from its checkout by this code.
# A PR that means to change one of these programs pins its new hash and
# says so; one that does not has a finding.
# ---------------------------------------------------------------------------

PINNED_PROGRAMS = {
    "tiny/admit":
        "741174c67cfb469e778631676b87640b9bca70958b616e21eb6afa044e1b3efe",
    "tiny-eva/admit":
        "9474a1bdb9aa2fea06042ff9da7c8f5ac805802b127fee1d9146090859bd9024",
    "tiny-mixed/admit":
        "9c85553b1f30e32f0c282c07d3ff4a47fe3e6b4f9b2f5074a112fcf2c4f9d4f5",
    "tiny-xing/admit":
        "db9b6dd88d732f50ff5663af5bf26bf60d63e2422c3ae226796e5cfa16bc040b",
    "tiny-xing/decode":
        "f33456e7740a6f129139516ceefbdacb5c1d91cb571e0ffd7ef38088ba1d0b74",
    "tiny-glm/admit":
        "d159d4c04f659da6c562ef36c7e549537eb0c6f38ee37d404285301e510d0783",
    "tiny-glm/decode":
        "ea76bdcb01d6d8f5971adf577b3ce6a7f7c54fabd75959455de1b44c3a6a36f4",
}


@pytest.fixture(scope="module")
def lowered_text_hashes(lowered_others):
    """sha256 of ``.lower().as_text()`` (StableHLO, no source lines) of
    the tiny engines' programs on the CPU."""
    import hashlib

    key = jax.random.PRNGKey(0)
    i32 = jnp.zeros((4,), jnp.int32)
    two = jnp.zeros((2,), jnp.int32)

    def rows(s):
        return jnp.zeros((2, s), jnp.int32), jnp.ones((2,), jnp.int32)

    def engine(name, buckets):
        return GenerationEngine(
            decoder_config(name), None, num_slots=4, max_len=256,
            prefill_buckets=buckets, dtype=jnp.bfloat16, eos_id=-1,
            quantize="int8")

    low = {"tiny/admit": lowered_others["jit__admit_fused"],
           "tiny-eva/admit": lowered_others["jit__admit_eva"]}
    eng = engine("tiny-mixed", (8, 16))
    low["tiny-mixed/admit"] = eng._admit_mixed_fn.lower(
        eng.params, *rows(16), two, two, eng._cache, key)
    for name in ("tiny-xing", "tiny-glm"):
        eng = engine(name, (32,))
        low[name + "/admit"] = eng._admit_mla_fn.lower(
            eng.params, *rows(32), two, two, eng._cache, key)
        low[name + "/decode"] = eng._decode_mla_fn.lower(
            eng.params, i32, i32, eng._cache, key)
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()
            for k, v in low.items()}


@pytest.mark.parametrize("program", list(PINNED_PROGRAMS))
def test_the_lowered_text_of_the_untouched_programs_is_the_parents(
        lowered_text_hashes, program):
    assert lowered_text_hashes[program] == PINNED_PROGRAMS[program]
