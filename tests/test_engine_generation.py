# Continuous-batching engine vs naive full-forward greedy decoding.
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytestmark = pytest.mark.slow   # JAX compiles / multi-process:
# excluded from the CI fast lane (pytest -m "not slow")


from copilot_for_consensus_tpu.engine.generation import GenerationEngine
from copilot_for_consensus_tpu.engine.sampling import SamplingConfig
from copilot_for_consensus_tpu.engine.tokenizer import ByteTokenizer
from copilot_for_consensus_tpu.models import decoder
from copilot_for_consensus_tpu.models.configs import decoder_config
from copilot_for_consensus_tpu.parallel import MeshConfig, build_mesh

CFG = decoder_config("tiny")
PARAMS = decoder.init_params(jax.random.PRNGKey(7), CFG, dtype=jnp.float32)


def _naive_greedy(prompt, n_new):
    """Oracle: re-run the full forward for every generated token."""
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        logits = decoder.forward(PARAMS, jnp.asarray([toks]), CFG,
                                 attn_impl="xla")
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def _engine(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("attn_impl", "xla")
    return GenerationEngine(CFG, PARAMS, **kw)


@pytest.mark.parametrize("window,n_windows", [(1, 1), (4, 1),
                                              (4, 2), (2, 3)])
def test_greedy_matches_naive_forward(window, n_windows):
    eng = _engine(decode_window=window, windows_per_dispatch=n_windows)
    prompts = [[5, 9, 13], [40, 41, 42, 43, 44, 45, 46]]
    comps = eng.generate(prompts, max_new_tokens=6)
    for p, c in zip(prompts, comps):
        want = _naive_greedy(p, 6)
        got = c.tokens
        # Engine stops early on eos; compare up to what it produced.
        assert got == want[:len(got)]
        assert len(got) == 6 or want[len(got)] != got[-1]


def test_more_requests_than_slots_all_complete():
    eng = _engine(num_slots=2)
    prompts = [[i + 3, i + 4, i + 5] for i in range(7)]
    comps = eng.generate(prompts, max_new_tokens=4)
    assert len(comps) == 7
    for p, c in zip(prompts, comps):
        assert c.tokens == _naive_greedy(p, 4)[:len(c.tokens)]


def test_mid_stream_join_does_not_disturb_running_slot():
    # Request B joins while A is mid-decode; A's output must be identical
    # to solo decoding — the continuous-batching invariant.
    solo = _engine().generate([[11, 12, 13]], max_new_tokens=8)[0].tokens

    eng = _engine(decode_window=2)
    done = {}
    a = eng.submit([11, 12, 13], max_new_tokens=8)
    for _ in range(3):
        for c in eng.step():
            done[c.request_id] = c
    b = eng.submit([30, 31, 32, 33], max_new_tokens=3)
    for _ in range(30):
        for c in eng.step():
            done[c.request_id] = c
        if len(done) == 2:
            break
    assert done[a].tokens == solo
    assert done[b].tokens == _naive_greedy([30, 31, 32, 33], 3)[
        :len(done[b].tokens)]


def test_slot_reuse_after_retirement():
    eng = _engine(num_slots=1)
    c1 = eng.generate([[9, 8, 7]], max_new_tokens=3)[0]
    c2 = eng.generate([[21, 22, 23]], max_new_tokens=3)[0]
    assert c1.tokens == _naive_greedy([9, 8, 7], 3)[:len(c1.tokens)]
    assert c2.tokens == _naive_greedy([21, 22, 23], 3)[:len(c2.tokens)]


def test_long_prompt_truncates_to_tail():
    eng = _engine(max_len=32, prefill_buckets=(32,), decode_window=1)
    prompt = list(np.arange(100) % 200 + 3)
    c = eng.generate([prompt], max_new_tokens=2)[0]
    assert c.prompt_len == 31          # max_len - decode_window
    assert len(c.tokens) <= 2


def test_sampled_generation_is_reproducible_and_in_vocab():
    eng1 = _engine(sampling=SamplingConfig(temperature=0.8, top_k=20),
                   seed=3)
    eng2 = _engine(sampling=SamplingConfig(temperature=0.8, top_k=20),
                   seed=3)
    t1 = eng1.generate([[4, 5, 6]], max_new_tokens=8)[0].tokens
    t2 = eng2.generate([[4, 5, 6]], max_new_tokens=8)[0].tokens
    assert t1 == t2
    assert all(0 <= t < CFG.vocab_size for t in t1)


def test_generate_text_roundtrip():
    eng = _engine()
    tok = ByteTokenizer(CFG.vocab_size)
    outs = eng.generate_text(["hi", "ok"], tok, max_new_tokens=4)
    assert len(outs) == 2
    assert all(isinstance(o, str) for o in outs)


def test_engine_on_mesh_matches_single_device():
    want = _engine().generate([[5, 9, 13]], max_new_tokens=5)[0].tokens
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    eng = _engine(mesh=mesh)
    got = eng.generate([[5, 9, 13]], max_new_tokens=5)[0].tokens
    assert got == want


def test_fp8_kv_cache_close_to_full_precision():
    """float8_e4m3 KV halves cache HBM (the slot-count ceiling). Random
    weights make long token-exactness meaningless (near-tie argmax), so
    the acceptance bar is: the first decode steps agree, and the whole
    generated distribution stays close — logit cosine vs the f32 cache
    well above what a broken cache would give."""
    import jax.numpy as jnp
    import numpy as np

    from copilot_for_consensus_tpu.engine.generation import GenerationEngine
    from copilot_for_consensus_tpu.models import decoder, decoder_config

    cfg = decoder_config("tiny")
    prompts = [list(range(1, 20)), list(range(5, 40))]
    outs, engines = {}, {}
    for name, kv in (("f32", None), ("fp8", jnp.float8_e4m3fn)):
        eng = GenerationEngine(cfg, num_slots=4, max_len=128, seed=3,
                               kv_dtype=kv, dtype=jnp.float32)
        engines[name] = eng
        outs[name] = [c.tokens for c in eng.generate(prompts,
                                                     max_new_tokens=12)]
    for a, b in zip(outs["f32"], outs["fp8"]):
        assert a[:3] == b[:3], (a, b)

    # Distributional closeness where the cache is actually READ: prefill
    # fills each dtype's cache, then a decode_step attends over it — its
    # logits carry the full quantization error of every cached position.
    logits = {}
    for name, eng in engines.items():
        tokens = jnp.asarray([prompts[0]], dtype=jnp.int32)
        n = len(prompts[0])
        lengths = jnp.asarray([n], dtype=jnp.int32)
        cache = decoder.init_cache(cfg, 1, 64, dtype=eng.kv_dtype)
        _, cache = decoder.prefill(eng.params, tokens, lengths, cfg,
                                   cache, attn_impl="xla")
        lg, _ = decoder.decode_step(
            eng.params, jnp.asarray([7], dtype=jnp.int32),
            jnp.asarray([n], dtype=jnp.int32), cfg, cache)
        logits[name] = np.asarray(lg[0], dtype=np.float64)
    x, y = logits["f32"], logits["fp8"]
    assert not np.array_equal(x, y), "fp8 cache read should perturb logits"
    cos = float(x @ y / (np.linalg.norm(x) * np.linalg.norm(y)))
    assert cos > 0.99, cos


@pytest.mark.parametrize("n_windows", [1, 3])
def test_sliding_window_greedy_multi_window(n_windows):
    """tiny-swa through the chained-window dispatch: the done-piece
    masking (completed windows held OUT of the cache until the single
    end-of-dispatch merge) must respect the sliding window exactly —
    greedy tokens match the naive forward oracle."""
    cfg = decoder_config("tiny-swa")
    assert cfg.sliding_window > 0
    params = decoder.init_params(jax.random.PRNGKey(9), cfg,
                                 dtype=jnp.float32)
    eng = GenerationEngine(cfg, params, num_slots=2, max_len=64,
                           prefill_buckets=(16,), dtype=jnp.float32,
                           attn_impl="xla", decode_window=4,
                           windows_per_dispatch=n_windows)
    prompt = list(range(5, 17))
    comp = eng.generate([prompt], max_new_tokens=16)[0]
    toks, want = list(prompt), []
    for _ in range(16):
        logits = decoder.forward(params, jnp.asarray([toks]), cfg,
                                 attn_impl="xla")
        nxt = int(jnp.argmax(logits[0, -1]))
        want.append(nxt)
        toks.append(nxt)
    assert comp.tokens == want[:len(comp.tokens)]
    assert len(comp.tokens) >= 8
