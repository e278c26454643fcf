"""Builder for Xing4.0 (``model_type`` ``xing4_0``: latent attention,
sparse experts beside a shared one, a multi-stream residual) behind the
same ``GenerationEngine`` and runner as the engine builder: the model's
keys, its seeded weights and the program's config object are this
file's; tap, runner, load and outcome are inherited. ``warm`` reaches
every admission program a piece of one of the plan's prompts can fall
into and the one decode program; ``collect`` adds to each step record
the counts the program writes for this architecture (the routing's,
counted on the device, and the latent rows live and read), which the
tap, written before them, does not copy."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmark.builders._decoder import dims_of, token_ids
from benchmark.builders.engine import System as EngineSystem
from benchmark.harness import weights as W

#: How far the seeded matrices that set a sublayer's size stand from
#: unit scale (every other matrix keeps activations at unit scale, as
#: ``harness/weights.py`` draws them), so that the streams look like a
#: trained model's to the comparison that decides ``correct``: an
#: embedding of unit size that every sublayer adds a fraction to
#: (attention about 0.3, the dense and the shared SwiGLU 0.15, the
#: routed experts' sum 0.04: rms of the write over rms of a stream at
#: the input), and scores of std 3 (``wq_b``; unit scale gives
#: ``mscale ** 2`` = 2), under which a query's softmax rests on a few
#: of 16,384 keys and not on three hundred, so attention writes as
#: much after a long prompt as after a short one. With every matrix at
#: unit scale and the embedding at ``d ** -0.5`` the first sublayers
#: ARE the stream and the routed sum is most of every later write: one
#: expert chosen otherwise in bfloat16 than in the reference's float32
#: (a near-tie between the 4th and the 5th score) then moves the
#: token's stream by a third, the next layer's choice flips with it,
#: and a sound run reads a quarter of what a random token reads
#: (PERF.md section 6, PR 33). The choice of experts still decides the
#: logits at these sizes: one wrong expert of four on one token in ten
#: reads three times a sound run.
DRAWN = {"tok_emb": 1.0, "wq_b": 1.5, "wo": 0.45, "w_down": 0.25,
         "we_down": 0.06}

XING_COUNTS = ("experts_touched", "expert_rows", "expert_rows_max",
               "window_tokens", "state_tokens_read", "attn_pairs")


def seeded_weights(dims: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The program's layout for this model (two stacks of layers,
    ``dense`` and ``moe``; an int8 leaf is ``{"q", "scale"}`` with one
    float32 scale per output channel, per expert and channel in an
    expert stack), made on the device in one jitted call, in the types
    the configuration's ``serving`` states. Matrices keep activations
    at unit scale, as ``harness/weights.py`` draws them, but for those
    of ``DRAWN``; the router's logits and the residual maps' are of
    order one (the mix's a quarter of that), so the experts chosen and
    the maps differ from token to token."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    n = dims["hc_mult"]
    rq, r, dr = (dims["q_lora_rank"], dims["kv_lora_rank"],
                 dims["qk_rope_head_dim"])
    dn, dv = dims["qk_nope_head_dim"], dims["v_head_dim"]
    e, fe = dims["n_routed_experts"], dims["moe_intermediate_size"]
    k0 = min(dims["first_k_dense_replace"], dims["num_hidden_layers"])
    maps = n * (n + 2)

    def int8(key, shape, times=1.0):
        *lead, rows, cols = shape
        base = times * rows ** -0.5 / 73.3   # uniform int8 has std ~73.3

        def one(k, group=()):
            """One layer's matrix, or all of a layer's experts."""
            kq, ks = jax.random.split(k)
            # four int8 out of every 32 random bits
            words = jax.random.bits(kq, (*group, rows, cols // 4),
                                    jnp.uint32)
            q = jax.lax.bitcast_convert_type(words, jnp.int8)
            q = jnp.maximum(q.reshape(*group, rows, cols), -127)
            scale = base * jax.random.uniform(
                ks, (*group, 1, cols), jnp.float32, 0.5, 1.5)
            return {"q": q, "scale": scale}

        if not lead:
            return one(key)
        # layer by layer, so the scratch is one layer's
        return jax.lax.map(lambda k: one(k, tuple(lead[1:])),
                           jax.random.split(key, lead[0]))

    def normal(key, shape, std, dt):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)

    def gain(key, shape):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def stack(key, count: int, moe: bool) -> dict:
        keys = iter(jax.random.split(key, 32))
        out = {
            "attn_norm": gain(next(keys), (count, d)),
            "ffn_norm": gain(next(keys), (count, d)),
            "wq_a": int8(next(keys), (count, d, rq)),
            "q_norm": gain(next(keys), (count, rq)),
            "wq_b": int8(next(keys), (count, rq, h * (dn + dr)),
                         DRAWN["wq_b"]),
            "wkv_a": int8(next(keys), (count, d, r + dr)),
            "kv_norm": gain(next(keys), (count, r)),
            "wkv_b": normal(next(keys), (count, r, h * (dn + dv)),
                            r ** -0.5, dtype),
            "wo": int8(next(keys), (count, h * dv, d), DRAWN["wo"]),
        }
        for sub in ("attn", "ffn"):
            out[f"hc_{sub}_phi"] = normal(
                next(keys), (count, n, d, maps), (n * d) ** -0.5,
                jnp.float32)
            # the mix's logits at a quarter of the others' size (std
            # about 0.45): twenty Sinkhorn rounds then end within 1e-6
            # of doubly stochastic, with three quarters of the mass
            # off the diagonal
            out[f"hc_{sub}_alpha"] = jnp.asarray([1.0, 1.0, 0.25]) \
                * jax.random.uniform(next(keys), (count, 3), jnp.float32,
                                     1.0, 2.0)
            out[f"hc_{sub}_bias"] = normal(
                next(keys), (count, maps), 0.5, jnp.float32) \
                * jnp.where(jnp.arange(maps) < 2 * n, 1.0, 0.5)
        f = fe * max(dims["n_shared_experts"], 1) if moe \
            else dims["intermediate_size"]
        out.update(w_gate=int8(next(keys), (count, d, f)),
                   w_up=int8(next(keys), (count, d, f)),
                   w_down=int8(next(keys), (count, f, d), DRAWN["w_down"]))
        if moe:
            out.update(
                router=normal(next(keys), (count, d, e), d ** -0.5,
                              jnp.float32),
                e_bias=normal(next(keys), (count, e), 0.01, jnp.float32),
                we_gate=int8(next(keys), (count, e, d, fe)),
                we_up=int8(next(keys), (count, e, d, fe)),
                we_down=int8(next(keys), (count, e, fe, d),
                             DRAWN["we_down"]))
        return out

    def build(key):
        k_emb, k_norm, k_head, k_dense, k_moe = jax.random.split(key, 5)
        out = {
            "tok_emb": (jax.random.truncated_normal(
                k_emb, -2, 2, (dims["vocab_size"], d), jnp.float32)
                * DRAWN["tok_emb"]).astype(dtype),
            "final_norm": gain(k_norm, (d,)),
            "lm_head": int8(k_head, (d, dims["vocab_size"])),
        }
        if k0:
            out["dense"] = stack(k_dense, k0, False)
        if dims["num_hidden_layers"] > k0:
            out["moe"] = stack(k_moe, dims["num_hidden_layers"] - k0, True)
        return out

    return jax.jit(build)(W.seed_key(seed))


class System(EngineSystem):
    def __init__(self, cell: dict, seed: int, rehearse: bool, log):
        #: a closed loop's first burst, until the driver first waits
        self._burst: list[tuple] = []
        super().__init__(cell, seed, rehearse, log)

    def make_dims(self, data: dict, rehearse: bool) -> dict:
        try:       # before any weight is made: a program without it
            import copilot_for_consensus_tpu.models.xing  # noqa: F401
        except ImportError as e:
            raise SystemExit(
                f"{data['name']}: the program in this checkout cannot "
                f"serve attention='mla' ({e})")
        dims = dims_of(data, rehearse)
        if dims["model_type"] != "xing4_0" or not dims["norm_topk_prob"] \
                or dims["scoring_func"] != "sigmoid" \
                or dims["n_group"] != 1 or dims["topk_group"] != 1:
            raise ValueError(f"{data['name']}: not a shape this builder "
                             f"serves")
        return dims

    def make_weights(self, seed: int):
        return seeded_weights(self.dims, seed)

    def program_config(self, name: str):
        from copilot_for_consensus_tpu.models.configs import DecoderConfig

        d = self.dims
        return DecoderConfig(
            name=name, vocab_size=d["vocab_size"], d_model=d["hidden_size"],
            n_layers=d["num_hidden_layers"],
            n_heads=d["num_attention_heads"],
            n_kv_heads=d["num_key_value_heads"],
            d_ff=d["intermediate_size"], rope_theta=float(d["rope_theta"]),
            max_seq_len=d["max_position_embeddings"],
            norm_eps=d["rms_norm_eps"], attention="mla",
            q_lora_rank=d["q_lora_rank"], kv_lora_rank=d["kv_lora_rank"],
            qk_nope_head_dim=d["qk_nope_head_dim"],
            qk_rope_head_dim=d["qk_rope_head_dim"],
            v_head_dim=d["v_head_dim"],
            rope_scaling=tuple(sorted(d["rope_scaling"].items())),
            n_routed_experts=d["n_routed_experts"],
            n_shared_experts=d["n_shared_experts"],
            experts_per_token=d["num_experts_per_tok"],
            moe_intermediate_size=d["moe_intermediate_size"],
            first_k_dense_replace=d["first_k_dense_replace"],
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            hc_mult=d["hc_mult"],
            hc_sinkhorn_iters=d["hc_sinkhorn_iters"], hc_eps=d["hc_eps"],
            mhc_h_res_clamp_min=float(d["mhc_h_res_clamp_min"]),
            mhc_h_res_clamp_max=float(d["mhc_h_res_clamp_max"]))

    def warm(self, plan: dict) -> None:
        """Every program the plan can reach, through the engine's
        public ``generate``: an admission wave for each bucket a piece
        of one of the plan's prompts falls in, at every row count (a
        power of two, under the admission token budget), and the decode
        program."""
        eng, rng = self.engine, self._rng
        vocab, top = eng.cfg.vocab_size, eng.buckets[-1]
        t0 = time.monotonic()
        sizes = set()
        for p_len in {it["prompt_len"] for it in plan["items"]}:
            if p_len >= top:
                sizes.add(top)
            if p_len % top:
                sizes.add(p_len % top)
        used = sorted({next(b for b in eng.buckets if b >= n)
                       for n in sizes})
        waves = 0
        for bucket in used:
            rows = 1
            while (rows <= eng.num_slots
                   and (rows == 1 or rows * bucket
                        <= eng.admission_token_budget)):
                eng.generate([token_ids(rng, bucket, vocab)
                              for _ in range(rows)], 1)
                waves += 1
                rows *= 2
        eng.generate([token_ids(rng, used[0], vocab)],
                     1 + eng.decode_window)
        self.parts["warm_s"] = time.monotonic() - t0
        self.log(f"warm-up: {waves} admission waves over buckets {used}, "
                 f"1 decode program")

    # -- load: a closed loop's first burst goes to the engine whole ------

    def submit(self, i: int, item: dict, due) -> None:
        """A closed loop's first burst (as many requests as may be
        outstanding, one call after the other, until the driver first
        waits) is kept here and handed to the runner as ONE batch
        (``AsyncEngineRunner.submit_many``): the dispatcher's first
        step then admits from all of them in every run. Handed over one
        by one, its first step sees one request or several as the
        threads happen to run; the first admission wave is then one row
        or two, every later wave pairs other rows, and with requests
        this large (16 finish in a window) a run reads one of two
        schedules 1.7% apart in ``out_tok_s`` (PERF.md section 6,
        PR 33)."""
        if due is not None or self._sent:
            return super().submit(i, item, due)
        with self._cv:
            self._inflight += 1
        self._burst.append((i, item, time.monotonic()))

    def wait_progress(self, timeout: float) -> None:
        if self._burst:
            burst, self._burst = self._burst, []
            handles = self.runner.submit_many([
                (self._prompts.pop(i), item["new_tokens"],
                 {"correlation_id": f"{self._tag}{i}"})
                for i, item, _sent in burst])
            for (i, _item, sent), h in zip(burst, handles):
                self._sent[i] = (sent, None, h)
                h.add_done_callback(lambda hh, i=i: self._finished(i, hh))
        super().wait_progress(timeout)

    def collect(self, plan: dict) -> dict:
        records = super().collect(plan)
        by_seq = {r.seq: r
                  for r in self.engine.telemetry.recorder.records()}
        for step in records["steps"]:
            rec = by_seq.get(step["seq"])
            for name in XING_COUNTS:
                step[name] = getattr(rec, name, 0)
        return records
