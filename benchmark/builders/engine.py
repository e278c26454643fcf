"""Builder: a bare GenerationEngine behind an AsyncEngineRunner, with
the constructor arguments of the configuration file and the benchmark's
own seeded weights.

What is shaped by the model is in three methods, ``make_dims``,
``make_weights`` and ``program_config``. The builder of another
architecture that ``GenerationEngine`` serves is a file with a
subclass named ``System`` that overrides them; the tap, the runner,
the load, the records and the warm-up are inherited."""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.builders._decoder import (
    decoder_config,
    dims_of,
    token_ids,
    warm_engine,
)
from benchmark.builders._tap import EngineTap
from benchmark.harness import weights as W


class System:
    def __init__(self, cell: dict, seed: int, rehearse: bool, log):
        import jax
        import jax.numpy as jnp

        from copilot_for_consensus_tpu.engine.async_runner import (
            AsyncEngineRunner,
        )
        from copilot_for_consensus_tpu.engine.generation import (
            GenerationEngine,
        )

        self.log, self.seed, self.parts = log, seed, {}
        data = cell["config_data"]
        self.dims = self.make_dims(data, rehearse)
        eng_args = dict(data["engine"])
        if rehearse:
            eng_args.update(data["rehearsal"]["engine"])
        eng_args["prefill_buckets"] = tuple(eng_args["prefill_buckets"])
        t0 = time.monotonic()
        self.weights = self.make_weights(seed)
        jax.block_until_ready(self.weights)
        self.parts["weights_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        self.engine = GenerationEngine(
            self.program_config(cell["config"]), self.weights,
            dtype=jnp.bfloat16, seed=seed & 0x7FFFFFFF, **eng_args)
        self.parts["build_s"] = time.monotonic() - t0
        self.tap = EngineTap(self.engine)
        self.runner = AsyncEngineRunner(self.engine)
        self._rng = np.random.default_rng(seed)
        self._prompts: dict[int, list[int]] = {}
        self._sent: dict[int, tuple] = {}
        self._done: dict[int, bool] = {}
        self._inflight = 0
        self._cv = threading.Condition()
        self._stopping = False
        self._tag = "b"

    # -- the model's shape -------------------------------------------------

    def make_dims(self, data: dict, rehearse: bool) -> dict:
        """The model's own keys of the configuration file: what the
        weights, the program's config, the reference and the roofline
        functions read."""
        return dims_of(data, rehearse)

    def make_weights(self, seed: int):
        """Seeded weights on the device, in the types and the layout
        the program serves; the same arrays go to the reference."""
        return W.decoder_weights(self.dims, seed)

    def program_config(self, name: str):
        """The program's own config object for ``self.dims``."""
        return decoder_config(self.dims, name)

    # -- set-up ----------------------------------------------------------

    def prepare(self, plan: dict) -> None:
        vocab = self.dims["vocab_size"]
        for i, item in enumerate(plan["items"]):
            self._prompts[i] = token_ids(self._rng, item["prompt_len"],
                                         vocab)

    def warm(self, plan: dict) -> None:
        shapes = sorted({(it["prompt_len"], it["new_tokens"])
                         for it in plan["items"]})
        t0 = time.monotonic()
        warm_engine(self.engine, shapes, self._rng, self.log)
        self.parts["warm_s"] = time.monotonic() - t0

    def start(self) -> None:
        self.runner.start()
        self.tap.start()

    # -- load ------------------------------------------------------------

    def submit(self, i: int, item: dict, due) -> None:
        sent = time.monotonic()
        with self._cv:
            self._inflight += 1
        h = self.runner.submit(self._prompts.pop(i), item["new_tokens"],
                               correlation_id=f"{self._tag}{i}")
        self._sent[i] = (sent, due, h)
        h.add_done_callback(lambda hh, i=i: self._finished(i, hh))

    def _finished(self, i: int, handle) -> None:
        try:
            handle.result(0)
            ok = True
        except Exception:
            ok = False
        with self._cv:
            if not self._stopping:     # the stop fails what is left
                self._done[i] = ok
            self._inflight -= 1
            self._cv.notify_all()

    def wants_more(self, plan: dict) -> bool:
        return self._inflight < (plan["outstanding_per_slot"]
                                 * self.engine.num_slots)

    def wait_progress(self, timeout: float) -> None:
        with self._cv:
            self._cv.wait(timeout)

    def is_done(self, i: int) -> bool:
        return i in self._done

    def all_done(self, ids) -> bool:
        return all(i in self._done for i in ids)

    # -- results ---------------------------------------------------------

    def stop(self) -> None:
        self._stopping = True
        self.tap.stop()
        self.runner.stop()
        self.tap.poll()

    def reset(self, tag: str, seed: int | None = None) -> None:
        """For the tools that make several runs in one process: wait
        until the engine is idle, forget the last run's requests and,
        with ``seed``, serve new seeded weights (same programs)."""
        import jax

        self.runner.drain(120.0)
        self.tap.poll()
        self._sent.clear()
        self._done.clear()
        self._prompts.clear()
        self._inflight = 0
        self._tag = tag
        if seed is not None:
            old, self.engine.params = self.weights, None
            self.weights = None
            for leaf in jax.tree.leaves(old):
                leaf.delete()
            self.seed = seed
            self.weights = self.make_weights(seed)
            self.engine.params = self.weights
            self._rng = np.random.default_rng(seed)

    def collect(self, plan: dict) -> dict:
        by_corr = {r["correlation_id"]: r
                   for r in self.tap.engine_requests()}
        requests = []
        for i, (sent, due, _h) in self._sent.items():
            item = plan["items"][i]
            tr = by_corr.get(f"{self._tag}{i}")
            ok = bool(self._done.get(i)) and tr is not None \
                and tr["finish_reason"] in ("length", "eos")
            requests.append({
                "index": i, "phase": item["phase"], "due": due,
                "sent": sent, "prompt_len": item["prompt_len"],
                "ok": ok, "done": i in self._done,
                "first_token_at": tr["first_token_at"] if tr else None,
                "finished_at": tr["finished_at"] if tr else None,
                "admitted_at": tr["admitted_at"] if tr else None,
                "new_tokens": tr["new_tokens"] if tr else 0,
                "stalled_s": tr["stalled_s"] if tr else None,
                "host_s": tr["host_s"] if tr else None,
            })
        return {"requests": requests, "steps": self.tap.step_list(),
                "engine_requests": list(by_corr.values()),
                "engine": {"num_slots": self.engine.num_slots,
                           "steps_per_dispatch":
                               self.engine.decode_window
                               * self.engine.windows_per_dispatch,
                           "errors": self.engine.telemetry.errors}}

    def outcome(self, records: dict, window) -> tuple[int, int]:
        """Open loop: every request DUE in the interval was attempted,
        and one that did not finish well failed. Closed loop: those
        that came to an end, one way or the other, by the stop."""
        from benchmark.harness import stats

        rows = stats.counted(records["requests"], window) or [
            r for r in records["requests"] if r["done"]]
        return len(rows), sum(1 for r in rows if not r["ok"])
