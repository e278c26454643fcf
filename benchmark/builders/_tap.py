"""What a builder reads from a GenerationEngine: a tap on its public
``submit``/``step`` (prompt and served tokens per request id), its
request traces and its step records, read incrementally because the
flight recorder is a ring."""

from __future__ import annotations

import threading
import time


class EngineTap:
    def __init__(self, engine):
        self.engine = engine
        self.prompts: dict[int, list[int]] = {}
        self.completions: dict[int, object] = {}
        self.steps: dict[int, dict] = {}
        self.traces: dict[int, object] = {}
        self.piggy: dict[int, tuple[int, int]] = {}
        self.wall_minus_mono = time.time() - time.monotonic()
        self._stop = threading.Event()
        self._thread = None
        submit, step = engine.submit, engine.step

        def tapped_submit(prompt, *a, **kw):
            rid = submit(prompt, *a, **kw)
            self.prompts[rid] = prompt
            return rid

        def tapped_step():
            before = engine.piggy_tokens, engine.piggy_rows
            comps = step()
            if engine.piggy_tokens != before[0]:
                # this call's piggyback dispatch prefilled whole
                # prompts: its record, the newest of that kind, counts
                # their tokens among its own and leaves out the first
                # token it sampled for each
                for r in reversed(engine.telemetry.recorder.records()):
                    if r.kind == "piggyback":
                        self.piggy[r.seq] = (
                            engine.piggy_tokens - before[0],
                            engine.piggy_rows - before[1])
                        break
            for c in comps:
                self.completions[c.request_id] = c
            return comps

        engine.submit, engine.step = tapped_submit, tapped_step

    def poll(self) -> None:
        tel = self.engine.telemetry
        for r in tel.recorder.records():
            if r.seq not in self.steps:
                self.steps[r.seq] = {
                    "seq": r.seq, "kind": r.kind,
                    "t_end": r.t_wall - self.wall_minus_mono,
                    "duration_s": r.duration_s, "rows": r.rows,
                    "batch": r.batch, "tokens": r.tokens,
                    "padded_tokens": r.padded_tokens}
        for tr in list(tel.completed):
            self.traces[tr.request_id] = tr

    def start(self, period: float = 0.5) -> None:
        def loop():
            while not self._stop.wait(period):
                self.poll()
        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="bench-tap")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
        self.poll()

    def step_list(self) -> list[dict]:
        out = []
        for k in sorted(self.steps):
            s = self.steps[k]
            if s["kind"] == "piggyback":
                prompt, first = self.piggy.get(k, (0, 0))
                s = dict(s, prompt_tokens=prompt, first_tokens=first)
            out.append(s)
        return out

    def engine_requests(self) -> list[dict]:
        """One record per request the engine retired: for the layer
        metrics and the correctness sample."""
        out = []
        for rid, tr in self.traces.items():
            comp = self.completions.get(rid)
            prompt = self.prompts.get(rid)
            out.append({
                "rid": rid, "correlation_id": tr.correlation_id,
                "prompt_len": tr.prompt_len,
                "enqueued_at": tr.enqueued_at,
                "admitted_at": tr.admitted_at,
                "first_token_at": tr.first_token_at,
                "finished_at": tr.finished_at,
                "new_tokens": tr.new_tokens,
                "finish_reason": tr.finish_reason,
                "prompt": (prompt[-tr.prompt_len:]
                           if prompt is not None else None),
                "tokens": list(comp.tokens) if comp is not None else None,
            })
        return out
