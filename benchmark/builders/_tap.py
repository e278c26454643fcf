"""What a builder reads from a GenerationEngine: a tap on its public
``submit``/``step`` (prompt and served tokens per request id), its
request traces and its step records, read incrementally because the
flight recorder is a ring. A step record is copied as the program
wrote it: how many tokens a dispatch handed to requests is the
program's own ``new_tokens``, whatever the dispatch's kind."""

from __future__ import annotations

import threading


class EngineTap:
    def __init__(self, engine):
        self.engine = engine
        self.prompts: dict[int, list[int]] = {}
        self.completions: dict[int, object] = {}
        self.steps: dict[int, dict] = {}
        self.traces: dict[int, object] = {}
        self._stop = threading.Event()
        self._thread = None
        submit, step = engine.submit, engine.step

        def tapped_submit(prompt, *a, **kw):
            rid = submit(prompt, *a, **kw)
            self.prompts[rid] = prompt
            return rid

        def tapped_step():
            comps = step()
            for c in comps:
                self.completions[c.request_id] = c
            return comps

        engine.submit, engine.step = tapped_submit, tapped_step

    def poll(self) -> None:
        tel = self.engine.telemetry
        for r in tel.recorder.records():
            if r.seq not in self.steps:
                # a record without these fields is an error here, not
                # a zero in a rate later
                self.steps[r.seq] = {
                    "seq": r.seq, "kind": r.kind,
                    "t_start": r.t_start, "t_end": r.t_end,
                    "duration_s": r.duration_s, "rows": r.rows,
                    "batch": r.batch, "tokens": r.tokens,
                    "padded_tokens": r.padded_tokens,
                    "new_tokens": r.new_tokens,
                    "prompt_tokens": r.prompt_tokens,
                    "first_use": r.first_use}
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
        return [self.steps[k] for k in sorted(self.steps)]

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
                "stalled_s": tr.stalled_s, "host_s": tr.host_s,
                "prompt": (prompt[-tr.prompt_len:]
                           if prompt is not None else None),
                "tokens": list(comp.tokens) if comp is not None else None,
            })
        return out
