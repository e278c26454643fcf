"""Starts and stops the profiler around a slice of the counted
interval, from a side thread, and hands the trace to the reducer."""

from __future__ import annotations

import glob
import pathlib
import shutil
import threading
import time


class Tracer:
    def __init__(self, out_dir: pathlib.Path):
        self.out_dir = out_dir
        self.t0 = self.t1 = None
        self._thread = None

    def schedule(self, start_at: float, stop_at: float) -> None:
        """Trace [start_at, stop_at] on the monotonic clock."""
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2

        def run():
            time.sleep(max(0.0, start_at - time.monotonic()))
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=opts)
            self.t0 = time.monotonic()
            time.sleep(max(0.0, stop_at - time.monotonic()))
            self.t1 = time.monotonic()
            jax.profiler.stop_trace()

        self._thread = threading.Thread(target=run, name="bench-trace")
        self._thread.start()

    def finish(self) -> dict | None:
        from benchmark.harness import trace_reduce

        if self._thread is None:
            return None
        self._thread.join()
        files = glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        reduced = trace_reduce.reduce_file(files[0])
        reduced["host_window"] = (self.t0, self.t1)
        return reduced
