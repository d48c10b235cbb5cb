"""Host spans and counters the runners record around their calls into the
program's layers, and the switch that traces part of a run.

A span adds its host-clock duration to a running total by name and, while
the profiler records, also writes a ``bench:<name>`` annotation into the
trace, so that idle gaps on the device can be named by what the host did.
"""

from __future__ import annotations

import os
import shutil
import time
from contextlib import contextmanager

from bench.tracefile import SPAN_PREFIX, WINDOW_SPAN


class Spans:
    def __init__(self):
        self.total: dict[str, float] = {}
        self.count: dict[str, int] = {}
        self.counters: dict[str, float] = {}

    @contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        try:
            with TraceAnnotation(SPAN_PREFIX + name):
                yield
        finally:
            self.total[name] = self.total.get(name, 0.0) + time.perf_counter() - t0
            self.count[name] = self.count.get(name, 0) + 1

    def add(self, counter: str, value: float = 1.0) -> None:
        self.counters[counter] = self.counters.get(counter, 0.0) + value

    def reset(self) -> None:
        self.total.clear()
        self.count.clear()
        self.counters.clear()


class Tracer:
    """Starts and stops the profiler around the part of the window a runner
    chooses to trace.  Inactive (every call a no-op) unless ``--trace 1``."""

    def __init__(self, active: bool, log_dir: str):
        self.active = active
        self.log_dir = log_dir
        self.started = self.stopped = False
        self._window = None

    def start(self) -> None:
        if not self.active or self.started:
            return
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self.started = True

    def stop(self) -> None:
        if not self.started or self.stopped:
            return
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True
