"""Deterministic synthetic data pipeline.

Stateless: ``batch_for_step(step)`` derives every batch from (seed, step), so
checkpoint/restart and elastic rescaling never need data-state checkpoints —
restarting at step k regenerates exactly the batch stream from k.  A
background prefetch thread keeps ``depth`` batches ready; an error making a
batch is raised by the ``next()`` that would have handed it out.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass

import numpy as np

from .. import telemetry
from ..configs.base import ArchConfig, ShapeSpec


@dataclass(frozen=True)
class DataSpec:
    cfg: ArchConfig
    shape: ShapeSpec
    seed: int = 0
    # Multi-host sharding: this process owns batch rows
    # [process_index::process_count] (single-process here, but the layout
    # matches jax.process_index() usage on real pods).
    process_index: int = 0
    process_count: int = 1


def batch_for_step(spec: DataSpec, step: int) -> dict:
    """Deterministic batch for a global step (numpy, host-side)."""
    cfg, shape = spec.cfg, spec.shape
    rng = np.random.default_rng(np.uint64(spec.seed * 1_000_003 + step))
    B, S = shape.global_batch, shape.seq_len
    rows = range(spec.process_index, B, spec.process_count)
    nb = len(list(rows))

    if cfg.family == "audio":
        return {
            "frames": rng.standard_normal((nb, S, cfg.d_model), dtype=np.float32),
            "labels": rng.integers(0, cfg.vocab, (nb, S), dtype=np.int32),
        }
    batch = {"tokens": rng.integers(0, cfg.vocab, (nb, S), dtype=np.int32)}
    if cfg.family == "vlm":
        batch["image_embeds"] = rng.standard_normal(
            (nb, cfg.img_tokens, cfg.d_model), dtype=np.float32
        )
    return batch


class Prefetcher:
    """Background-thread batch prefetch with bounded depth.

    Each ``next()`` is a ``data.wait`` span, tagged with the step it hands
    out."""

    def __init__(self, spec: DataSpec, start_step: int, depth: int = 2):
        self.spec = spec
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._step = start_step
        self._error: Exception | None = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        step = self._step
        while not self._stop.is_set():
            try:
                item = (step, batch_for_step(self.spec, step))
            except Exception as e:  # handed to next(), which raises it
                item = (step, e)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if isinstance(item[1], Exception):
                return
            step += 1

    def next(self) -> tuple[int, dict]:
        if self._error is not None:
            raise self._error
        with telemetry.span("data.wait") as rec:
            step, batch = self._q.get()
            rec.step = step
        if isinstance(batch, Exception):
            self._error = batch
            raise batch
        return step, batch

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2.0)
