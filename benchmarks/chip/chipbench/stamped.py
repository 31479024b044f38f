"""The sink every app writes its keyed results to, with the time each key
was written: half of ``batch_latency_s``."""
from __future__ import annotations

import time

import jax

from repro.data import NpzDirectorySink


class StampedNpzSink(NpzDirectorySink):
    """``NpzDirectorySink``, with the time each key was first written."""

    def __init__(self, directory: str) -> None:
        super().__init__(directory)
        self.stamps: dict[str, float] = {}

    def write_batch(self, items, **kw):
        with jax.profiler.TraceAnnotation("bench.sink.write"):
            n = super().write_batch(items, **kw)
        now = time.perf_counter()
        for key, _ in items:
            self.stamps.setdefault(key, now)
        return n
