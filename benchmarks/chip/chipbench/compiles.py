"""Counts the XLA programs a process asks for (``jax.monitoring``)."""
from __future__ import annotations

from typing import Any

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts the programs XLA was asked for: compiled, or loaded from the
    persistent cache. A program that appears inside a measured window is
    counted whether or not the cache had it."""

    def __init__(self, jax: Any) -> None:
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration_secs: float, **_: Any) -> None:
        if event == _COMPILE_EVENT:
            self.programs += 1
