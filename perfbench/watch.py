"""Listeners the harness hangs on JAX and on the program's log."""
from __future__ import annotations

import logging

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the backend compile seconds JAX reports between laps."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def lap(self) -> tuple:
        """(seconds, programs) compiled since the last lap."""
        out = (self.seconds, self.programs)
        self.seconds, self.programs = 0.0, 0
        return out


class FailureWarnings(logging.Handler):
    """Collects the warnings that mean a save or scan did not run as
    asked: a writer rank failed and was retried, a round committed
    degraded past the fast tier, a scan fell back off the device. In a
    benchmark run each is a failure, not a warning."""

    CODES = ("[CKPT_W_RETRY]", "[CKPT_W_DEGRADED]", "[CDC_W_SCAN]")

    def __init__(self):
        super().__init__(logging.WARNING)
        self.records = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith(self.CODES):
            self.records.append(msg)
