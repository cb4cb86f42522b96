"""The benchmark's own host spans and the compile counter.

Spans are written into the profiler's trace (``jax.profiler.
TraceAnnotation``), where the trace reduction reads them on the device's
clock; with no trace running they cost a few microseconds each."""
from __future__ import annotations

import contextlib
import os
import shutil


class Compiles:
    """Backend compiles, counted from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def window(trace_dir: str | None):
    """The measured window: a ``bench.window`` span, inside a profiler
    trace written to ``trace_dir`` when one is given."""
    import jax
    if trace_dir is None:
        with span("bench.window"):
            yield
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        with span("bench.window"):
            yield
