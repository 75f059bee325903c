"""Host spans on the profiler's clock, and the one blocking transfer.

``span(name, **meta)`` opens a ``jax.profiler.TraceAnnotation`` named
``receipt.<name>``: under an active profiler the span lands on the
``/host:CPU`` plane of the trace, on the same clock as the device's
``XLA Modules`` line, so a device-idle gap can be put down to the host
phase that was running.  With no profiler active a span costs one C++
check and a clock read.  The profiler holds the spans in memory and
writes them when the trace stops; there is no recorder or exporter here.
``span.seconds`` is the span's wall time once it has closed, which is
what the ``RunStats`` wall timers read.

``fetch(stats, tree, phase)`` is the only place the engine blocks on a
device-to-host transfer: the transfer runs inside ``span("sync",
phase=phase)`` and adds one to ``stats.host_round_trips``, so the counter
and the trace's ``receipt.sync`` events count the same boundary.
"""
from __future__ import annotations

import time

import jax
from jax.profiler import TraceAnnotation

__all__ = ["span", "fetch"]

PREFIX = "receipt."


class span(TraceAnnotation):
    """``with span("cd", dispatch="subset") as s: ...`` then ``s.seconds``.

    Metadata known only inside the span goes in through the inherited
    ``set_metadata(**meta)``.
    """

    def __init__(self, name: str, **meta):
        super().__init__(PREFIX + name, **meta)
        self.seconds = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return super().__enter__()

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        return super().__exit__(*exc)


def fetch(stats, tree, phase: str):
    """``jax.device_get(tree)`` as one counted, traced round trip."""
    with span("sync", phase=phase):
        out = jax.device_get(tree)
    stats.host_round_trips += 1
    return out
