"""Helpers every driver shares: the traced part of a window, the sample
of outputs kept for the check, and percentiles."""

from __future__ import annotations

import random
from contextlib import contextmanager
from pathlib import Path
from typing import List, Optional

#: seconds of a window that a traced run records (its middle part)
TRACE_SECONDS = 6.0


class TraceWindow:
    """Starts the profiler in the middle of the window and stops it
    ``TRACE_SECONDS`` later, inside a ``bench.window`` host span.  Drivers
    call ``poll(now)`` as they go and ``close()`` when the window ends;
    with ``trace_dir=None`` it does nothing."""

    def __init__(self, trace_dir: Optional[Path], t_open: float,
                 seconds: float):
        self.dir = trace_dir
        span = min(TRACE_SECONDS, seconds)
        self.start_at = t_open + 0.5 * (seconds - span)
        self.stop_at = self.start_at + span
        self.on = False
        self.done = trace_dir is None
        self._span = None

    def poll(self, now: float) -> None:
        if self.done:
            return
        if not self.on and now >= self.start_at:
            import jax
            jax.profiler.start_trace(str(self.dir))
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.on = True
        elif self.on and now >= self.stop_at:
            self.close()

    def close(self) -> None:
        if self.on and not self.done:
            import jax
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.done = True
        self.on = False


@contextmanager
def span(name: str, on: bool):
    """A ``bench.<name>`` host span in the trace, while tracing."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


class Reservoir:
    """A uniform sample of at most ``k`` items from a stream, drawn from
    the seed (Algorithm R)."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen, self.items = k, 0, []
        self.rng = random.Random(seed)

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = self.rng.randrange(self.seen)
            if j < self.k:
                self.items[j] = item


def percentile(values: List[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))
