"""Host spans: named, timed sections of the engine's host path.

``spans.span("stage", rnd=8)`` opens ``jax.profiler.TraceAnnotation
("dl.stage", rnd=8)`` — in a profiler trace it lies on the same clock as
the device's ops, and with no profiler running it is inert — and adds the
block's wall seconds to ``spans["stage"]``, so a run knows its host split
without a trace.  Spans nest; a child inherits its parent's stats by
nesting.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict

import jax


class HostSpans(dict):
    """Wall seconds spent in each span, by name; ``counts[name]`` is how
    often it closed."""

    def __init__(self):
        super().__init__()
        self.counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str, **stats):
        """Time the block as ``dl.<name>``; yields the annotation, whose
        ``set_metadata(**stats)`` adds stats known only at the end."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dl." + name, **stats) as ann:
            try:
                yield ann
            finally:
                self[name] = self.get(name, 0.0) + time.perf_counter() - t0
                self.counts[name] = self.counts.get(name, 0) + 1
