"""Host wall time of staging per chunk: the ``dl.stage`` span of
``Scheduler._stage_xs`` (batch indices, graphs, their copies to the device
and the staging program's dispatch)."""
import layers


def read(trace, ctx):
    return layers.per_chunk_ms(trace, lambda run: layers.span_seconds(run, "dl.stage"))
