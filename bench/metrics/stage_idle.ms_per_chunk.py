"""Device-idle time under the ``dl.stage`` span per chunk (idle as
``idle_share`` counts it), averaged over the chips: how long the chip
waits on the host while it stages the next chunk."""
import layers


def read(trace, ctx):
    return layers.per_chunk_ms(trace, lambda run: layers.idle_under(run, "dl.stage"))
