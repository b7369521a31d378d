"""Device time of batch staging per chunk: the ``jit_stage_batches``
program that gathers the chunk's batches from the dataset on the device,
averaged over the chips."""
import layers


def read(trace, ctx):
    return layers.per_chunk_ms(trace, lambda run: layers.program_seconds(run, layers.STAGE) or None)
