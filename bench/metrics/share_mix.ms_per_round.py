"""Device time of the sharing strategy's round (the ``share_mix`` scope:
for full sharing the gossip operand's build and ``gossip_mix_nodes``, for
secure sharing the masks, the neighbour stack and its sum) per round,
averaged over the chips."""
import layers


def read(trace, ctx):
    return layers.scope_ms_per_round(trace, ctx, ("share_mix",))
