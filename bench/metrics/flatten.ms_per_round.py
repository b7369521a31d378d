"""Device time of the flat layout per round: node parameters flattened to
the (N, P) rows the sharing strategy mixes and cut back into leaves (the
``flatten`` and ``unflatten`` scopes), averaged over the chips."""
import layers


def read(trace, ctx):
    return layers.scope_ms_per_round(trace, ctx, ("flatten", "unflatten"))
