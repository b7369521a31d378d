"""Device time of the round's local SGD step (the ``local_step`` scope of
``RoundSteps.train_and_mix``) per round, averaged over the chips."""
import layers


def read(trace, ctx):
    return layers.scope_ms_per_round(trace, ctx, ("local_step",))
