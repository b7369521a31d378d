"""Device time of the transformer blocks' MLP per round (the ``mlp`` scope
of ``models/vit.py``: fc1, GELU and fc2, in the forward pass, its
recomputation and the backward pass), averaged over the chips.  Silent for
a model without that scope."""
import model_scopes


def read(trace, ctx):
    return model_scopes.scope_ms_per_round(trace, ctx, "mlp")
