"""Device time of the transformer blocks' self-attention per round (the
``attention`` scope of ``models/vit.py``: the qkv matrix, scores, softmax,
weighted values and output projection, in the forward pass, its
recomputation and the backward pass), averaged over the chips.  Silent for
a model without that scope."""
import model_scopes


def read(trace, ctx):
    return model_scopes.scope_ms_per_round(trace, ctx, "attention")
