"""The round step's share of the chips' bf16 peak: the model's forward and
backward operations per sample times the samples trained in the traced
window, over the window, chips and peak.  Mixing does not count."""


def read(trace, ctx):
    flops = ctx["flops"].train_flops(ctx["config"]["model"])
    samples = ctx["rounds"] * ctx["n_nodes"] * ctx["config"]["batch_size"] * ctx["config"]["local_steps"]
    return 100.0 * flops * samples / ctx["window_s"] / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
