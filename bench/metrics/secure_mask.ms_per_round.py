"""Device time of the keyed secure-mask kernel per round, averaged over
the chips."""
import traces as tr


def read(trace, ctx):
    ops = tr.kernel_ops(trace, "secure_mask_keyed")
    if not ops or not ctx["rounds"]:
        return None
    return sum(o.dur for o in ops) / 1e6 / len(trace.devices()) / ctx["rounds"]
