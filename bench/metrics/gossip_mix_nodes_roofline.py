"""The gossip kernel's share of its HBM roofline: the bytes each call must
move (the (1 + D, N, P) slot stack read, the (N, P) mix written) over the
summed device time of its calls, against the chip's HBM bandwidth."""
import traces as tr
from flops import kernels


def read(trace, ctx):
    ops = tr.kernel_ops(trace, "gossip_mix_nodes")
    if not ops:
        return None
    per_call = kernels.gossip_mix_nodes(ctx["n_local"], ctx["params_per_node"], ctx["degree"])
    seconds = sum(o.dur for o in ops) / 1e9
    return 100.0 * len(ops) * per_call / ctx["peaks"]["hbm_bytes_per_s"] / seconds
