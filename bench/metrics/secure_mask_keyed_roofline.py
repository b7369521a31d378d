"""The keyed secure-mask kernel's share of its HBM roofline: the (N, P)
messages each call reads and writes over the summed device time of its
calls.  The threefry work runs on the VPU, whose peak the table does not
give, so HBM bytes bound it and the share reads low."""
import traces as tr
from flops import kernels


def read(trace, ctx):
    ops = tr.kernel_ops(trace, "secure_mask_keyed")
    if not ops:
        return None
    per_call = kernels.secure_mask_keyed(ctx["n_local"], ctx["params_per_node"], ctx["degree"])
    seconds = sum(o.dur for o in ops) / 1e9
    return 100.0 * len(ops) * per_call / ctx["peaks"]["hbm_bytes_per_s"] / seconds
