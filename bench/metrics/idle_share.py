"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips."""
import traces as tr


def read(trace, ctx):
    if not trace.ops:
        return None
    return 100.0 * tr.idle_share(trace)
