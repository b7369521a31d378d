"""Device bytes in use after set-up, before the window, on the fullest
chip: the node state, the dataset and what the program keeps besides."""


def read(trace, ctx):
    b = ctx.get("resident_bytes")
    return None if not b else b / 1e9
