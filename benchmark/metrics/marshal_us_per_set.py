"""bls host: time in the program's `verify/marshal` spans (points, limb
packing) per live set the bus dispatched, in microseconds."""


def read(ctx):
    s, n = ctx["stages"].get("marshal", (0.0, 0))
    if not n or not ctx["live_sets"]:
        return None
    return s / ctx["live_sets"] * 1e6
