"""bls host: time in the program's `verify/marshal/pubkeys` spans (a
batch's pubkey slots, as HBM-table indices or packed key by key, inside
`verify/marshal/pack`) per live set the bus dispatched, in
microseconds."""


def read(ctx):
    s, n = ctx["stages"].get("marshal/pubkeys", (0.0, 0))
    if not n or not ctx["live_sets"]:
        return None
    return s / ctx["live_sets"] * 1e6
