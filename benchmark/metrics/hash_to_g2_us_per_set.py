"""bls host: time in the program's `verify/marshal/hash_to_g2` spans (one
for each hash-to-G2 memo miss, inside `verify/marshal/points`) per live
set the bus dispatched, in microseconds."""


def read(ctx):
    s, n = ctx["stages"].get("marshal/hash_to_g2", (0.0, 0))
    if not n or not ctx["live_sets"]:
        return None
    return s / ctx["live_sets"] * 1e6
