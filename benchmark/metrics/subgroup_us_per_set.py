"""bls host: time in the program's `verify/subgroup_check` spans per live
set the bus dispatched, in microseconds (stage-histogram sum over the
window)."""


def read(ctx):
    s, n = ctx["stages"].get("subgroup_check", (0.0, 0))
    if not n or not ctx["live_sets"]:
        return None
    return s / ctx["live_sets"] * 1e6
