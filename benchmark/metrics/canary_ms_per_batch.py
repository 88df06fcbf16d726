"""device plane: time in the program's `verify/canary` spans (the
known-answer sentinel pair checked before every bus batch: its subgroup
check, marshal and per-set device call) per bus batch, in
milliseconds."""


def read(ctx):
    s, n = ctx["stages"].get("canary", (0.0, 0))
    if not n or not ctx["bus"]["batches"]:
        return None
    return s / ctx["bus"]["batches"] * 1e3
