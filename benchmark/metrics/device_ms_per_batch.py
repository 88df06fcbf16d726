"""device plane: the program's `verify/device` span (host clock around a
guarded dispatch and its forced verdict) per device call, in
milliseconds. Each bus batch makes two such calls: the canary pair and
the batch itself."""


def read(ctx):
    s, n = ctx["stages"].get("device", (0.0, 0))
    if not n:
        return None
    return s / n * 1e3
