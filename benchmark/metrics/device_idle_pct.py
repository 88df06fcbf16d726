"""device: share of the window in which no operation ran on the device,
in percent, from the profiler trace (1 - union of op intervals / window)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["n_devices"] or not trace["window_s"]:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
