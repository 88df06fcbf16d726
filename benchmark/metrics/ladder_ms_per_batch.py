"""kernels: device time of the window-ladder Pallas kernels (G1 and G2
scalar ladders of the random linear combination) per dispatch of a flat
verify program, in milliseconds, from the profiler trace of the window.
Ops are matched by the kernel's name in the trace, so the sum also holds
the ladders of the canary pair's per-set program, which runs once for
every bus batch; the trace does not yet tell the two programs' ops
apart."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx["verify_dispatches"]:
        return None
    t = sum(v for name, v in trace["ops"].items() if "ladder" in name.lower())
    if not t:
        return None
    return t / ctx["verify_dispatches"] * 1e3
