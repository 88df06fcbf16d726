"""kernels: device time of the fused Miller-loop Pallas kernel
(`miller_loop_pallas`) per dispatch of a flat verify program, in
milliseconds, from the profiler trace of the window. Ops are matched by
name, so the sum also holds the Miller loop of the canary pair's
per-set program, which runs once for every bus batch; the trace does not
yet tell the two programs' ops apart."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx["verify_dispatches"]:
        return None
    t = sum(v for name, v in trace["ops"].items() if "miller_loop" in name)
    if not t:
        return None
    return t / ctx["verify_dispatches"] * 1e3
