"""Reduce a JAX profiler trace (`.xplane.pb`) of one window to numbers.

- the window: the host span named `bench/window`;
- device busy time: the union of the intervals in which an operation ran
  on a device, clipped to the window, averaged over the devices traced;
- per-op device time: summed durations by op name;
- idle gaps: the complement of the busy intervals in the window, each
  attributed to the benchmark's own host span (`bench/...`) that overlaps
  it most, "none" where no span does.

Device operations are the events of each `/device:` plane's op line. A
trace of the CPU backend has no such plane; there the ops are the host
events that carry an `hlo_op` stat, which lets a CPU trace check this
arithmetic (a CPU trace gives no device numbers).
"""

import glob
import os

WINDOW = "bench/window"
SPAN_PREFIX = "bench/"
OP_LINES = ("XLA Ops",)
TOP = 10


def op_name(text):
    """A TPU op event is named by its HLO instruction text
    ("%fusion.426 = s32[...] fusion(...)"); keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def collect(planes):
    """-> (device ops per device [[(name, start, end)]], host spans
    [(name, start, end)], a summary of the planes and lines seen).
    `planes` is an iterable of objects with `.name` and `.lines`, each
    line with `.name` and `.events` (name, start_ns, duration_ns,
    stats), as `jax.profiler.ProfileData` gives them."""
    devices, spans, summary = [], [], {}
    host_ops = []
    for plane in planes:
        lines = list(plane.lines)
        summary[plane.name] = sorted({ln.name for ln in lines})[:12]
        is_device = plane.name.startswith("/device:")
        ops = []
        for line in lines:
            take_ops = is_device and line.name in OP_LINES
            for ev in line.events:
                s = ev.start_ns
                e = s + ev.duration_ns
                if take_ops:
                    ops.append((op_name(ev.name), s, e))
                elif not is_device:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, s, e))
                    elif ev.duration_ns > 0 and "hlo_op" in dict(ev.stats):
                        host_ops.append((ev.name, s, e))
        if is_device and ops:
            devices.append(ops)
    if not devices and host_ops:
        devices = [host_ops]
    return devices, spans, summary


def reduce(planes):
    devices, spans, summary = collect(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = windows[0]
    window_s = (w1 - w0) / 1e9
    per_op = {}
    busy_ns = []
    busy_all = []
    for ops in devices:
        ivs = _union(_clip([(s, e) for _, s, e in ops], w0, w1))
        busy_ns.append(sum(e - s for s, e in ivs))
        busy_all.append(ivs)
        for name, s, e in ops:
            lo, hi = max(s, w0), min(e, w1)
            if hi > lo:
                per_op[name] = per_op.get(name, 0.0) + (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else 0.0
    # idle gaps of the first device, by what the host was doing
    gaps = []
    ivs = busy_all[0] if busy_all else []
    cursor = w0
    for s, e in ivs + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    host = [(n, s, e) for n, s, e in spans if n != WINDOW]
    named = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, best_ov = "none", 0
        for n, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = n, ov
        named.append([best, (g1 - g0) / 1e9])
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "n_devices": len(devices),
        "ops": per_op,
        "device_ops": [[n, v] for n, v in top_ops],
        "idle_gaps": named,
        "planes": summary,
    }


def reduce_file(path):
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path).planes)


def reduce_dir(log_dir):
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` dir."""
    paths = glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_file(max(paths, key=os.path.getmtime))
