"""Mesh-scaling measurement for the sharded verifier (VERDICT r4 #9).

Measures `parallel.sharded_verify` on the 8-device virtual CPU mesh:
throughput vs device count along the "sets" axis, and the ring
(recursive-doubling ppermute butterfly) vs gather+fold reduction, at a
fixed GLOBAL batch size. Prints one JSON line per config and a table.

Caveat recorded in every line: a virtual CPU mesh shares one socket's
cores, so absolute numbers measure collective/program STRUCTURE (graph
overhead, reduction shape), not ICI bandwidth — the relative ring vs
gather comparison and the scaling CURVE are the signal, the absolute
sigs/s are not.

Usage: python scripts/mesh_scaling.py [--sets 256] [--reps 5]
"""

import argparse
import datetime
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sets", type=int, default=256)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()

    from lighthouse_tpu.backend import (
        enable_compile_cache,
        force_cpu_backend,
    )

    enable_compile_cache()
    force_cpu_backend(args.devices)

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from lighthouse_tpu import testing as td
    from lighthouse_tpu.parallel.sharded_verify import (
        sharded_verify_signature_sets,
    )

    devices = jax.devices()
    assert len(devices) == args.devices, devices
    batch = td.make_signature_set_batch(
        args.sets, max_keys=1, seed=0, fast_sequential=True
    )

    git_head = os.popen("git -C %s rev-parse --short HEAD" % REPO).read()
    rows = []
    for n in (1, 2, 4, 8):
        if n > args.devices:
            continue
        mesh = Mesh(
            np.array(devices[:n]).reshape(n, 1), ("sets", "keys")
        )
        for ring in (False, True):
            fn = sharded_verify_signature_sets(
                mesh, ring=ring, consumer="bench"
            )
            t0 = time.perf_counter()
            ok = bool(np.asarray(fn(*batch)))
            compile_s = time.perf_counter() - t0
            assert ok, f"n={n} ring={ring}: batch failed to verify"
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*batch))
                times.append(time.perf_counter() - t0)
            p50 = sorted(times)[len(times) // 2]
            rec = {
                "metric": "sharded_verify_throughput",
                "value": round(args.sets / p50, 2),
                "unit": "sigs/sec",
                "platform": "cpu-mesh",
                "n_devices": n,
                "reduction": "ring" if ring else "gather_fold",
                "n_sets": args.sets,
                "p50_s": round(p50, 4),
                "compile_s": round(compile_s, 1),
                "caveat": "virtual CPU mesh: structure signal only",
                "recorded_at": datetime.datetime.now(
                    datetime.timezone.utc
                ).isoformat(timespec="seconds"),
                "git_head": git_head.strip(),
            }
            rows.append(rec)
            print(json.dumps(rec))
            print(
                f"n={n} ring={int(ring)}: {rec['value']:>9} sigs/s "
                f"(p50 {rec['p50_s']}s, compile {rec['compile_s']}s)"
            )
    # summary table
    print("\ndevices | gather_fold | ring")
    by = {
        (r["n_devices"], r["reduction"]): r["value"] for r in rows
    }
    for n in (1, 2, 4, 8):
        if (n, "gather_fold") in by:
            print(
                f"{n:7} | {by[(n, 'gather_fold')]:11} | "
                f"{by.get((n, 'ring'), '-')}"
            )


if __name__ == "__main__":
    main()
