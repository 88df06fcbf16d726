#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration and traffic mix) is looked up by name in
BENCHMARK.json. Set-up (registry, device table, traffic, every program
the traffic reaches) is timed from process start as `setup_s`; then the
window runs for `--seconds`, the answers are checked against the
benchmark's own reference, and the last line of standard output is one
JSON object: `correct`, `attempted`, `failed`, `metrics`, `device`
(with `--trace 1` also `breakdown`), and last `checks`, each compared
number beside its limit, which also end standard error.

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, read from a profiler trace of the window and from the
program's spans and counters. Without a TPU with the chips the cell asks
for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_checkout_cache():
    """Put the benchmark and the persistent compile cache in reach: the
    cache lives at one fixed place in the checkout. Call before JAX is
    imported."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, "benchmark", "cache", "jax"
    )


def require_tpu(cell, workload):
    """The JAX devices, or None (with the reason on stderr) when there is
    no TPU with the chips the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"bench: {workload} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return None
    return devices


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_checkout_cache()
    from benchmark import harness

    spec = harness.load_spec()
    cell, cfg, mix = harness.resolve(spec, args.workload)
    devices = require_tpu(cell, args.workload)
    if devices is None:
        return 3
    result = harness.run_cell(
        spec, cell, cfg, mix, args.seed, args.seconds, args.trace,
        T_START, devices,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
