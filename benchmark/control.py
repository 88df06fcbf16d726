#!/usr/bin/env python3
"""The control of the benchmark's correctness check: one run of a cell
with the program's random linear combination replaced by unit scalars.

    python benchmark/control.py --workload <name> --seed <n> --seconds <s>

Batch verification with every scalar 1 accepts a batch whose invalid
signatures cancel (+Delta, -Delta), so it breaks the configuration's
stated guarantee that batch verification is sound per set. The traffic
carries such pairs; the run must come out `"correct": false`. The
program's shapes are unchanged, so the control reuses the compiled
programs of the cell. The benchmark's own runs never run this.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def unit_scalars(n, seed):
    return [1] * n


def main(argv=None):
    run.use_checkout_cache()
    from lighthouse_tpu.bls import tpu_backend

    tpu_backend._rlc_scalars = unit_scalars
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
