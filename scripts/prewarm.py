"""Pre-warm the repo-local JAX compilation cache (.jax_cache) for the
driver's multi-chip dryrun check (virtual CPU mesh). Several device
counts run one child process each; every child is on the CPU, so none
competes for a chip.

Run: python scripts/prewarm.py [n_devices ...]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__  # noqa: E402  (enables the repo-local compile cache)


def main():
    counts = [int(a) for a in sys.argv[1:]] or [8]
    if len(counts) == 1:
        t0 = time.time()
        __graft_entry__.dryrun_multichip(counts[0])
        print(f"dryrun_multichip({counts[0]}) ok in {time.time() - t0:.1f}s")
        return
    # XLA_FLAGS (device count) is parsed once per process — run each
    # count in its own subprocess.
    import subprocess

    for n in counts:
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), str(n)], check=True
        )


if __name__ == "__main__":
    main()
