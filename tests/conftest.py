"""Test configuration: force an 8-device virtual CPU mesh before tests run.

Tests run on the CPU (JAX_PLATFORMS=cpu); sharding correctness is
validated on a virtual CPU mesh exactly as the driver's dryrun does. The
chip is reached only through `chip_smoke.py` and `bench.py`.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compilation cache: the pairing/batch-verify graphs are large;
# compile once per machine, reuse across every test session.
from lighthouse_tpu.backend import (  # noqa: E402
    enable_compile_cache,
    force_cpu_backend,
)

enable_compile_cache()
force_cpu_backend(8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration tests"
    )
