#!/bin/sh
# Run the test suite on the virtual CPU mesh (JAX_PLATFORMS=cpu).
#
# With arguments: one pytest invocation, args passed through.
# Without: each test FILE runs in its own pytest process — a jax
# compile-cache serialization segfault (observed on this host writing a
# freshly-compiled large pairing executable, killing the whole run at 50%)
# must cost one file, not the suite. Files run sequentially: concurrent
# pytest processes compiling fresh entries into the same per-host cache
# directory is exactly the observed crash condition.
if [ $# -gt 0 ]; then
    # args pass through with the caller's cwd untouched (relative paths
    # keep resolving exactly as before)
    exec env JAX_PLATFORMS=cpu python -m pytest "$@" -q
fi

cd "$(dirname "$0")/.." || exit 1
rc=0
for f in tests/test_*.py; do
    env JAX_PLATFORMS=cpu python -m pytest "$f" -q || rc=1
done
exit $rc
