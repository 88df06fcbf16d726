"""JAX backend helpers: the persistent compile cache and the virtual CPU
mesh that tests and dryruns run on.

`force_cpu_backend` flips a process onto N virtual CPU devices in
process: update the `jax_platforms` config, set the forced-host-device-
count XLA flag *before* the CPU client is instantiated, then
`clear_backends()` so the next `jax.devices()` re-resolves onto the CPU
devices. XLA_FLAGS is parsed once, at first client creation — call it
before anything queries `jax.devices()` / `jax.default_backend()` or runs
a computation.
"""

import os
import re


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache. Where the caller sets
    JAX_COMPILATION_CACHE_DIR, JAX uses that directory and nothing here
    overrides it; otherwise the cache lives at the fixed
    `<checkout>/.jax_cache/<host fingerprint>` (a fixed path: the path is
    part of the cache's key, so a moving directory never hits)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache",
                _host_fingerprint(),
            ),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def _host_fingerprint() -> str:
    """Per-host cache subdirectory key. The jax CPU AOT cache key does
    NOT fully capture the host's CPU features: an entry compiled on a
    machine with different vector extensions SIGSEGVs on load here
    (observed: a cache populated on an amx/avx10-capable builder crashed
    pytest on this host inside get_executable_and_time). Keying the
    directory by the CPU-flag set makes entries from other machines
    invisible instead of fatal."""
    import hashlib
    import platform

    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    digest = hashlib.sha256(
        (platform.machine() + "|" + flags).encode()
    ).hexdigest()[:16]
    return f"host_{digest}"


def force_cpu_backend(n_devices: int = 8) -> None:
    """Flip this process onto `n_devices` virtual CPU devices.

    Idempotent; raises RuntimeError if the device count cannot be
    materialized (XLA_FLAGS already parsed by an existing CPU client).
    """
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    # NOTE: do NOT lower --xla_backend_optimization_level here. With the
    # scan-rolled crypto graphs, default optimization both compiles faster
    # (fewer instructions survive to the backend) and runs ~500x faster
    # (fusion collapses the per-op dispatch overhead that dominates the
    # field-op bodies).
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        existing = int(
            re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
            .group(1)
        )
        if existing < n_devices:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags
            )
            os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()

    from jax.extend.backend import clear_backends

    jax.config.update("jax_platforms", "cpu")
    clear_backends()
    if jax.device_count() < n_devices or jax.devices()[0].platform != "cpu":
        raise RuntimeError(
            f"force_cpu_backend: wanted {n_devices} CPU devices, got "
            f"{jax.devices()} (XLA_FLAGS was parsed before the override)"
        )
