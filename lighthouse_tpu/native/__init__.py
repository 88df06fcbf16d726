"""Native (C) components with pure-Python fallbacks.

`build()` compiles the _hashtree extension in-place from hashtree.c with
the system toolchain (no pip) whenever the source is newer than the
build; `hash_pairs` resolves to the native implementation when
the extension is present, else the hashlib fallback.
"""

import hashlib
import os
import subprocess
import sysconfig

_HERE = os.path.dirname(__file__)


def _so_path() -> str:
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_HERE, "_hashtree" + suffix)


def build(force: bool = False) -> bool:
    """Compile the extension with cc when it is missing or older than
    its source; returns True when an up-to-date build exists. The build
    writes a temporary file and renames it into place, so a process
    importing concurrently never loads a half-written library."""
    so = _so_path()
    src = os.path.join(_HERE, "hashtree.c")
    try:
        fresh = os.path.getmtime(so) >= os.path.getmtime(src)
    except OSError:
        fresh = False
    if fresh and not force:
        return True
    include = sysconfig.get_paths()["include"]
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        os.environ.get("CC", "cc"),
        "-O3",
        "-shared",
        "-fPIC",
        f"-I{include}",
        src,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=120
        )
        os.replace(tmp, so)
        return True
    # lint: allow(except-swallow): build probe; False selects the
    except Exception:  # pure-python fallback
        return False


def _load():
    if not build():
        return None
    try:
        from lighthouse_tpu.native import _hashtree  # noqa: F401

        return _hashtree
    except ImportError:
        return None


_mod = _load()
NATIVE_AVAILABLE = _mod is not None


def hash_pairs(data: bytes) -> bytes:
    """SHA-256 of each consecutive 64-byte block -> concatenated digests."""
    if _mod is not None:
        return _mod.hash_pairs(data)
    out = bytearray()
    for i in range(0, len(data), 64):
        out += hashlib.sha256(data[i : i + 64]).digest()
    return bytes(out)
