"""The validator registry a deployment holds: N pubkeys, built once per
checkout and then read back from the benchmark's cache.

Validator v has the secret key v + 1 and the pubkey (v + 1)·G1, made by
running additions of the generator (one group addition per validator, as
the program's `testing.make_api_signature_sets` builds its keys), so the
benchmark can sign for any validator without storing secret keys. The
registry depends on N alone, never on the seed.

File layout: per validator 48 bytes of affine x, 48 of affine y
(big-endian) and its 48-byte compressed encoding.
"""

import os

from benchmark.crypto.curve import G1
from benchmark.crypto.serde import batch_to_affine, g1_compress_affine

RECORD = 144


def secret_key(validator: int) -> int:
    return validator + 1


def _build_chunk(args) -> bytes:
    start, count = args
    pt = G1.mul_scalar(G1.generator, secret_key(start))
    pts = []
    for _ in range(count):
        pts.append(pt)
        pt = G1.add(pt, G1.generator)
    out = bytearray()
    for aff in batch_to_affine(G1, pts):
        out += aff[0].to_bytes(48, "big") + aff[1].to_bytes(48, "big")
        out += g1_compress_affine(aff)
    return bytes(out)


def path_for(cache_dir: str, n: int) -> str:
    return os.path.join(cache_dir, "registry", f"g1-{n}.bin")


def build(cache_dir: str, n: int, pool, chunk: int = 16384) -> str:
    """Write the registry file of `n` validators (if absent) using the
    worker `pool`; returns its path."""
    path = path_for(cache_dir, n)
    if os.path.exists(path) and os.path.getsize(path) == n * RECORD:
        return path
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp"
    jobs = [(s, min(chunk, n - s)) for s in range(0, n, chunk)]
    with open(tmp, "wb") as f:
        for part in pool.imap(_build_chunk, jobs):
            f.write(part)
    os.replace(tmp, path)
    return path


def load(path: str):
    """-> (affine points [(x, y)], compressed encodings [bytes])."""
    with open(path, "rb") as f:
        buf = f.read()
    n = len(buf) // RECORD
    frm = int.from_bytes
    points = [
        (frm(buf[o:o + 48], "big"), frm(buf[o + 48:o + 96], "big"))
        for o in range(0, n * RECORD, RECORD)
    ]
    compressed = [buf[o + 96:o + RECORD] for o in range(0, n * RECORD, RECORD)]
    return points, compressed
