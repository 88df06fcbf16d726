"""Compressed encodings (ZCash format, as on the wire) and batched affine
conversion for the benchmark's own points.

Compression is canonical: one point has one 48- or 96-byte encoding, and
the program's decoder refuses every other. So two encodings are equal
exactly when the points are, which is what the reference compares.
"""

from .constants import P
from .curve import G1, G2

COMPRESSION_FLAG = 0x80
INFINITY_FLAG = 0x40
SORT_FLAG = 0x20
HALF_P = (P - 1) // 2


def batch_to_affine(group, points):
    """Jacobian points -> affine pairs with one field inversion in all
    (Montgomery's simultaneous inversion). Infinity -> None."""
    F = group.F
    keep = [i for i, pt in enumerate(points) if not group.is_infinity(pt)]
    out = [None] * len(points)
    if not keep:
        return out
    prefix = []
    acc = F.one
    for i in keep:
        acc = F.mul(acc, points[i][2])
        prefix.append(acc)
    inv = F.inv(prefix[-1])
    for j in range(len(keep) - 1, -1, -1):
        i = keep[j]
        zi = F.mul(inv, prefix[j - 1]) if j else inv
        inv = F.mul(inv, points[i][2])
        zi2 = F.sqr(zi)
        x, y, _ = points[i]
        out[i] = (F.mul(x, zi2), F.mul(y, F.mul(zi2, zi)))
    return out


def g1_compress_affine(aff) -> bytes:
    if aff is None:
        return bytes([COMPRESSION_FLAG | INFINITY_FLAG]) + b"\x00" * 47
    x, y = aff
    data = bytearray(x.to_bytes(48, "big"))
    data[0] |= COMPRESSION_FLAG | (SORT_FLAG if y > HALF_P else 0)
    return bytes(data)


def g2_compress_affine(aff) -> bytes:
    if aff is None:
        return bytes([COMPRESSION_FLAG | INFINITY_FLAG]) + b"\x00" * 95
    (x0, x1), (y0, y1) = aff
    larger = y1 > HALF_P if y1 else y0 > HALF_P
    data = bytearray(x1.to_bytes(48, "big") + x0.to_bytes(48, "big"))
    data[0] |= COMPRESSION_FLAG | (SORT_FLAG if larger else 0)
    return bytes(data)


def g2_compress_all(points) -> list:
    return [g2_compress_affine(a) for a in batch_to_affine(G2, points)]


def g1_compress_all(points) -> list:
    return [g1_compress_affine(a) for a in batch_to_affine(G1, points)]
