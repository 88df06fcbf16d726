"""Signatures made with the benchmark's own arithmetic, in worker processes
that never import JAX or the program.

A set is (message, validators, mod): the signature is
(sum of the validators' secret keys)·H(message), changed by `mod`:

- 0: left valid;
- +1 or -1: plus or minus Delta, a G2 point of the seed's own. Two sets of
  one batch with +1 and -1 form an adversarial pair whose errors cancel
  in an unweighted sum, which only a random linear combination of the
  batch catches;
- TORSION: plus a point of order 13 on the curve of G2, outside G2. Only
  the G2 subgroup check refuses it for sure: in a random linear
  combination it vanishes whenever the set's scalar is a multiple of 13.

The reference verdict of a delivered signature is whether it equals the
valid one, compared as canonical compressed bytes: for a key v + 1 and
a signature in G2, BLS verification holds exactly when the signature is
(v + 1)·H(m), and a signature outside G2 is refused.
"""

from benchmark.crypto import fields as ff
from benchmark.crypto.constants import B_G2, H2, R
from benchmark.crypto.curve import G2
from benchmark.crypto.hash_to_curve import hash_to_g2
from benchmark.crypto.serde import g2_compress_all
from benchmark.registry import secret_key

TORSION = 2
TORSION_ORDER = 13

_torsion = None


def adversary_delta(seed: int):
    tag = b"lighthouse-tpu benchmark adversary " + str(int(seed)).encode()
    return hash_to_g2(tag)


def torsion_point():
    """A point of order 13 on E'(Fp2): y^2 = x^3 + 4(1 + u). The group
    has h2·r points and 13^2 divides h2, so (h2·r / 13^2)·Q lies in the
    13-part for any point Q; the first x = (i, 1) whose point gives a
    nonzero multiple of order 13 fixes it."""
    global _torsion
    if _torsion is not None:
        return _torsion
    p = TORSION_ORDER
    i = 0
    while True:
        x = (i, 1)
        i += 1
        y = ff.fp2_sqrt(ff.fp2_add(ff.fp2_mul(ff.fp2_sqr(x), x), B_G2))
        if y is None:
            continue
        t = G2.mul_scalar((x, y, ff.FP2_ONE), H2 * R // (p * p))
        if not G2.is_infinity(G2.mul_scalar(t, p)):
            t = G2.mul_scalar(t, p)
        if not G2.is_infinity(t):
            _torsion = t
            return t


def sign_sets(task):
    """task = (sets, delta): sets a list of (message, validators, mod);
    returns each set's compressed signature."""
    sets, delta = task
    hashed = {}
    points = []
    for message, validators, mod in sets:
        h = hashed.get(message)
        if h is None:
            h = hashed[message] = hash_to_g2(message)
        sig = G2.mul_scalar(h, sum(secret_key(v) for v in validators))
        if mod == TORSION:
            sig = G2.add(sig, torsion_point())
        elif mod:
            sig = G2.add(sig, delta if mod > 0 else G2.neg(delta))
        points.append(sig)
    return g2_compress_all(points)


def expected_sigs(sets):
    """The valid signature of each (message, validators, mod) set, with
    mod ignored: what the reference compares a delivered one against."""
    return sign_sets(([(m, v, 0) for m, v, _ in sets], None))
