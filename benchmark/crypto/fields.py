"""Pure-Python BLS12-381 base field Fp and its quadratic extension Fp2.

Fp  : ints mod P
Fp2 : (c0, c1) = c0 + c1*u, u^2 = -1

Copied from the program's reference tower so that the benchmark's traffic
and reference verdicts stand apart from the code under test.
"""

from .constants import P

# ---------------------------------------------------------------- Fp


def fp_add(a, b):
    return (a + b) % P


def fp_sub(a, b):
    return (a - b) % P


def fp_mul(a, b):
    return (a * b) % P


def fp_neg(a):
    return (-a) % P


def fp_inv(a):
    return pow(a, -1, P)


def fp_sqrt(a):
    """Square root in Fp (p % 4 == 3). Returns None if no root exists."""
    root = pow(a, (P + 1) // 4, P)
    return root if root * root % P == a % P else None


# ---------------------------------------------------------------- Fp2

FP2_ZERO = (0, 0)
FP2_ONE = (1, 0)


def fp2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def fp2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def fp2_neg(a):
    return ((-a[0]) % P, (-a[1]) % P)


def fp2_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    return ((a0 * b0 - a1 * b1) % P, (a0 * b1 + a1 * b0) % P)


def fp2_sqr(a):
    a0, a1 = a
    # (a0 + a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def fp2_scalar(a, k):
    return (a[0] * k % P, a[1] * k % P)


def fp2_conj(a):
    return (a[0], (-a[1]) % P)


def fp2_inv(a):
    a0, a1 = a
    norm_inv = pow(a0 * a0 + a1 * a1, -1, P)
    return (a0 * norm_inv % P, (-a1) * norm_inv % P)


def fp2_pow(a, e):
    result = FP2_ONE
    base = a
    while e > 0:
        if e & 1:
            result = fp2_mul(result, base)
        base = fp2_sqr(base)
        e >>= 1
    return result


def fp2_mul_by_xi(a):
    # (c0 + c1 u)(1 + u) = (c0 - c1) + (c0 + c1) u
    return ((a[0] - a[1]) % P, (a[0] + a[1]) % P)


def fp2_sqrt(a):
    """Square root in Fp2 via the p % 4 == 3 method. None if no root."""
    if a == FP2_ZERO:
        return FP2_ZERO
    cand = fp2_pow(a, (P * P + 7) // 16)
    # cand^2 = a * s where s^8 = 1; fix up by multiplying cand with an 8th
    # root of unity t such that (cand*t)^2 == a.
    roots = _eighth_roots_of_unity()
    for t in roots:
        r = fp2_mul(cand, t)
        if fp2_sqr(r) == (a[0] % P, a[1] % P):
            return r
    return None


_EIGHTH_ROOTS = None


def _eighth_roots_of_unity():
    global _EIGHTH_ROOTS
    if _EIGHTH_ROOTS is None:
        # u has order 4 (u^2 = -1); powers of u give the 4th roots of unity.
        roots = [FP2_ONE]
        for _ in range(3):
            roots.append(fp2_mul(roots[-1], (0, 1)))
        # An 8th root: sqrt(u) = (a, -a) with a^2 = -1/2. Since P % 8 == 3,
        # both -1 and 2 are non-residues in Fp, hence -1/2 IS a residue.
        a = pow((-pow(2, -1, P)) % P, (P + 1) // 4, P)
        assert a * a % P == (-pow(2, -1, P)) % P
        eighth = (a, P - a)
        assert fp2_sqr(eighth) == (0, 1)
        roots = roots + [fp2_mul(r, eighth) for r in roots]
        _EIGHTH_ROOTS = roots
    return _EIGHTH_ROOTS
