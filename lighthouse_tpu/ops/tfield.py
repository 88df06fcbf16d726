"""Transposed ("batch-last") BLS12-381 field arithmetic for TPU kernels.

Layout: a bundle is an int32 array `(S_slots, NB, B)` — slots lead, the
12-bit limb axis is second-to-last (sublanes), and the BATCH axis B is last
(lanes). With B a multiple of 128 every elementwise op runs at full VPU
lane utilization, unlike the batch-leading layout in ops.fieldb whose
33-limb trailing axis wastes 3/4 of each vector register row.

Functions here are pure jnp and run in two modes:
  * directly under jit (XLA level), via ops.tpairing;
  * inside a Pallas TPU kernel (ops.pallas_pairing), where the whole
    Miller loop stays in VMEM.

The arithmetic, bounds, and relaxed-limb invariant are IDENTICAL to
ops.fieldb (see its module docstring for the full analysis): limbs stay in
[0, LIMB_RELAX], values < 2.2p, no exact carry resolution on the hot path.
Only the data movement differs:
  * the data x data convolution unrolls over the 33 limbs of `a`
    (static-slice accumulate) instead of an einsum against a one-hot
    tensor;
  * the two static convolutions of Montgomery REDC (by N' and by p)
    unroll over STATIC scalar limbs — scalar * tensor fused multiply-adds;
  * slot recombinations unroll per output row over the (sparse, small)
    static coefficients instead of an einsum.

Parity note: behind the reference's BLS boundary
(crypto/bls/src/impls/blst.rs), alternate layout of the same plane.
"""

import functools

import numpy as np

import jax.numpy as jnp

from lighthouse_tpu.crypto.constants import LIMB_BITS, LIMB_MASK, NLIMBS
from lighthouse_tpu.ops import fieldb as fb

NB = fb.NB
LIMB_RELAX = fb.LIMB_RELAX

_NPRIME = [int(v) for v in fb.NPRIME_LIMBS]
_PLIMBS = [int(v) for v in fb.P_LIMBS32]
_COMP_2P = [int(v) for v in fb.COMP_2P]
_OFF = [int(v) for v in fb.OFF_CONST]
_SPREAD_SUB = [int(v) for v in fb.SPREAD_SUB]


# ----------------------------------------------------------- carry handling


def _partial_pass(x):
    """One value-preserving carry pass along the limb axis (-2)."""
    c = x >> LIMB_BITS
    d = x & LIMB_MASK
    pad = [(0, 0)] * x.ndim
    pad[-2] = (1, 0)
    return d + jnp.pad(c[..., :-1, :], pad)


def _relax(x, out_len, passes=3):
    """Limbs -> <= ~4096; truncation beyond out_len is deliberate mod-R /
    mod-2^396 arithmetic (same bound chains as fieldb._relax)."""
    in_len = x.shape[-2]
    if in_len < out_len:
        pad = [(0, 0)] * x.ndim
        pad[-2] = (0, out_len - in_len)
        x = jnp.pad(x, pad)
    elif in_len > out_len:
        x = x[..., :out_len, :]
    for _ in range(passes):
        x = _partial_pass(x)
    return x


import contextlib
import threading

# Inside a Pallas kernel, captured array constants are not allowed — the
# kernel passes them as inputs and installs them here for the duration of
# its trace (see ops.pallas_miller). Keys: "off", "spread_sub", "comp_2p",
# "one". Per thread: a trace runs on its caller's thread, and threads
# compiling programs in parallel must never see each other's tracers.
_TLS = threading.local()


def _installed() -> dict:
    d = getattr(_TLS, "overrides", None)
    if d is None:
        d = _TLS.overrides = {}
    return d


_MISSING = object()


@contextlib.contextmanager
def const_overrides(**cols):
    """Reentrant: saves and restores any previously-installed value per
    key, so nested kernel traces cannot leak each other's tracers."""
    installed = _installed()
    prev = {k: installed.get(k, _MISSING) for k in cols}
    installed.update(cols)
    try:
        yield
    finally:
        for k, v in prev.items():
            if v is _MISSING:
                installed.pop(k, None)
            else:
                installed[k] = v


def _const_col(limbs, name=None):
    """Static limb list -> (len, 1) column broadcastable over (..., L, B);
    an installed override (a traced in-kernel value) takes precedence."""
    installed = _installed()
    if name is not None and name in installed:
        return installed[name]
    # lint: allow(device-purity): limbs is a static host constant list
    return jnp.asarray(np.array(limbs, dtype=np.int32)[:, None])


def one_col():
    """Montgomery 1 as a (NB, 1) column."""
    return _const_col(list(fb.ONE_MONT_B), "one")


def reduce_small(x):
    """fieldb.reduce_small in transposed layout: quotient estimate from the
    top two limbs, subtract q*2p via the 2^396-complement."""
    t2 = x[..., NB - 1, :] * (1 << LIMB_BITS) + x[..., NB - 2, :]
    q = t2 // 833
    return _relax(x + q[..., None, :] * _const_col(_COMP_2P, "comp_2p"), NB)


# ------------------------------------------------------------- multiplies


def _tpu_backend() -> bool:
    """True when this process computes on real TPU hardware (the MXU
    default only makes sense where there IS an MXU)."""
    import jax

    return jax.default_backend() == "tpu"


def use_mxu_redc() -> str:
    """Route the two STATIC convolutions of Montgomery REDC (by N' and
    by p) through MXU matmuls. LIGHTHOUSE_TPU_MXU_REDC selects the
    operand form: "1"/"i8" = int8 x int8 -> int32; "bf16" = bfloat16
    operands with f32 accumulation (exact: 7-bit digits give column
    sums <= 2^19 << 2^24, and bf16 matmul is the most-trodden Mosaic
    lowering); "0" = forced off (the legacy unrolled VPU chain, A/B via
    BENCH_IMPL=vredc). ""/unset resolves the DEFAULT device form: bf16
    on real TPU hardware (the Toeplitz matmuls replace ~57 of ~90 VPU
    FMA stages per Montgomery product), the VPU chain on the CPU mesh
    (XLA:CPU runs the FMA chain faster and has no MXU to feed). Unlike
    the failed data-conv int8 path (fieldb._conv_contract, measured
    slower 2026-07-31), the MXU here consumes RAW limb digits against
    precomputed Toeplitz digit matrices — no VPU-computed products
    feed it. Read at trace time — part of the backend jit cache keys
    (_impl_key); build fresh jitted functions after flipping it."""
    import os

    # lint: allow(device-purity): trace-time knob, keyed via _impl_key
    v = os.environ.get("LIGHTHOUSE_TPU_MXU_REDC", "")
    if v == "":
        return "bf16" if _tpu_backend() else ""
    if v == "0":
        return ""
    if v == "1":
        return "i8"
    if v in ("i8", "bf16"):
        return v
    # a typo must not silently measure the baseline under an MXU label
    raise ValueError(f"LIGHTHOUSE_TPU_MXU_REDC={v!r}: use i8, bf16, or 0")


def _toeplitz(vals, n_out: int, n_in: int) -> np.ndarray:
    """Conv-as-matmul matrix: out_k = sum_l x_l * vals[k - l], rows
    truncated at n_out (mod-R truncation for the N' matrix)."""
    m = np.zeros((n_out, n_in), np.int32)
    for l in range(n_in):
        for k in range(l, min(n_out, l + len(vals))):
            m[k, l] = vals[k - l]
    return m


# TP gets 64 output rows (63 real + one all-zero) so kernel refs slice at
# 8-aligned sublane offsets and no value-slicing is needed; the zero row
# contributes nothing downstream.
_TN_FULL = _toeplitz(_NPRIME, NLIMBS, NLIMBS)
_TP_FULL = _toeplitz(_PLIMBS, 64, NLIMBS)


def _digits8(m: np.ndarray):
    """12-bit-entry static matrix -> (lo7, hi5) int8 digit matrices."""
    return (m & 127).astype(np.int8), (m >> 7).astype(np.int8)


_TN_LO, _TN_HI = _digits8(_TN_FULL)
_TP_LO, _TP_HI = _digits8(_TP_FULL)


# Stack layout of redc_mats_array: [tn_lo | tn_hi | tp_lo | tp_hi] with
# row offsets derived from the matrix heights. Kernels size their
# BlockSpecs from REDC_MATS_SHAPE so a change here cannot silently
# misalign the in-kernel slices.
_REDC_OFFS = np.cumsum(
    [0, _TN_LO.shape[0], _TN_HI.shape[0], _TP_LO.shape[0], _TP_HI.shape[0]]
)
REDC_MATS_SHAPE = (int(_REDC_OFFS[-1]), NLIMBS)


def redc_mats_array():
    """(REDC_MATS_SHAPE) int8 stack — the single extra input a Pallas
    kernel threads when the MXU-REDC path is on (kernels cannot capture
    array constants). All slice offsets are 8-aligned sublane offsets."""
    return jnp.asarray(
        np.concatenate([_TN_LO, _TN_HI, _TP_LO, _TP_HI], axis=0)
    )


def redc_overrides(mats):
    """Split a REDC_MATS_SHAPE stack (ref-loaded in-kernel) into the
    const_overrides keys _static_conv_mxu reads."""
    o = _REDC_OFFS
    return {
        "tn_lo": mats[int(o[0]) : int(o[1])],
        "tn_hi": mats[int(o[1]) : int(o[2])],
        "tp_lo": mats[int(o[2]) : int(o[3])],
        "tp_hi": mats[int(o[3]) : int(o[4])],
    }


def _const_mat(arr_np, name):
    installed = _installed()
    if name in installed:
        return installed[name]
    return jnp.asarray(arr_np)


def _static_conv_mxu(x, lo_np, hi_np, lo_name, hi_name, form: str):
    """Static convolution as four digit-matmuls on the MXU.

    x: (..., L, B) non-negative limbs < 2^13 (relaxed bound 4097).
    Exactness: x splits into lo7 (< 2^7) and hi (< 2^6) digits, the
    matrices into lo7/hi5; per-digit column sums <= 32*127*127 < 2^19
    (int32-exact, and also f32-exact since 2^19 << 2^24 for the bf16
    form) and the recombination sum(p_ab << 7(a+b)) <= 32*4097*4095
    < 2^30 — bit-identical to the unrolled shift-pad FMA chain
    (adversarially checked in tests/test_tfield.py)."""
    mlo = _const_mat(lo_np, lo_name)
    mhi = _const_mat(hi_np, hi_name)
    xlo = x & 127
    xhi = x >> 7
    if form == "bf16":
        dt, acc = jnp.bfloat16, jnp.float32
    else:
        dt, acc = jnp.int8, jnp.int32

    def dot(m, v):
        out = jnp.einsum(
            "kl,...lb->...kb",
            m.astype(dt),
            v.astype(dt),
            preferred_element_type=acc,
        )
        return out.astype(jnp.int32)

    p00 = dot(mlo, xlo)
    p01 = dot(mlo, xhi)
    p10 = dot(mhi, xlo)
    p11 = dot(mhi, xhi)
    return p00 + ((p01 + p10) << 7) + (p11 << 14)


def _shift_pad(x, lo: int, total: int):
    """Place x at limb offset `lo` within a length-`total` limb axis.
    Pad-and-sum composition (NO .at[] scatter updates: those lower to
    scatter-add with empty index constants, which Pallas kernels reject)."""
    pad = [(0, 0)] * x.ndim
    pad[-2] = (lo, total - lo - x.shape[-2])
    return jnp.pad(x, pad)


def mul_lazy(a, b):
    """Stacked Montgomery product: (..., S, NB, B) x (..., S, NB, B) ->
    (..., S, NB, B); inputs < 2.2p relaxed, output < 1.5p (fieldb bound
    chain). Data x data conv unrolls over a's limbs; REDC's two static
    convs unroll over scalar limbs of N' and p."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    t = sum(
        _shift_pad(a[..., i : i + 1, :] * b, i, 2 * NB) for i in range(NB)
    )
    t = _relax(t, 2 * NB)

    t_low = t[..., :NLIMBS, :]
    form = use_mxu_redc()
    if form:
        # both static convs as digit MXU matmuls against Toeplitz digit
        # matrices (the _TN mod-R truncation is baked into the matrix)
        m = _relax(
            _static_conv_mxu(
                t_low, _TN_LO, _TN_HI, "tn_lo", "tn_hi", form
            ),
            NLIMBS,
        )
        mp = _static_conv_mxu(m, _TP_LO, _TP_HI, "tp_lo", "tp_hi", form)
    else:
        # shift t_low up by j limbs, truncated at NLIMBS (mod R)
        m = sum(
            _shift_pad(_NPRIME[j] * t_low[..., : NLIMBS - j, :], j, NLIMBS)
            for j in range(NLIMBS)
            if _NPRIME[j] != 0
        )
        m = _relax(m, NLIMBS)

        mp = sum(
            _shift_pad(_PLIMBS[j] * m, j, 2 * NLIMBS - 1)
            for j in range(NLIMBS)
            if _PLIMBS[j] != 0
        )
    full = _relax(t + _shift_pad(mp, 0, 2 * NB), 2 * NB)

    low_nonzero = jnp.any(full[..., :NLIMBS, :] != 0, axis=-2)
    out = full[..., NLIMBS : NLIMBS + NB, :]
    bump = low_nonzero[..., None, :].astype(jnp.int32)
    return out + _shift_pad(bump, 0, NB)


def sqr_lazy(a):
    return mul_lazy(a, a)


# --------------------------------------------------------------- combos


def apply_combo(x, matrix):
    """Slot recombination: (..., S_in, NB, B) -> (..., S_out, NB, B).
    Unrolled per output row over static small coefficients (rows L1 <= 36);
    double-reduced exactly like fieldb.apply_combo."""
    # lint: allow(device-purity): matrix is a static recombination table
    m = np.asarray(matrix, dtype=np.int64)
    assert np.abs(m).sum(axis=1).max() <= fb._OFF_K, "combo L1 too large"
    off = _const_col(_OFF, "off")
    rows = []
    for o in range(m.shape[0]):
        acc = None
        for s in range(m.shape[1]):
            c = int(m[o, s])
            if c == 0:
                continue
            term = x[..., s, :, :] if c == 1 else c * x[..., s, :, :]
            acc = term if acc is None else acc + term
        if acc is None:
            acc = jnp.zeros_like(x[..., 0, :, :])
        rows.append(acc + off)
    y = jnp.stack(rows, axis=-3)
    y = _relax(y, NB, passes=2)
    return reduce_small(reduce_small(y))


def add(a, b):
    return reduce_small(_partial_pass(a + b))


def sub(a, b):
    s = a - b + _const_col(_SPREAD_SUB, "spread_sub")
    return reduce_small(_relax(s, NB, passes=2))


def scalar_small(a, k: int):
    if k == 0:
        return jnp.zeros_like(a)
    assert k <= 12
    return reduce_small(_relax(a * k, NB, passes=2))


def select(cond, a, b):
    """cond: (..., B) broadcasting over (slots, limbs)."""
    return jnp.where(cond[..., None, None, :], a, b)


# --------------------------------------------------------- layout converts


def from_batchlead(x):
    """(..., S, NB) batch-leading (fieldb layout, batch axes in ...) ->
    (S, NB, B) with the single leading batch axis moved last."""
    return jnp.moveaxis(x, -3, -1)


def to_batchlead(x):
    """(S, NB, B) -> (B, S, NB)."""
    return jnp.moveaxis(x, -1, -3)
