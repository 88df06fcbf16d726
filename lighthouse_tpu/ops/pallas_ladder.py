"""Pallas TPU kernel: per-lane RLC scalar ladders fused in VMEM.

The G2 ladder (sum_i r_i * sig_i) is the second-hottest stage of batch
verification after the Miller loop. This kernel keeps the accumulator,
the multiple table, and all intermediates in VMEM for the whole ladder;
the XLA level then tree-folds the per-lane multiples. Works for G1
(w=1) and G2 (w=2) via ops.tcurve.

Three kernel bodies, selected by `ops.window_ladder.ladder_impl()`
(the one LIGHTHOUSE_TPU_LADDER knob shared with the XLA planes):

  * "window" (DEFAULT) — the unified signed-digit window kernel: the
    scalar bits are recoded to window-major signed digits at the XLA
    level (`window_ladder.recode_bits`, one cheap int32 scan) and the
    kernel runs W windows of c doublings + ONE complete add against a
    VMEM multiple table (tcurve.window_table/window_step) — ~17 adds +
    72 doublings for 64-bit scalars vs the chain's 64 + 64;
  * "w2" — the earlier 2-bit unsigned window (kept for A/B);
  * "chain" — the legacy per-bit double-add (A/B via BENCH_IMPL=chain).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lighthouse_tpu.ops import tcurve, tfield as tf
from lighthouse_tpu.ops import window_ladder as wl

NB = tf.NB

# Scoped-VMEM budget for the Miller and final-exp kernels. In the bf16
# MXU-REDC form (the on-TPU default) one 128-lane Miller block needs
# ~21 MiB of scoped VMEM, over the compiler's 16 MiB default scope; v5e
# has 128 MiB of physical VMEM. Raising the scope keeps the full 128-lane
# block (halving block_b would leave half of every vreg idle) and the
# kernel bodies as they are.
VMEM_LIMIT_BYTES = 64 * 2**20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _consts_array():
    return jnp.asarray(
        np.stack(
            [
                np.array(tf._OFF, np.int32)[:, None],
                np.array(tf._SPREAD_SUB, np.int32)[:, None],
                np.array(tf._COMP_2P, np.int32)[:, None],
                np.array(tf.fb.ONE_MONT_B, np.int32)[:, None],
            ]
        )
    )  # (4, NB, 1)


def _overrides(consts):
    return {
        "off": consts[0],
        "spread_sub": consts[1],
        "comp_2p": consts[2],
        "one": consts[3],
    }


def _ladder_kernel(group, n_bits, x_ref, y_ref, z_ref, bits_ref,
                   consts_ref, redc_ref, ox_ref, oy_ref, oz_ref):
    with tf.const_overrides(
        **_overrides(consts_ref[:]), **tf.redc_overrides(redc_ref[:])
    ):
        pt = (x_ref[:], y_ref[:], z_ref[:])
        B = pt[0].shape[-1]
        acc0 = group.identity(B)

        def body(i, carry):
            acc, addend = carry
            bit = bits_ref[i]  # (B,) int32
            return group.ladder_step(acc, addend, bit)

        acc, _ = jax.lax.fori_loop(0, n_bits, body, (acc0, pt))
        ox_ref[:], oy_ref[:], oz_ref[:] = acc


def _ladder_kernel_w2(group, n_bits, x_ref, y_ref, z_ref, bits_ref,
                      consts_ref, redc_ref, ox_ref, oy_ref, oz_ref):
    """Windowed-2 MSB-first ladder: per window 2 doubles + ONE complete
    add from a {identity, P, 2P, 3P} VMEM table — ~25% fewer group ops
    than the double-add chain (tcurve.window2_step)."""
    assert n_bits % 2 == 0, n_bits
    with tf.const_overrides(
        **_overrides(consts_ref[:]), **tf.redc_overrides(redc_ref[:])
    ):
        pt = (x_ref[:], y_ref[:], z_ref[:])
        B = pt[0].shape[-1]
        table = group.window2_table(pt)
        n_windows = n_bits // 2

        def body(j, acc):
            # window j covers bits (n_bits-2j-2, n_bits-2j-1), MSB-first
            lo = n_bits - 2 * j - 2
            digit = bits_ref[lo] + 2 * bits_ref[lo + 1]
            return group.window2_step(acc, table, digit)

        acc = jax.lax.fori_loop(0, n_windows, body, group.identity(B))
        ox_ref[:], oy_ref[:], oz_ref[:] = acc


def _ladder_kernel_w4(group, n_windows, c, x_ref, y_ref, z_ref, mags_ref,
                      negs_ref, consts_ref, redc_ref, ox_ref, oy_ref,
                      oz_ref):
    """The unified signed-digit window kernel (MSB-first): per window
    c doublings + ONE complete add against the in-VMEM multiple table
    [0..2^(c-1)]·P, digit sign applied by negating y. Digits arrive
    pre-recoded (window_ladder.recode_bits at the XLA level)."""
    with tf.const_overrides(
        **_overrides(consts_ref[:]), **tf.redc_overrides(redc_ref[:])
    ):
        pt = (x_ref[:], y_ref[:], z_ref[:])
        B = pt[0].shape[-1]
        table = group.window_table(pt, c)

        def body(j, acc):
            w_i = n_windows - 1 - j  # MSB-first over LSB-first storage
            return group.window_step(
                acc, table, mags_ref[w_i], negs_ref[w_i] == 1, c
            )

        acc = jax.lax.fori_loop(0, n_windows, body, group.identity(B))
        ox_ref[:], oy_ref[:], oz_ref[:] = acc


def ladder_pallas(
    pt,
    bits,
    group_name: str = "G2",
    block_b: int = 128,
    interpret: bool = False,
    kind: str | None = None,
):
    """Per-lane scalar ladder on PROJECTIVE inputs: pt = (X, Y, Z)
    bundles (w, NB, B) (identity lanes pass through as the identity),
    bits (n_bits, B) int32 LSB-first. Returns projective (X, Y, Z).

    `kind` None resolves LIGHTHOUSE_TPU_LADDER HERE
    (window_ladder.ladder_impl — "window" default / "w2" / "chain"),
    outside the jit — the kernel choice must be part of the jit key, or
    flipping the env var after a first trace would silently reuse the
    old kernel."""
    if kind is None:
        kind = wl.ladder_impl()
    return _ladder_pallas(
        pt, bits, group_name=group_name, block_b=block_b,
        interpret=interpret, kind=kind,
    )


@functools.partial(
    jax.jit,
    static_argnames=("group_name", "block_b", "interpret", "kind"),
)
def _ladder_pallas(
    pt,
    bits,
    group_name: str = "G2",
    block_b: int = 128,
    interpret: bool = False,
    kind: str = "window",
):
    group = tcurve.TPG2 if group_name == "G2" else tcurve.TPG1
    w = group.w
    X, Y, Z = pt
    B = X.shape[-1]
    n_bits = bits.shape[0]
    if kind == "w2" and n_bits % 2:
        bits = jnp.concatenate(
            [bits, jnp.zeros((1, B), bits.dtype)]
        )
        n_bits += 1
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)

    def spec(s):
        return pl.BlockSpec(
            (s, NB, block_b), lambda i: (0, 0, i),
            memory_space=pltpu.VMEM,
        )

    const_spec = pl.BlockSpec(
        (4, NB, 1), lambda i: (0, 0, 0), memory_space=pltpu.VMEM
    )
    redc_spec = pl.BlockSpec(
        tf.REDC_MATS_SHAPE, lambda i: (0, 0), memory_space=pltpu.VMEM
    )

    shape = jax.ShapeDtypeStruct((w, NB, B), jnp.int32)
    if kind == "window":
        c = wl.WINDOW_BITS
        # recode at the XLA level (cheap int32 scan); the kernel reads
        # window-major digit magnitudes + sign flags from VMEM
        mags, negs = wl.recode_bits(jnp.moveaxis(bits, 0, -1), c)
        n_windows = mags.shape[0]
        dig_spec = pl.BlockSpec(
            (n_windows, block_b), lambda i: (0, i),
            memory_space=pltpu.VMEM,
        )
        ox, oy, oz = pl.pallas_call(
            functools.partial(_ladder_kernel_w4, group, n_windows, c),
            out_shape=(shape, shape, shape),
            grid=grid,
            in_specs=[spec(w), spec(w), spec(w), dig_spec, dig_spec,
                      const_spec, redc_spec],
            out_specs=(spec(w), spec(w), spec(w)),
            interpret=interpret,
        )(
            X, Y, Z, mags, negs.astype(jnp.int32), _consts_array(),
            tf.redc_mats_array(),
        )
        return ox, oy, oz

    bits_spec = pl.BlockSpec(
        (n_bits, block_b), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    kernel = _ladder_kernel_w2 if kind == "w2" else _ladder_kernel
    ox, oy, oz = pl.pallas_call(
        functools.partial(kernel, group, n_bits),
        out_shape=(shape, shape, shape),
        grid=grid,
        in_specs=[spec(w), spec(w), spec(w), bits_spec, const_spec,
                  redc_spec],
        out_specs=(spec(w), spec(w), spec(w)),
        interpret=interpret,
    )(X, Y, Z, bits, _consts_array(), tf.redc_mats_array())
    return ox, oy, oz
