"""Pallas TPU kernel: the full Miller loop fused in VMEM.

The XLA-level pipeline materializes every field-op intermediate to HBM
(each stacked multiply round-trips its conv tensor), which caps the
composed graph ~20x below VPU peak. This kernel keeps f, the running
point T, and every intermediate of all 63 Miller iterations resident in
VMEM: HBM traffic is exactly one read of the pair inputs and one write of
the Fp12 outputs per batch tile.

Layout: ops.tfield batch-last bundles (S, NB, B) — limbs on sublanes,
batch on lanes; the grid tiles the lane axis in blocks of `block_b`.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lighthouse_tpu.crypto.constants import BLS_X
from lighthouse_tpu.ops import tfield as tf
from lighthouse_tpu.ops import tpairing as tp

NB = tf.NB

_BITS = np.array(tp._X_BITS, dtype=np.int32)


def _kernel(
    bits_ref, px_ref, py_ref, qx_ref, qy_ref, consts_ref, redc_ref, f_ref
):
    from lighthouse_tpu.ops.pallas_ladder import _overrides

    px, py = px_ref[:], py_ref[:]
    qx, qy = qx_ref[:], qy_ref[:]
    overrides = {
        **_overrides(consts_ref[:]),
        **tf.redc_overrides(redc_ref[:]),
    }
    with tf.const_overrides(**overrides):
        B = qx.shape[-1]
        f0 = tp.fp12_one(B)
        t0 = (qx, qy, tp.fp2_one(B))

        def body(i, carry):
            f, t = carry
            bit = bits_ref[i]
            f, t = tp.miller_body(f, t, px, py, qx, qy, bit)
            return (f, t)

        f, _ = jax.lax.fori_loop(0, len(_BITS), body, (f0, t0))
        if BLS_X < 0:
            m = np.diag([1] * 6 + [-1] * 6).astype(np.int32)
            f = tf.apply_combo(f, m)
        f_ref[:] = f


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def miller_loop_pallas(
    p_g1_affine, q_g2_affine, valid_mask=None, block_b: int = 128,
    interpret: bool = False,
):
    """Batched Miller loop on TPU via one fused VMEM kernel.

    p_g1_affine: (px, py) (1, NB, B); q_g2_affine: (qx, qy) (2, NB, B);
    B must be a multiple of `block_b`. Returns f (12, NB, B).
    """
    px, py = p_g1_affine
    qx, qy = q_g2_affine
    B = qx.shape[-1]
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)

    def spec(s):
        return pl.BlockSpec(
            (s, NB, block_b),
            lambda i: (0, 0, i),
            memory_space=pltpu.VMEM,
        )

    from lighthouse_tpu.ops.pallas_ladder import (
        COMPILER_PARAMS,
        _consts_array,
    )

    consts = _consts_array()
    bits = jnp.asarray(_BITS)

    f = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((12, NB, B), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # bits
            spec(1),
            spec(1),
            spec(2),
            spec(2),
            pl.BlockSpec(
                (4, NB, 1), lambda i: (0, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                tf.REDC_MATS_SHAPE, lambda i: (0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=spec(12),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(bits, px, py, qx, qy, consts, tf.redc_mats_array())
    if valid_mask is not None:
        f = tf.select(valid_mask, f, tp.fp12_one(B))
    return f
