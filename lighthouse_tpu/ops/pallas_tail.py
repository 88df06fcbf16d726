"""Pallas TPU kernel: the final exponentiation fused in VMEM.

After the Miller loop and the (batched, XLA-friendly) product fold, the
batch-verify verdict is ~300 sequential Fp12 ops plus one Fp inversion on
a batch of ONE value — the tail of the reference's one multi-pairing per
batch (crypto/bls/src/impls/blst.rs:114-119). On the XLA path each of
those small ops is its own HBM round-trip; this kernel keeps every chain
intermediate in VMEM, with exponent bits in SMEM and the field/Frobenius
constants passed as inputs (kernels cannot capture array constants —
tfield.const_overrides convention).

The product FOLD deliberately stays at the XLA level: its lane-halving
tree slices the lane axis at sub-tile offsets, which Mosaic rejects
("result/input offset mismatch on non-concat dimension" — measured on
v5e 2026-07-31); XLA handles those slices fine and the fold is batched
work it already does well.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lighthouse_tpu.ops import tfexp
from lighthouse_tpu.ops import tfield as tf

NB = tf.NB


from lighthouse_tpu.ops.pallas_ladder import (
    COMPILER_PARAMS,
    _consts_array,
    _overrides,
)


def use_fused_tail() -> bool:
    """LIGHTHOUSE_TPU_TAIL=1 runs the product fold + final
    exponentiation inside this fused VMEM kernel on the Pallas verify
    path (BENCH_IMPL=ptail); ""/unset keeps them at the XLA level
    (measured equal on v5e — PERF_NOTES: ptail ~= pallas, the final
    exp is not the bottleneck — so the simpler XLA tail stays the
    default and the kernel is one knob away). Read at trace time —
    part of the backend jit cache key (_impl_key), so the tail choice
    rides the same unified dispatch as the ladder/REDC/squaring
    knobs."""
    import os

    # lint: allow(device-purity): trace-time knob, keyed via _impl_key
    v = os.environ.get("LIGHTHOUSE_TPU_TAIL", "")
    if v in ("", "0"):
        return False
    if v == "1":
        return True
    raise ValueError(f"LIGHTHOUSE_TPU_TAIL={v!r}: use 1, 0, or unset")


def _kernel(
    pbits_ref, xbits_ref, f_ref, consts_ref, frob_ref, redc_ref, out_ref
):
    overrides = {
        **_overrides(consts_ref[:]),
        **tf.redc_overrides(redc_ref[:]),
    }
    with tf.const_overrides(**overrides):
        frob = frob_ref[:]
        res = tfexp.final_exponentiation_t(
            f_ref[:],
            frob[:12],
            frob[12:],
            get_pbit=lambda j: pbits_ref[j],
            get_xbit=lambda j: xbits_ref[j],
        )
        out_ref[:] = res


@functools.partial(jax.jit, static_argnames=("interpret",))
def final_exp_pallas(f1_t, interpret: bool = False):
    """(12, NB, 1) folded Miller product -> (12, NB, 1) final-exp'd
    value, the whole addition chain in one VMEM-resident kernel."""
    assert f1_t.shape == (12, NB, 1), f1_t.shape

    pbits = jnp.asarray(tfexp.P_MINUS_2_BITS)
    xbits = jnp.asarray(tfexp.X_ABS_BITS)

    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((12, NB, 1), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # p-2 bits
            pl.BlockSpec(memory_space=pltpu.SMEM),  # |x| bits
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(
        pbits,
        xbits,
        f1_t,
        _consts_array(),
        jnp.asarray(tfexp.frob_consts())[:, :, None],
        tf.redc_mats_array(),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def fold_final_exp_pallas(f_t, interpret: bool = False):
    """(12, NB, B) per-pair Miller outputs -> (12, NB, 1) final-exp'd
    product. XLA lane-tree fold + the final-exp kernel; any B (odd
    fold levels carry a tail)."""
    return final_exp_pallas(tfexp.fold_lanes(f_t), interpret=interpret)
