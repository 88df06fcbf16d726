"""Parity tests: ops.tfield (transposed batch-last layout) vs ops.fieldb.

tfield must compute identical relaxed-limb bundles (same values mod p and
the same invariants) as fieldb for every op — it is the same arithmetic
with different data movement, consumed by the Pallas pairing kernel.
"""

import random

import numpy as np
import jax.numpy as jnp

from lighthouse_tpu.crypto.constants import P
from lighthouse_tpu.ops import fieldb as fb, tfield as tf

rng = random.Random(77)


def _rand_bundle(s_slots, batch):
    vals = [
        [rng.randrange(int(2.1 * P)) for _ in range(s_slots)]
        for _ in range(batch)
    ]
    arr = np.stack(
        [np.stack([fb._limbs(v, fb.NB) for v in row]) for row in vals]
    )  # (B, S, NB) canonical-limbed
    return jnp.asarray(arr)


def _t(x):  # batch-lead (B, S, NB) -> batch-last (S, NB, B)
    return jnp.moveaxis(x, 0, -1)


def _check_same(name, got_t, want_b):
    got = np.asarray(jnp.moveaxis(got_t, -1, 0))
    want = np.asarray(want_b)
    assert got.min() >= 0 and got.max() <= tf.LIMB_RELAX, name
    gv = fb.unpack_ints(fb.canon(jnp.asarray(got)))
    wv = fb.unpack_ints(fb.canon(jnp.asarray(want)))
    assert gv == wv, name


def test_mul_add_sub_scalar_parity():
    a = _rand_bundle(6, 4)
    b = _rand_bundle(6, 4)
    _check_same("mul", tf.mul_lazy(_t(a), _t(b)), fb.mul_lazy(a, b))
    _check_same("add", tf.add(_t(a), _t(b)), fb.add(a, b))
    _check_same("sub", tf.sub(_t(a), _t(b)), fb.sub(a, b))
    _check_same("k8", tf.scalar_small(_t(a), 8), fb.scalar_small(a, 8))


def test_combo_and_reduce_parity():
    a = _rand_bundle(6, 3)
    m = np.array(
        [
            [3, -3, 6, -6, 9, -9],
            [1, 0, 0, 0, 0, -1],
            [0, 2, 0, -2, 0, 0],
        ],
        dtype=np.int32,
    )
    _check_same("combo", tf.apply_combo(_t(a), m), fb.apply_combo(a, m))
    _check_same("reduce", tf.reduce_small(_t(a)), fb.reduce_small(a))


def test_mul_chain_parity():
    a = _rand_bundle(12, 2)
    bt, bb = _t(a), a
    for _ in range(4):
        bt = tf.mul_lazy(bt, _t(a))
        bb = fb.mul_lazy(bb, a)
    _check_same("chain", bt, bb)


def test_mxu_redc_bit_identical(monkeypatch):
    """LIGHTHOUSE_TPU_MXU_REDC=1 (static REDC convs as int8 Toeplitz
    matmuls) is bit-identical to the unrolled shift-pad chain, including
    at the adversarial relaxed-limb bound (all limbs = LIMB_RELAX)."""
    a = _rand_bundle(6, 4)
    b = _rand_bundle(6, 4)
    worst = jnp.full((2, 6, fb.NB), tf.LIMB_RELAX, dtype=jnp.int32)

    monkeypatch.delenv("LIGHTHOUSE_TPU_MXU_REDC", raising=False)
    base = np.asarray(tf.mul_lazy(_t(a), _t(b)))
    base_w = np.asarray(tf.mul_lazy(_t(worst), _t(worst)))

    monkeypatch.setenv("LIGHTHOUSE_TPU_MXU_REDC", "1")
    mxu = np.asarray(tf.mul_lazy(_t(a), _t(b)))
    mxu_w = np.asarray(tf.mul_lazy(_t(worst), _t(worst)))
    assert np.array_equal(base, mxu)
    assert np.array_equal(base_w, mxu_w)

    monkeypatch.setenv("LIGHTHOUSE_TPU_MXU_REDC", "bf16")
    mxu = np.asarray(tf.mul_lazy(_t(a), _t(b)))
    mxu_w = np.asarray(tf.mul_lazy(_t(worst), _t(worst)))

    assert np.array_equal(base, mxu)
    assert np.array_equal(base_w, mxu_w)


def test_mxu_redc_override_split_matches():
    """redc_overrides(redc_mats_array()) reproduces the four digit
    matrices exactly (the kernel threading path)."""
    mats = np.asarray(tf.redc_mats_array())
    ov = tf.redc_overrides(mats)
    assert np.array_equal(np.asarray(ov["tn_lo"]), tf._TN_LO)
    assert np.array_equal(np.asarray(ov["tn_hi"]), tf._TN_HI)
    assert np.array_equal(np.asarray(ov["tp_lo"]), tf._TP_LO)
    assert np.array_equal(np.asarray(ov["tp_hi"]), tf._TP_HI)


def test_const_overrides_are_per_thread():
    """A kernel trace installs its constants for its own thread only:
    programs traced in parallel threads (the verify path's compile-ahead)
    must never capture each other's tracers."""
    import threading

    import numpy as np

    installed, release = threading.Event(), threading.Event()

    def tracing_thread():
        with tf.const_overrides(one="other-thread-tracer"):
            installed.set()
            release.wait(5.0)

    t = threading.Thread(target=tracing_thread)
    t.start()
    try:
        assert installed.wait(5.0)
        col = tf.one_col()  # this thread installed nothing
        assert not isinstance(col, str)
        assert np.array_equal(
            np.asarray(col)[:, 0], np.asarray(tf.fb.ONE_MONT_B)
        )
        with tf.const_overrides(one="mine"):
            assert tf.one_col() == "mine"
    finally:
        release.set()
        t.join(5.0)
    assert not t.is_alive()
