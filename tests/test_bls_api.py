"""Host BLS API: serde, sign/verify, aggregation, signature-set batches.

Mirrors the reference's bls conformance surface (the seven ef-test BLS
handlers: verify, aggregate_verify, fast_aggregate_verify, eth variants,
aggregation — testing/ef_tests/src/cases/bls_*.rs) with locally generated
vectors (no network), plus wire-format edge cases.
"""

import pytest

from lighthouse_tpu import bls
from lighthouse_tpu.bls.point_serde import DecodeError, g1_compress, g1_decompress
from lighthouse_tpu.common import tracing
from lighthouse_tpu.crypto.constants import R
from lighthouse_tpu.crypto.ref_curve import G1 as G1_GROUP


def kp(i):
    return bls.interop_keypairs(i + 1)[i]


def test_keygen_deterministic():
    a = bls.interop_keypairs(3)
    b = bls.interop_keypairs(3)
    assert [x.pk.to_bytes() for x in a] == [x.pk.to_bytes() for x in b]
    assert len({x.pk.to_bytes() for x in a}) == 3


def test_pubkey_serde_roundtrip():
    pk = kp(0).pk
    data = pk.to_bytes()
    assert len(data) == 48
    pk2 = bls.PublicKey.from_bytes(data)
    assert pk == pk2


def test_infinity_pubkey_rejected():
    with pytest.raises(bls.BlsError):
        bls.PublicKey.from_bytes(bls.INFINITY_PUBKEY_BYTES)


def test_non_subgroup_pubkey_rejected():
    # find an x whose curve point is NOT in the r-subgroup
    x = 0
    while True:
        x += 1
        try:
            pt = g1_decompress(
                bytes([0x80 | (x >> 376 if False else 0)])
                + x.to_bytes(47, "big")
            )
        except DecodeError:
            continue
        if not G1_GROUP.in_subgroup(pt):
            data = g1_compress(pt)
            break
    with pytest.raises(bls.BlsError):
        bls.PublicKey.from_bytes(data)


def test_sign_verify_roundtrip():
    pair = kp(1)
    msg = b"\x01" * 32
    sig = pair.sk.sign(msg)
    assert len(sig.to_bytes()) == 96
    assert bls.verify(pair.pk, msg, sig)
    assert not bls.verify(pair.pk, b"\x02" * 32, sig)
    assert not bls.verify(kp(2).pk, msg, sig)
    # serde roundtrip preserves verification
    sig2 = bls.Signature.from_bytes(sig.to_bytes())
    assert bls.verify(pair.pk, msg, sig2)


def test_fast_aggregate_verify():
    msg = b"\x05" * 32
    pairs = bls.interop_keypairs(4)
    sigs = [p.sk.sign(msg) for p in pairs]
    agg = bls.aggregate_signatures(sigs)
    pks = [p.pk for p in pairs]
    assert bls.fast_aggregate_verify(pks, msg, agg)
    assert not bls.fast_aggregate_verify(pks[:3], msg, agg)
    assert not bls.fast_aggregate_verify([], msg, agg)


def test_eth_fast_aggregate_verify_infinity_special_case():
    inf_sig = bls.Signature.from_bytes(bls.INFINITY_SIGNATURE_BYTES)
    assert bls.eth_fast_aggregate_verify([], b"msg", inf_sig)
    assert not bls.fast_aggregate_verify([], b"msg", inf_sig)


def test_aggregate_verify_distinct_messages():
    pairs = bls.interop_keypairs(3)
    msgs = [bytes([i]) * 32 for i in range(3)]
    sigs = [p.sk.sign(m) for p, m in zip(pairs, msgs)]
    agg = bls.aggregate_signatures(sigs)
    assert bls.aggregate_verify([p.pk for p in pairs], msgs, agg)
    bad = list(msgs)
    bad[1] = b"\xff" * 32
    assert not bls.aggregate_verify([p.pk for p in pairs], bad, agg)


def test_verify_signature_sets_ref_backend():
    pairs = bls.interop_keypairs(3)
    msgs = [bytes([i]) * 32 for i in range(3)]
    sets = []
    for p, m in zip(pairs, msgs):
        sets.append(bls.SignatureSet(p.sk.sign(m), [p.pk], m))
    # multi-pubkey set
    shared = b"\x09" * 32
    agg = bls.aggregate_signatures([p.sk.sign(shared) for p in pairs])
    sets.append(bls.SignatureSet(agg, [p.pk for p in pairs], shared))

    assert bls.verify_signature_sets(sets, backend="ref")
    assert bls.verify_signature_sets(sets, backend="fake")
    assert not bls.verify_signature_sets([], backend="ref")

    # corrupt one set
    bad = list(sets)
    bad[1] = bls.SignatureSet(sets[0].signature, [pairs[1].pk], msgs[1])
    assert not bls.verify_signature_sets(bad, backend="ref")


def test_secret_key_bounds():
    with pytest.raises(bls.BlsError):
        bls.SecretKey(0)
    with pytest.raises(bls.BlsError):
        bls.SecretKey(R)
    sk = bls.SecretKey.from_bytes((1).to_bytes(32, "big"))
    assert sk.public_key() is not None


def test_verify_signature_set_batches_streaming():
    """Double-buffered multi-batch dispatch (tpu_backend
    verify_signature_set_batches_tpu): per-batch verdicts must equal the
    single-batch API on every backend, including bad and empty batches."""
    from lighthouse_tpu.bls import tpu_backend

    pairs = bls.interop_keypairs(4)
    msgs = [bytes([40 + i]) * 32 for i in range(4)]
    good = [
        bls.SignatureSet(p.sk.sign(m), [p.pk], m)
        for p, m in zip(pairs, msgs)
    ]
    bad = [
        bls.SignatureSet(good[0].signature, [pairs[1].pk], msgs[1]),
        good[2],
    ]
    batches = [good[:2], bad, [], good[2:]]

    expected = [True, False, False, True]
    for backend in ("ref", "tpu"):
        assert (
            bls.verify_signature_set_batches(batches, backend=backend)
            == expected
        ), backend
    stats = tpu_backend.LAST_STREAM_STATS
    assert stats["batches"] == 4
    # the empty batch never dispatches; the bad batch carries
    # subgroup-valid signatures, so its reject is a device verdict
    assert stats["dispatched"] == 3
    assert stats["host_marshal_ms"] > 0


def test_native_decompression_matches_python():
    """native/g2decomp.c vs the pure-Python sqrt path: identical
    decompression results on valid points, identical rejections on
    non-curve x, across G1 and G2 (the sort flag normalizes whichever
    root family the backend returns)."""
    import random

    from lighthouse_tpu.bls import point_serde as ps
    from lighthouse_tpu.crypto.ref_curve import G1 as RG1, G2 as RG2
    from lighthouse_tpu.native import g2decomp

    if not g2decomp.available():
        import pytest

        pytest.skip("native g2decomp unavailable")

    rnd = random.Random(9)
    for k in (rnd.randrange(2, 2**200) for _ in range(4)):
        for group, compress, decompress in (
            (RG1, ps.g1_compress, ps.g1_decompress),
            (RG2, ps.g2_compress, ps.g2_decompress),
        ):
            pt = group.mul_scalar(group.generator, k)
            data = compress(pt)
            native_pt = decompress(data)
            # force the Python fallback and compare exactly
            g2decomp._lib_failed, saved = True, g2decomp._lib
            g2decomp._lib = None
            try:
                py_pt = decompress(data)
            finally:
                g2decomp._lib, g2decomp._lib_failed = saved, False
            assert group.to_affine(native_pt) == group.to_affine(py_pt)
            assert compress(native_pt) == data  # roundtrip
    # not-on-curve x rejected identically
    bad_g2 = bytearray(ps.g2_compress(RG2.mul_scalar(RG2.generator, 5)))
    bad_g2[-1] ^= 0x01
    for _ in range(4):  # find an x off the curve (half are)
        try:
            ps.g2_decompress(bytes(bad_g2))
            bad_g2[-1] += 1
        except ps.DecodeError:
            break
    else:
        raise AssertionError("never found an off-curve x")


def test_native_subgroup_checks_match_python():
    """native in-subgroup ladders vs the Python [r]P ground truth, on
    r-torsion points AND adversarial pre-cofactor-clear curve points."""
    import random

    from lighthouse_tpu.bls.hash_to_curve import (
        hash_to_field_fp2,
        iso_map,
        map_to_curve_sswu,
    )
    from lighthouse_tpu.crypto.ref_curve import G1 as RG1, G2 as RG2
    from lighthouse_tpu.native import g2decomp

    if not g2decomp.available():
        pytest.skip("native g2decomp unavailable")
    rnd = random.Random(11)
    for k in (1, 7, rnd.randrange(2, R)):
        assert g2decomp.g1_in_subgroup(
            *RG1.to_affine(RG1.mul_scalar(RG1.generator, k))
        )
        assert g2decomp.g2_in_subgroup(
            *RG2.to_affine(RG2.mul_scalar(RG2.generator, k))
        )
    for i in range(3):
        u = hash_to_field_fp2(bytes([i]) + b"probe", 2)
        pt = iso_map(map_to_curve_sswu(u[0]))
        assert g2decomp.g2_in_subgroup(pt[0], pt[1]) is False


def _last_marshal():
    """Attributes of the newest `verify/marshal` span the tracer holds."""
    return [
        m for r in tracing.TRACER.recent()
        for m in tracing.find(r, "verify/marshal")
    ][-1]["attrs"]


def test_tpu_backend_grouped_dispatch():
    """Sets sharing messages route through the message-grouped device
    path (G+1 pairs): verdicts match the ref backend, forgery fails the
    batch and the per-set fallback (always flat) isolates it, and
    LIGHTHOUSE_TPU_GROUPED=0 falls back to the flat layout."""
    import os

    from lighthouse_tpu.bls import tpu_backend

    pairs = bls.interop_keypairs(8)
    msgs = [b"\x41" * 32, b"\x42" * 32]  # 2 messages x 4 signers
    sets = [
        bls.SignatureSet(p.sk.sign(msgs[i // 4]), [p.pk], msgs[i // 4])
        for i, p in enumerate(pairs)
    ]

    assert bls.verify_signature_sets(sets, backend="tpu", seed=3)
    assert _last_marshal()["layout"] == "grouped"
    assert _last_marshal()["n_groups"] == 2

    # forged member -> batch False; per-set fallback isolates it
    bad = list(sets)
    bad[5] = bls.SignatureSet(sets[0].signature, [pairs[5].pk], msgs[1])
    assert not bls.verify_signature_sets(bad, backend="tpu", seed=3)
    verdicts = tpu_backend.verify_signature_sets_tpu_individual(bad)
    assert verdicts == [True] * 5 + [False] + [True] * 2
    assert _last_marshal()["layout"] == "flat"

    # kill switch: flat layout, same verdict
    os.environ["LIGHTHOUSE_TPU_GROUPED"] = "0"
    try:
        assert bls.verify_signature_sets(sets, backend="tpu", seed=3)
        assert _last_marshal()["layout"] == "flat"
    finally:
        del os.environ["LIGHTHOUSE_TPU_GROUPED"]

    # distinct messages never group (the merge must pay >= 2x)
    distinct = [
        bls.SignatureSet(p.sk.sign(bytes([i]) * 32), [p.pk],
                         bytes([i]) * 32)
        for i, p in enumerate(pairs)
    ]
    assert bls.verify_signature_sets(distinct, backend="tpu", seed=3)
    assert _last_marshal()["layout"] == "flat"
