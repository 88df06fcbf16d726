"""Device pubkey table, indexed gather verification, and the one-call
per-set fallback.

Mirrors validator_pubkey_cache.rs (device half) and attestation
batch.rs:115-131 fallback semantics: a failed batch yields exact per-item
verdicts with at most 2 device dispatches total.
"""

import numpy as np
import pytest

from lighthouse_tpu import bls
from lighthouse_tpu.bls import tpu_backend as tb
from lighthouse_tpu.common import tracing
from lighthouse_tpu.state_processing.pubkey_cache import PubkeyCache


class _V:
    def __init__(self, pk_bytes):
        self.pubkey = pk_bytes


class _State:
    def __init__(self, pk_bytes_list):
        self.validators = [_V(b) for b in pk_bytes_list]


def _last_marshal():
    """Attributes of the newest `verify/marshal` span the tracer holds."""
    return [
        m for r in tracing.TRACER.recent()
        for m in tracing.find(r, "verify/marshal")
    ][-1]["attrs"]


@pytest.fixture(scope="module")
def cache_and_keys():
    kps = [
        bls.Keypair(bls.SecretKey.from_bytes((i + 1).to_bytes(32, "big")))
        for i in range(8)
    ]
    cache = PubkeyCache()
    cache.import_new(_State([kp.pk.to_bytes() for kp in kps]))
    return cache, kps


def test_indexed_gather_path_verifies(cache_and_keys):
    cache, kps = cache_and_keys
    msg = b"\x22" * 32
    sets = [
        bls.SignatureSet(kp.sk.sign(msg), [cache.get(i)], msg)
        for i, kp in enumerate(kps)
    ]
    assert bls.verify_signature_sets(sets, backend="tpu", seed=1)
    assert _last_marshal()["indexed"]

    # one forged signature breaks the whole batch
    bad = bls.SignatureSet(kps[0].sk.sign(b"other"), [cache.get(1)], msg)
    assert not bls.verify_signature_sets(
        sets[:3] + [bad], backend="tpu", seed=1
    )


def test_untagged_pubkeys_use_legacy_packing(cache_and_keys):
    _, kps = cache_and_keys
    msg = b"\x22" * 32
    raw_pk = bls.PublicKey.from_bytes(kps[0].pk.to_bytes())
    legacy = [bls.SignatureSet(kps[0].sk.sign(msg), [raw_pk], msg)]
    assert bls.verify_signature_sets(legacy, backend="tpu", seed=1)
    assert not _last_marshal()["indexed"]


def test_multi_key_aggregate_through_table(cache_and_keys):
    cache, kps = cache_and_keys
    msg = b"\x33" * 32
    agg = bls.aggregate_signatures([kp.sk.sign(msg) for kp in kps[:3]])
    aset = bls.SignatureSet(agg, [cache.get(i) for i in range(3)], msg)
    assert bls.verify_signature_sets([aset], backend="tpu", seed=2)
    assert _last_marshal()["indexed"]


def test_table_growth_after_new_validators(cache_and_keys):
    cache, kps = cache_and_keys
    table = cache.device_table()
    before = table.count
    extra = bls.Keypair(bls.SecretKey.from_bytes((99).to_bytes(32, "big")))
    state = _State(
        [kp.pk.to_bytes() for kp in kps] + [extra.pk.to_bytes()]
    )
    cache.import_new(state)
    assert cache.device_table().count == before + 1
    msg = b"\x44" * 32
    sset = bls.SignatureSet(extra.sk.sign(msg), [cache.get(before)], msg)
    assert bls.verify_signature_sets([sset], backend="tpu", seed=3)
    assert _last_marshal()["indexed"]


def test_one_bad_sig_fallback_two_device_calls(cache_and_keys):
    """VERDICT done-criterion: 1 bad signature in a batch -> exact
    per-item verdicts with <= 2 device dispatches."""
    cache, kps = cache_and_keys
    msg = b"\x55" * 32
    sets = [
        bls.SignatureSet(kp.sk.sign(msg), [cache.get(i)], msg)
        for i, kp in enumerate(kps)
    ]
    sets[5] = bls.SignatureSet(
        kps[5].sk.sign(b"forged"), [cache.get(5)], msg
    )

    tb.CALL_COUNTS["batch"] = 0
    tb.CALL_COUNTS["individual"] = 0
    ok = bls.verify_signature_sets(sets, backend="tpu", seed=7)
    assert not ok
    verdicts = bls.verify_signature_sets_individually(sets, backend="tpu")
    assert verdicts == [True] * 5 + [False] + [True] * 2
    assert tb.CALL_COUNTS["batch"] + tb.CALL_COUNTS["individual"] == 2


def test_individual_matches_ref_backend(cache_and_keys):
    cache, kps = cache_and_keys
    msg = b"\x66" * 32
    sets = []
    for i, kp in enumerate(kps[:4]):
        m = msg if i != 2 else b"wrong"
        sets.append(
            bls.SignatureSet(kp.sk.sign(msg), [cache.get(i)], m)
        )
    ref = bls.verify_signature_sets_individually(sets, backend="ref")
    tpu = bls.verify_signature_sets_individually(sets, backend="tpu")
    assert ref == tpu == [True, True, False, True]


def test_individual_subgroup_and_infinity_policy(cache_and_keys):
    cache, kps = cache_and_keys
    msg = b"\x77" * 32
    good = bls.SignatureSet(kps[0].sk.sign(msg), [cache.get(0)], msg)
    inf = bls.SignatureSet(
        bls.Signature.from_bytes(bls.INFINITY_SIGNATURE_BYTES),
        [cache.get(1)],
        msg,
    )
    verdicts = bls.verify_signature_sets_individually(
        [good, inf], backend="tpu"
    )
    assert verdicts == [True, False]


def test_message_cache_dedup(cache_and_keys):
    cache, kps = cache_and_keys
    tb._MSG_CACHE.clear()
    msg = b"\x88" * 32
    sets = [
        bls.SignatureSet(kp.sk.sign(msg), [cache.get(i)], msg)
        for i, kp in enumerate(kps[:4])
    ]
    assert bls.verify_signature_sets(sets, backend="tpu", seed=9)
    assert len(tb._MSG_CACHE) == 1  # one distinct message, hashed once


def test_batch_to_affine_matches_single():
    from lighthouse_tpu.crypto.ref_curve import G2 as G2_GROUP

    kps = [
        bls.Keypair(bls.SecretKey.from_bytes((i + 1).to_bytes(32, "big")))
        for i in range(5)
    ]
    pts = [kp.sk.sign(bytes([i]) * 8).point for i, kp in enumerate(kps)]
    pts.append(G2_GROUP.infinity)
    batched = tb.batch_to_affine_g2(pts)
    singles = [G2_GROUP.to_affine(p) for p in pts]
    assert batched == singles
    assert batched[-1] is None


def test_seeded_rlc_scalars_are_full_64_bit():
    """blst.rs:15 RAND_BITS parity: the seeded path must sample the whole
    64-bit range, not 63 bits."""
    tops = 0
    for seed in range(64):
        for s in tb._rlc_scalars(16, seed):
            assert 1 <= s < (1 << 64)
            if s >> 63:
                tops += 1
    # ~half of all samples should have the top bit set
    assert tops > 0
