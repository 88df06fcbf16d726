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
from lighthouse_tpu.bls.device_pubkey_table import DevicePubkeyTable
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.device_plane import canary
from lighthouse_tpu.ops import fieldb as fb
from lighthouse_tpu.state_processing.pubkey_cache import PubkeyCache


class _V:
    def __init__(self, pk_bytes):
        self.pubkey = pk_bytes


class _State:
    def __init__(self, pk_bytes_list):
        self.validators = [_V(b) for b in pk_bytes_list]


def _last_marshal(name="verify/marshal"):
    """Attributes of the newest span `name` the tracer holds."""
    return [
        m for r in tracing.TRACER.recent()
        for m in tracing.find(r, name)
    ][-1]["attrs"]


def _twin(kp):
    """`kp`'s public key decoded afresh: the same point, untagged."""
    return bls.PublicKey.from_bytes(kp.pk.to_bytes())


def _slots():
    fam = REGISTRY.get("lighthouse_tpu_pubkey_slots_total")
    return {
        path: fam.labels(path).value
        for path in ("table", "overflow", "packed")
    }


@pytest.fixture(scope="module")
def cache_and_keys():
    kps = [
        bls.Keypair(bls.SecretKey.from_bytes((i + 1).to_bytes(32, "big")))
        for i in range(8)
    ]
    cache = PubkeyCache()
    cache.import_new(_State([kp.pk.to_bytes() for kp in kps]))
    cache.device_table()  # as a chain on the TPU backend does at start-up
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

    # an untagged key rides the same program as an overflow row
    twin = list(sets)
    twin[3] = bls.SignatureSet(sets[3].signature, [_twin(kps[3])], msg)
    assert bls.verify_signature_sets(twin, backend="tpu", seed=1)
    assert _last_marshal()["indexed"]
    assert _last_marshal("verify/marshal/pubkeys")["overflow"] == 1
    # ... and is the key it claims to be: another key's twin fails
    twin[3] = bls.SignatureSet(sets[3].signature, [_twin(kps[4])], msg)
    assert not bls.verify_signature_sets(twin, backend="tpu", seed=1)
    assert _last_marshal("verify/marshal/pubkeys")["overflow"] == 1

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


def test_overflow_rows_are_the_twins_table_rows(cache_and_keys):
    """Host-only: an untagged key's overflow row is, limb for limb, its
    tagged twin's table row, indexed capacity + j; padding slots read
    row 0; the flat and grouped layouts follow one rule."""
    cache, kps = cache_and_keys
    sig = kps[0].sk.sign(b"overflow rows")
    sets = [
        bls.SignatureSet(sig, [cache.get(0), _twin(kps[2])], b"a" * 32),
        bls.SignatureSet(sig, [_twin(kps[5])], b"b" * 32),
        bls.SignatureSet(sig, [cache.get(1)], b"c" * 32),
    ]
    m = tb._marshal(sets, allow_grouped=False)
    tx, ty = (np.asarray(r) for r in m.table)
    ox, oy = m.overflow
    cap = tx.shape[0]
    assert m.pubkeys is None and tb._shape_key(m) == "s4k2e8"
    assert m.indices.tolist() == [
        [1, cap], [cap + 1, 0], [2, 0], [0, 0]
    ]
    assert ox.shape == oy.shape == (8, 1, fb.NB)
    for j, v in enumerate((2, 5)):
        assert np.array_equal(ox[j], tx[v + 1])
        assert np.array_equal(oy[j], ty[v + 1])
    assert not ox[2:].any() and not oy[2:].any()

    msg = b"d" * 32
    grouped = [
        bls.SignatureSet(sig, [_twin(kps[7])], msg),
        bls.SignatureSet(sig, [cache.get(6), cache.get(3)], msg),
    ]
    g = tb._marshal(grouped)
    assert g.grouped and tb._shape_key(g) == "g1x2k2e8"
    assert g.indices.tolist() == [[[cap, 0], [7, 4]]]
    assert np.array_equal(g.overflow[0][0], tx[8])
    assert np.array_equal(g.overflow[1][0], ty[8])


def test_sentinel_batch_takes_the_table(cache_and_keys):
    """Host-only: tagged sets plus the canary's untagged sentinel take
    the table path with one overflow slot; a batch with no tagged key
    (the canary pair) stays packed. The slot counter says which."""
    cache, kps = cache_and_keys
    sig = kps[0].sk.sign(b"sentinel batch")
    sentinel, invalid = canary.bls_sentinels()
    tagged = [
        bls.SignatureSet(sig, [cache.get(i), cache.get(i + 1)], bytes([i]))
        for i in range(3)
    ]
    before = _slots()
    m = tb._marshal(tagged + [sentinel], allow_grouped=False)
    assert m.table is not None and m.pubkeys is None
    assert m.indices[3, 0] == m.table[0].shape[0]
    after = _slots()
    assert after["table"] - before["table"] == 6
    assert after["overflow"] - before["overflow"] == 1
    assert after["packed"] == before["packed"]

    p = tb._marshal([sentinel, invalid], allow_grouped=False)
    assert p.table is None and p.indices is None and p.overflow is None
    assert p.pubkeys[0].shape == (4, 1, 1, fb.NB)
    assert tb._shape_key(p) == "s4k1"
    assert _slots()["packed"] - after["packed"] == 2


def test_marshal_never_builds_a_table():
    """Host-only: tagged keys whose cache has no table yet pack, and
    the marshal leaves the table unbuilt; once built, the same batch
    gathers from it."""
    kps = [
        bls.Keypair(bls.SecretKey.from_bytes((i + 11).to_bytes(32, "big")))
        for i in range(2)
    ]
    cache = PubkeyCache()
    cache.import_new(_State([kp.pk.to_bytes() for kp in kps]))
    sig = kps[0].sk.sign(b"no table")
    sets = [bls.SignatureSet(sig, [cache.get(0), cache.get(1)], b"e" * 32)]
    m = tb._marshal(sets, allow_grouped=False)
    assert m.table is None and m.pubkeys is not None
    assert cache.ready_table() is None and cache._device_table is None

    cache.device_table()
    m = tb._marshal(sets, allow_grouped=False)
    assert m.table is not None and m.indices[0, :2].tolist() == [1, 2]


def test_appends_match_a_table_built_at_once():
    """Host-only: appends within the capacity and past it read the same
    device rows as a table built from all the keys in one go."""
    pks = [
        bls.Keypair(
            bls.SecretKey.from_bytes((i + 21).to_bytes(32, "big"))
        ).pk
        for i in range(9)
    ]
    grown = DevicePubkeyTable()
    grown.append(pks[:2])
    grown.rows()
    # keys 3-5 fit the 8-row capacity; keys 6-9 grow it to 16 rows
    for lo, hi, cap in ((2, 5, 8), (5, 9, 16)):
        grown.append(pks[lo:hi])
        whole = DevicePubkeyTable()
        whole.append(pks[:hi])
        assert grown.count == whole.count == hi
        for a, b in zip(grown.rows(), whole.rows()):
            assert a.shape == (cap, 1, fb.NB)
            assert np.array_equal(np.asarray(a), np.asarray(b))


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
