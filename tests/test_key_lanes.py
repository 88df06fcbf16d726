"""Key lanes: a flat batch with a set wider than the lane width cuts every
set into lanes of at most KEY_LANE_WIDTH keys; the device sums each lane
and folds the lanes back into one sum per set before the per-set RLC
ladder.

At a lane width of 4 and sets of 1-13 keys, the device half runs the
program's own marshal, gather and lane fold (one tiny jitted program);
the batch equation over the folded sums, with the program's 64-bit
scalars, is finished on the host with the reference pairing, so no
pairing program compiles here. Each verdict is held to `bls.api`'s
reference verification of each set alone.
"""

from types import SimpleNamespace as NS

import numpy as np
import pytest

import jax

from lighthouse_tpu import bls
from lighthouse_tpu.bls import tpu_backend as tb
from lighthouse_tpu.bls.api import _verify_one_ref
from lighthouse_tpu.bls.hash_to_curve import hash_to_g2
from lighthouse_tpu.crypto import ref_fields as ff
from lighthouse_tpu.crypto import ref_pairing
from lighthouse_tpu.crypto.constants import H2, P, R
from lighthouse_tpu.crypto.ref_curve import G1, G2
from lighthouse_tpu.ops import batch_verify, fieldb as fb
from lighthouse_tpu.state_processing.pubkey_cache import PubkeyCache

W = 4
N_KEYS = 14
# keys per set: narrow and wide sets mixed; 1 + 4 + 1 + 3 = 9 lanes, so
# every case below reaches the one program s4l16k4e8
SIZES = (1, 13, 2, 9)


@pytest.fixture
def lane_width(monkeypatch):
    monkeypatch.setattr(tb, "KEY_LANE_WIDTH", W)


@pytest.fixture(scope="module")
def cache():
    """Validator v holds the secret key v + 1, tagged by a PubkeyCache
    whose HBM table is built, as a chain on the TPU backend builds it."""
    keys = [bls.SecretKey(v + 1).public_key() for v in range(N_KEYS)]
    c = PubkeyCache()
    c.import_new(NS(validators=[NS(pubkey=pk.to_bytes()) for pk in keys]))
    c.device_table()
    return c


def _sign(message, validators, extra=None):
    sig = bls.SecretKey(sum(v + 1 for v in validators) % R).sign(message)
    if extra is None:
        return sig
    return bls.Signature(G2.add(sig.point, extra))


def _sets(cache, sizes=SIZES, mods=None):
    """Sets of `sizes` keys over fresh messages; `mods` adds a G2 point
    to the signature of a set. The widest set's last key is untagged, so
    the batch also carries an overflow row."""
    mods = mods or {}
    out = []
    for i, k in enumerate(sizes):
        msg = b"key lanes %d" % i
        vs = list(range(k))
        pks = [cache.get(v) for v in vs]
        if k == max(sizes):
            pks[-1] = bls.PublicKey.from_bytes(pks[-1].to_bytes())
        out.append(bls.SignatureSet(_sign(msg, vs, mods.get(i)), pks, msg))
    return out


def _torsion_point():
    """A point of order 13 on the curve of G2, outside G2 (13^2 divides
    its cofactor)."""
    i = 0
    while True:
        x = (i, 1)
        i += 1
        y = ff.fp2_sqrt(ff.fp2_add(ff.fp2_mul(ff.fp2_sqr(x), x), G2.b))
        if y is None:
            continue
        t = G2.mul_scalar((x, y, ff.FP2_ONE), H2 * R // 169)
        if not G2.is_infinity(G2.mul_scalar(t, 13)):
            t = G2.mul_scalar(t, 13)
        if not G2.is_infinity(t):
            return t


def _sums(tx, ty, ox, oy, indices, key_mask, lane_map):
    pks = tb._gather_pubkeys(tx, ty, ox, oy, indices)
    return batch_verify.aggregate_pubkeys(pks, key_mask, lane_map)


_SUMS = jax.jit(_sums)


def _per_set_sums(m):
    """The program's gather and key aggregation, its lanes folded per
    set, for a marshalled batch -> one affine sum per set (None for the
    identity)."""
    out = _SUMS(*m.table, *m.overflow, m.indices, m.key_mask, m.lane_map)
    xs, ys, zs = (
        np.asarray(fb.from_mont(c)).reshape(-1, fb.NB) for c in out
    )
    sums = []
    for row in zip(xs, ys, zs):
        x, y, z = fb.unpack_ints(np.stack(row))
        zi = pow(z, P - 2, P)
        sums.append(None if z % P == 0 else (x * zi % P, y * zi % P))
    return sums


def _batch_verdict(sets, m):
    """prod_i e(r_i sum_i, H(m_i)) e(-G1, sum_i r_i sig_i) == 1 over the
    device's per-set sums, with the program's RLC scalars."""
    sums = _per_set_sums(m)
    r = tb._rlc_scalars(len(sets), 7)
    pairs = []
    sig = G2.infinity
    for s, agg, ri in zip(sets, sums, r):
        if agg is not None:
            pk = G1.mul_scalar(G1.from_affine(agg), ri)
            pairs.append((G1.to_affine(pk), G2.to_affine(hash_to_g2(s.message))))
        sig = G2.add(sig, G2.mul_scalar(s.signature.point, ri))
    pairs.append((G1.to_affine(G1.neg(G1.generator)), G2.to_affine(sig)))
    return ref_pairing.multi_pairing_is_one(pairs)


def _drop_lane(m):
    m.lane_map[0][2] = -1  # a middle lane of the 13-key set


def _lane_to_wrong_set(m):
    m.lane_map[0][4] = 2  # the 13-key set's last lane, given to the next


DELTA = hash_to_g2(b"key lanes adversary")

CASES = {
    "valid": ({}, None, True),
    "forged wide set": ({3: G2.generator}, None, False),
    "cancelling pair on two wide sets": (
        {1: DELTA, 3: G2.neg(DELTA)}, None, False,
    ),
    "lane dropped from the fold": ({}, _drop_lane, False),
    "lane mapped to the wrong set": ({}, _lane_to_wrong_set, False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_lanes_give_the_reference_verdict(case, cache, lane_width):
    mods, fault, want = CASES[case]
    sets = _sets(cache, mods=mods)
    m = tb._marshal(sets)
    assert tb._shape_key(m) == "s4l16k4e8"
    lane_set, last_lane = m.lane_map
    assert lane_set.tolist()[:9] == [0, 1, 1, 1, 1, 2, 3, 3, 3]
    assert last_lane.tolist() == [0, 4, 5, 8]
    if fault is None:
        assert want == all(_verify_one_ref(s) for s in sets)
    else:
        fault(m)
    assert _batch_verdict(sets, m) is want


def test_torsion_signature_on_a_wide_set_is_refused(cache, lane_width):
    sets = _sets(cache, mods={1: _torsion_point()})
    assert not _verify_one_ref(sets[1])
    # refused by the subgroup check, before a marshal or a program
    assert tb.verify_signature_sets_tpu(sets) is False


@pytest.mark.parametrize(
    "sizes, shape",
    [
        ((1, 3, 2), "s4k4e8"),  # every set fits a lane: today's layout
        ((4, 4, 1, 2, 4), "s8k4e8"),
        ((5, 1), "s4l4k4e8"),
    ],
)
def test_shape_key(sizes, shape, cache, lane_width):
    m = tb._marshal(_sets(cache, sizes), allow_grouped=False)
    assert tb._shape_key(m) == shape
    assert (m.lane_map is None) == ("l" not in shape)


def _block(sizes):
    return [NS(pubkeys=[None] * k) for k in sizes]


@pytest.mark.parametrize(
    "name, sizes, lanes, width",
    [
        # a Gnosis block and the bus's sentinel: 132 sets of at most 512
        # keys keep the s256k512 grid and program, with no lane map
        ("gnosis", [1, 1] + [292] * 64 + [293] * 64 + [512, 1], None, 512),
        # Electra blocks at 1M validators, head vote 45% and 95% of each
        # slot's 31,250 attesters, the rest split 6:3:1
        ("electra-45", [1, 1] + [14063, 10313, 5156, 1718] * 2 + [512, 1],
         256, 512),
        ("electra-95", [1, 1] + [29688, 938, 468, 156] * 2 + [512, 1],
         128, 512),
    ],
)
def test_block_layouts(name, sizes, lanes, width):
    got, lane_map, k = tb._key_lanes(_block(sizes), tb._bucket(len(sizes), 4))
    assert k == width
    if lanes is None:
        assert lane_map is None and len(got) == 256
    else:
        lane_set, last_lane = lane_map
        assert len(got) == lane_set.shape[0] == lanes
        live = lane_set[lane_set >= 0]
        assert np.all(np.diff(live) >= 0) and live[-1] == len(sizes) - 1
        # each set's last lane is the last lane the map gives it
        assert last_lane.shape == (16,)
        for i in range(len(sizes)):
            assert last_lane[i] == np.flatnonzero(lane_set == i)[-1]
        assert np.all(last_lane[len(sizes):] == -1)
