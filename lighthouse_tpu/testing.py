"""Deterministic signature-set fixtures for tests, benches, and the graft
entry — the analog of the reference's deterministic interop keypairs
(common/eth2_interop_keypairs) + BeaconChainHarness test rigs.

Message points are generated as scalar multiples of the G2 generator: a
stand-in for hash-to-curve with identical device-side cost (the pairing does
not care how H(m) was produced). `lighthouse_tpu.bls` layers real RFC-9380
hashing on top for protocol use.
"""

import random

import numpy as np

from lighthouse_tpu.crypto import constants as C
from lighthouse_tpu.crypto.ref_curve import G1 as RG1
from lighthouse_tpu.crypto.ref_curve import G2 as RG2
from lighthouse_tpu.ops import batch_verify, curve, fieldb as fb, fp2


def _pack_g1_affine(pts):
    """[(x, y) or None, ...] -> affine Montgomery (N, 1, NB) bundle pair;
    None -> (0, 0) placeholder (masked out downstream)."""
    xs = np.stack([fb.pack_ints([0 if p is None else p[0]]) for p in pts])
    ys = np.stack([fb.pack_ints([0 if p is None else p[1]]) for p in pts])
    return (fb.to_mont(xs), fb.to_mont(ys))


def _pack_g2_affine(pts):
    zero2 = (0, 0)
    xs = fp2.pack([zero2 if p is None else p[0] for p in pts])
    ys = fp2.pack([zero2 if p is None else p[1] for p in pts])
    return (fb.to_mont(xs), fb.to_mont(ys))


def pack_sets_from_points(msgs, sigs, pk_rows, rand_scalars):
    """Pack explicit affine points into the 6-tuple of device inputs for
    `ops.batch_verify.verify_signature_sets`.

    msgs/sigs: affine G2 points, one per set; pk_rows: per-set lists of
    affine G1 points (ragged; padded with None to the widest row)."""
    n_sets = len(msgs)
    max_keys = max(len(r) for r in pk_rows)
    padded = [list(r) + [None] * (max_keys - len(r)) for r in pk_rows]
    mask_rows = [
        [True] * len(r) + [False] * (max_keys - len(r)) for r in pk_rows
    ]
    flat_pks = [p for row in padded for p in row]
    pk_x, pk_y = _pack_g1_affine(flat_pks)
    pubkeys = (
        np.asarray(pk_x).reshape(n_sets, max_keys, 1, fb.NB),
        np.asarray(pk_y).reshape(n_sets, max_keys, 1, fb.NB),
    )
    return (
        _pack_g2_affine(msgs),
        _pack_g2_affine(sigs),
        pubkeys,
        np.array(mask_rows, dtype=bool),
        curve.scalars_to_bits(rand_scalars, batch_verify.RAND_BITS),
        np.ones(n_sets, dtype=bool),
    )


def make_aggregate_set_batch(
    n_sets: int, n_keys: int, seed: int = 0, keys_per_set=None
):
    """Aggregate-signature fixtures: each set is ONE aggregate signature
    over one distinct message by a fixed (or per-set, via
    `keys_per_set`) number of distinct pubkeys. Shapes:

      * BASELINE config #2 (sync-committee fast_aggregate_verify,
        signature_sets.rs sync_aggregate role): n_keys=512;
      * BASELINE config #3 (full-block BlockSignatureVerifier): a
        ragged keys_per_set list — single-key proposal/randao/exit sets
        plus committee-sized attestation aggregates.

    Built with running point sums — O(total keys) additions + O(S)
    scalar muls — so S=64 x K=512 packs in seconds. Keys are assigned
    sequentially across sets, so set j (starting at global key base_j)
    has aggregate secret K_j*base_j + K_j*(K_j+1)/2 and its aggregate
    signature is one scalar mul of the set's message point."""
    rng = random.Random(seed)
    if keys_per_set is None:
        keys_per_set = [n_keys] * n_sets
    else:
        n_sets = len(keys_per_set)  # the list IS the shape
    msgs, sigs, pk_rows = [], [], []
    running_pk = RG1.infinity
    base = 0
    for j in range(n_sets):
        k = keys_per_set[j]
        h = RG2.mul_scalar(RG2.generator, rng.randrange(2, C.R))
        msgs.append(RG2.to_affine(h))
        row = []
        for _ in range(k):
            running_pk = RG1.add(running_pk, RG1.generator)
            row.append(RG1.to_affine(running_pk))
        pk_rows.append(row)
        agg_sk = (k * base + k * (k + 1) // 2) % C.R
        sigs.append(RG2.to_affine(RG2.mul_scalar(h, agg_sk)))
        base += k
    rand_scalars = [
        rng.randrange(1, 1 << batch_verify.RAND_BITS) for _ in range(n_sets)
    ]
    return pack_sets_from_points(msgs, sigs, pk_rows, rand_scalars)


def make_block_sets_batch(seed: int = 0, n_attestations: int = 128,
                          committee_size: int = 256):
    """BASELINE config #3 shape — every signature set of one full
    mainnet-ish block as BlockSignatureVerifier collects them
    (block_signature_verifier.rs:120-333): proposal + randao (single
    key), `n_attestations` committee aggregates, and two exits."""
    keys = [1, 1] + [committee_size] * n_attestations + [1, 1]
    return make_aggregate_set_batch(0, 0, seed=seed, keys_per_set=keys)


def make_signature_set_batch(
    n_sets: int,
    max_keys: int = 1,
    seed: int = 0,
    corrupt_indices: tuple = (),
    fast_sequential: bool = False,
):
    """Build a batch of valid BLS signature sets (optionally corrupting some).

    fast_sequential: secret keys are 1..N and points are built by running
    point additions instead of full scalar muls — O(N) instead of O(N*255);
    used for large benchmark batches.

    Returns the 6-tuple of device inputs for
    `ops.batch_verify.verify_signature_sets`.
    """
    rng = random.Random(seed)

    msgs, sigs, pk_rows, mask_rows = [], [], [], []
    if fast_sequential:
        h_scalar = rng.randrange(2, C.R)
        h = RG2.mul_scalar(RG2.generator, h_scalar)
        h_aff = RG2.to_affine(h)
        running_pk = RG1.infinity
        running_sig = RG2.infinity
        for i in range(n_sets):
            running_pk = RG1.add(running_pk, RG1.generator)  # (i+1) * G1
            running_sig = RG2.add(running_sig, h)            # (i+1) * H
            msgs.append(h_aff)
            sigs.append(RG2.to_affine(running_sig))
            pk_rows.append(
                [RG1.to_affine(running_pk)] + [None] * (max_keys - 1)
            )
            mask_rows.append([True] + [False] * (max_keys - 1))
    else:
        for i in range(n_sets):
            n_keys = rng.randrange(1, max_keys + 1)
            sks = [rng.randrange(2, C.R) for _ in range(n_keys)]
            h = RG2.mul_scalar(RG2.generator, rng.randrange(2, C.R))
            msgs.append(RG2.to_affine(h))
            agg_sig = RG2.infinity
            row = []
            for sk in sks:
                row.append(RG1.to_affine(RG1.mul_scalar(RG1.generator, sk)))
                agg_sig = RG2.add(agg_sig, RG2.mul_scalar(h, sk))
            sigs.append(RG2.to_affine(agg_sig))
            pk_rows.append(row + [None] * (max_keys - n_keys))
            mask_rows.append(
                [True] * n_keys + [False] * (max_keys - n_keys)
            )

    for idx in corrupt_indices:
        # corrupt the signature: use 7*H instead of the true aggregate
        bad = RG2.to_affine(
            RG2.mul_scalar(RG2.from_affine(msgs[idx]), 7)
        )
        sigs[idx] = bad

    flat_pks = [p for row in pk_rows for p in row]
    pk_x, pk_y = _pack_g1_affine(flat_pks)
    pubkeys = (
        np.asarray(pk_x).reshape(n_sets, max_keys, 1, fb.NB),
        np.asarray(pk_y).reshape(n_sets, max_keys, 1, fb.NB),
    )
    key_mask = np.array(mask_rows, dtype=bool)
    set_mask = np.ones(n_sets, dtype=bool)
    rand_scalars = [
        rng.randrange(1, 1 << batch_verify.RAND_BITS) for _ in range(n_sets)
    ]
    rand_bits = curve.scalars_to_bits(rand_scalars, batch_verify.RAND_BITS)

    return (
        _pack_g2_affine(msgs),
        _pack_g2_affine(sigs),
        pubkeys,
        key_mask,
        rand_bits,
        set_mask,
    )


def make_grouped_signature_set_batch(
    n_groups: int,
    sets_per_group: int,
    max_keys: int = 1,
    seed: int = 0,
    corrupt_indices: tuple = (),
    fast_sequential: bool = False,
    build_flat: bool = True,
):
    """Committee-shaped fixture: `n_groups` distinct messages with
    `sets_per_group` signature sets each — the gossip attestation load
    (~64 committees over >=30k sets) that the message-grouped pairing
    merge collapses to G+1 Miller loops.

    Returns (grouped_args, flat_args): the 7-tuple for
    verify_signature_sets_grouped and the SAME sets flattened as the
    6-tuple for verify_signature_sets, so tests can assert verdict
    equality. `corrupt_indices`: (group, set) pairs whose signature is
    replaced with a forgery. `build_flat=False` skips the flat copy
    (flat_args is None) — the bench shape repeats 30k message points
    for nothing."""
    rng = random.Random(seed)
    G, Sg, K = n_groups, sets_per_group, max_keys

    group_msgs = []
    sigs_grid, pk_grid, km_grid = [], [], []
    if fast_sequential:
        # secret keys are 1..Sg within each group; points built by
        # running additions — O(G*Sg) adds instead of O(G*Sg*255)
        # doublings (the 30k-set bench shape would otherwise take hours
        # of pure-Python scalar muls)
        assert K == 1, "fast_sequential supports single-key sets"
        for g in range(G):
            h = RG2.mul_scalar(RG2.generator, rng.randrange(2, C.R))
            group_msgs.append(RG2.to_affine(h))
            running_pk = RG1.infinity
            running_sig = RG2.infinity
            for s in range(Sg):
                running_pk = RG1.add(running_pk, RG1.generator)
                running_sig = RG2.add(running_sig, h)
                sigs_grid.append(RG2.to_affine(running_sig))
                pk_grid.append([RG1.to_affine(running_pk)])
                km_grid.append([True])
    else:
        for g in range(G):
            h = RG2.mul_scalar(RG2.generator, rng.randrange(2, C.R))
            group_msgs.append(RG2.to_affine(h))
            for s in range(Sg):
                n_keys = rng.randrange(1, K + 1)
                sks = [rng.randrange(2, C.R) for _ in range(n_keys)]
                agg_sig = RG2.infinity
                row = []
                for sk in sks:
                    row.append(
                        RG1.to_affine(RG1.mul_scalar(RG1.generator, sk))
                    )
                    agg_sig = RG2.add(agg_sig, RG2.mul_scalar(h, sk))
                sigs_grid.append(RG2.to_affine(agg_sig))
                pk_grid.append(row + [None] * (K - n_keys))
                km_grid.append([True] * n_keys + [False] * (K - n_keys))
    for g, s in corrupt_indices:
        # forge by adding one extra H to the true signature: always
        # invalid for this set's keys (a fixed scalar like 7 would
        # COLLIDE with fast_sequential's secret key 7 and be valid)
        sigs_grid[g * Sg + s] = RG2.to_affine(
            RG2.add(
                RG2.from_affine(sigs_grid[g * Sg + s]),
                RG2.from_affine(group_msgs[g]),
            )
        )

    flat_pks = [p for row in pk_grid for p in row]
    pk_x, pk_y = _pack_g1_affine(flat_pks)
    pubkeys_flat = (
        np.asarray(pk_x).reshape(G * Sg, K, 1, fb.NB),
        np.asarray(pk_y).reshape(G * Sg, K, 1, fb.NB),
    )
    sig_pack = tuple(
        np.asarray(c) for c in _pack_g2_affine(sigs_grid)
    )
    key_mask = np.array(km_grid, dtype=bool)
    rand_scalars = [
        rng.randrange(1, 1 << batch_verify.RAND_BITS)
        for _ in range(G * Sg)
    ]
    rand_bits = curve.scalars_to_bits(
        rand_scalars, batch_verify.RAND_BITS
    )
    set_mask = np.ones(G * Sg, dtype=bool)

    grouped = (
        _pack_g2_affine(group_msgs),
        tuple(c.reshape(G, Sg, 2, fb.NB) for c in sig_pack),
        tuple(c.reshape(G, Sg, K, 1, fb.NB) for c in pubkeys_flat),
        key_mask.reshape(G, Sg, K),
        rand_bits.reshape(G, Sg, batch_verify.RAND_BITS),
        set_mask.reshape(G, Sg),
        np.ones(G, dtype=bool),
    )
    if not build_flat:
        return grouped, None
    flat_msgs = [group_msgs[g] for g in range(G) for _ in range(Sg)]
    flat = (
        _pack_g2_affine(flat_msgs),
        sig_pack,
        pubkeys_flat,
        key_mask,
        rand_bits,
        set_mask,
    )
    return grouped, flat


def make_api_signature_sets(
    n_messages: int,
    sets_per_message: int,
    keys_per_set: int = 1,
    seed: int = 0,
):
    """`bls.SignatureSet` objects for the node's API boundary
    (`bls.api.verify_signature_sets`): `n_messages` distinct seeded
    32-byte messages (real hash-to-curve), `sets_per_message` sets
    each, every set signed by `keys_per_set` keys (one aggregate
    signature). Secret keys are 1..N in set order and points are built
    by running additions — O(N) group additions plus one short scalar
    multiplication per message, as `fast_sequential` above — so the
    30720-set mainnet slot builds in seconds. The signatures are NOT
    pre-marked as subgroup-checked: verification pays that host check
    as it would for gossip."""
    from lighthouse_tpu.bls.api import PublicKey, Signature, SignatureSet
    from lighthouse_tpu.bls.hash_to_curve import hash_to_g2

    rng = random.Random(seed)
    sets = []
    running_pk = RG1.infinity
    sk = 0  # last secret key handed out
    for _ in range(n_messages):
        message = rng.getrandbits(256).to_bytes(32, "big")
        h = hash_to_g2(message)
        sig_step = RG2.mul_scalar(h, keys_per_set)
        # first set of this message: keys sk+1..sk+k sum to
        # k*sk + k(k+1)/2; each next set's sum grows by k*k
        agg_sk = keys_per_set * sk + keys_per_set * (keys_per_set + 1) // 2
        sig = RG2.mul_scalar(h, agg_sk)
        for _ in range(sets_per_message):
            pks = []
            for _ in range(keys_per_set):
                running_pk = RG1.add(running_pk, RG1.generator)
                pks.append(PublicKey(running_pk))
            sets.append(SignatureSet(Signature(sig), pks, message))
            for _ in range(keys_per_set):
                sig = RG2.add(sig, sig_step)
            sk += keys_per_set
    return sets


def forge_signature_set(sset):
    """The same set with its signature moved off the valid point (the
    message point added once): never valid for the set's keys."""
    from lighthouse_tpu.bls.api import Signature, SignatureSet
    from lighthouse_tpu.bls.hash_to_curve import hash_to_g2

    bad = RG2.add(sset.signature.point, hash_to_g2(sset.message))
    return SignatureSet(Signature(bad), sset.pubkeys, sset.message)


def make_junk_attestation(t, spec, slot: int, tag: bytes):
    """A structurally-valid attestation that fails CHEAP stateful
    checks deterministically (committee index 63 is far out of range
    for the minimal preset) — flood fixtures for the overload plane:
    the processor queue pays for it, the crypto plane never does.
    `tag` is the caller's seeded correlation bytes (32), so two flood
    producers with different seed schemes stay byte-distinct. Shared
    by sim/orchestrator's att_flood actor and bench_serve's gossip
    flood so the reject path they exercise cannot drift apart."""
    epoch = spec.slot_to_epoch(slot)
    return t.Attestation(
        aggregation_bits=[True] * 4,
        data=t.AttestationData(
            slot=slot,
            index=63,
            beacon_block_root=tag,
            source=t.Checkpoint(epoch=max(0, epoch - 1), root=tag),
            target=t.Checkpoint(epoch=epoch, root=tag),
        ),
        signature=tag * 3,
    )
