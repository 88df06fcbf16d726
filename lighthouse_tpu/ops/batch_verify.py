"""Device data plane for batched BLS signature-set verification (bundles).

The TPU-native core of the north-star boundary — the role of
`verify_signature_sets` in the reference client
(crypto/bls/src/impls/blst.rs:36-119): S signature sets, each with a
message point H(m) in G2, a signature in G2, and up to K public keys in
G1, verified with ONE multi-pairing via the random-linear-combination
trick:

    prod_i [ e(r_i * agg_pk_i, H_i) ] * e(-G1, sum_i r_i * sig_i)  ==  1

Static shapes (S sets x K padded keys) with boolean masks — the TPU-native
replacement for per-set heap vectors. A pair whose RLC'd aggregate pubkey
is infinity is masked out (exact: e(inf, H) == 1); a forged signature
still breaks the identity through the signature-sum pair.

Shapes: G2 affine = pair of (..., 2, NB) bundles; G1 affine = pair of
(..., 1, NB); pubkeys = ((S, K, 1, NB), (S, K, 1, NB)), or with key
lanes ((L, W, 1, NB), ...) plus a lane map ((L,) int32 lane -> set,
(S,) int32 last lane of each set), folded into the S sets on device
(`aggregate_pubkeys`).

Host-side policy (empty-set rejection, infinity-pubkey rejection, point
decompression, subgroup checks, RLC sampling) lives in `lighthouse_tpu.bls`.
"""

import numpy as np

import jax.numpy as jnp

from lighthouse_tpu.common.tracing import span
from lighthouse_tpu.crypto.constants import G1_X, G1_Y, P, R
from lighthouse_tpu.ops import curve, fieldb as fb, pairing
from lighthouse_tpu.ops import window_ladder as wl

NB = fb.NB

# group order bits (LSB-first) for the device subgroup check
_R_BITS = curve.scalars_to_bits([R], R.bit_length())


def _mont1(v: int) -> np.ndarray:
    return fb._limbs((v << 384) % P, NB)[None, :]


# -G1 generator, affine Montgomery (static constant for the signature pair).
NEG_G1_AFFINE = (_mont1(G1_X), _mont1((P - G1_Y) % P))

RAND_BITS = 64  # >= 64-bit RLC scalars, matching the reference


def _expand0(pt):
    return tuple(c[None] for c in pt)


def aggregate_pubkeys(pubkeys_g1_aff, key_mask, lane_map=None):
    """(S, K) affine G1 + mask -> (S,) projective aggregate per set
    (log-depth tree fold over the key axis, complete-formula plane).
    from_affine already maps masked-out lanes to the identity.

    With a `lane_map` the leading axis holds key lanes, not sets:
    (L, W) keys, lane l a run of one set's keys; each lane is summed as
    above, then `fold_lanes` folds them into one sum per set."""
    pts = curve.PG1.from_affine(pubkeys_g1_aff, key_mask)
    agg = curve.PG1.sum_axis(pts, axis=1)
    if lane_map is None:
        return agg
    return fold_lanes(agg, *lane_map)


def fold_lanes(lane_aggs, lane_set, last_lane):
    """(L,) projective lane sums -> (S,) per-set sums, given the (L,)
    int32 lane -> set map and each set's (S,) int32 last lane. The lanes
    of a set are contiguous (the host lays them out set by set; padding
    lanes map to -1): a segmented log-depth scan leaves at each lane the
    sum of its set's lanes up to it, and each set reads its last lane. A
    set with no lane (last lane -1) reads the identity."""
    import jax

    G = curve.PG1
    L = lane_set.shape[0]
    lanes = jnp.arange(L, dtype=jnp.int32)

    def step(k, acc):
        d = jnp.left_shift(1, k)
        prev = tuple(jnp.roll(c, d, axis=0) for c in acc)
        same = (lanes >= d) & (jnp.roll(lane_set, d) == lane_set)
        return G.select(same, G.add(acc, prev), acc)

    acc = jax.lax.fori_loop(0, (L - 1).bit_length(), step, lane_aggs)
    picked = tuple(jnp.take(c, jnp.maximum(last_lane, 0), axis=0) for c in acc)
    return G.select(last_lane >= 0, picked, G.identity_like(picked))


def rlc_combined_signature(sigs_g2_aff, rand_bits, set_mask):
    """sum_i r_i * sig_i -> single projective G2 point. Masked-out lanes
    enter as the identity and stay the identity through the ladder
    (the shared window kernel — ops.window_ladder)."""
    sig_proj = curve.PG2.from_affine(sigs_g2_aff, set_mask)
    sig_r = wl.ladder(curve.PG2, sig_proj, rand_bits)
    return curve.PG2.sum_axis(sig_r, axis=0)


def _assemble_pairs(
    msgs_g2_aff, set_mask, pk_aff, sig_aff
):
    """Assemble the (S+1)-pair multi-pairing inputs from the affinized
    RLC'd pubkeys and signature sum — shared by the XLA and Pallas
    input builders so the pair/mask rules cannot diverge."""
    pk_x, pk_y, pk_inf = pk_aff
    s_x, s_y, s_inf = sig_aff
    neg_g1 = (
        jnp.asarray(NEG_G1_AFFINE[0])[None],
        jnp.asarray(NEG_G1_AFFINE[1])[None],
    )
    g1_side = (
        jnp.concatenate([pk_x, neg_g1[0]], axis=0),
        jnp.concatenate([pk_y, neg_g1[1]], axis=0),
    )
    g2_side = (
        jnp.concatenate([msgs_g2_aff[0], s_x], axis=0),
        jnp.concatenate([msgs_g2_aff[1], s_y], axis=0),
    )
    pair_mask = jnp.concatenate([set_mask & ~pk_inf, ~s_inf], axis=0)
    return g1_side, g2_side, pair_mask


def miller_inputs(
    msgs_g2_aff, sigs_g2_aff, pubkeys_g1_aff, key_mask, rand_bits, set_mask,
    lane_map=None,
):
    """Build the (S+1)-pair multi-pairing inputs; shared with the sharded
    path. The `trace/*` spans attribute JAX TRACE time per stage — they
    fire once per (re)compile of the enclosing jit, not per dispatch."""
    with span("trace/pubkey_aggregation"):
        agg_pk = aggregate_pubkeys(pubkeys_g1_aff, key_mask, lane_map)
    with span("trace/rlc_ladder_g1"):
        agg_pk_r = wl.ladder(curve.PG1, agg_pk, rand_bits)
    pk_aff = curve.PG1.to_affine(agg_pk_r)

    with span("trace/rlc_ladder_g2"):
        sig_acc = rlc_combined_signature(sigs_g2_aff, rand_bits, set_mask)
    sig_aff = curve.PG2.to_affine(_expand0(sig_acc))
    return _assemble_pairs(msgs_g2_aff, set_mask, pk_aff, sig_aff)


def verify_signature_sets(
    msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    rand_bits,
    set_mask,
    lane_map=None,
):
    """One-shot batched verification of S signature sets on one chip.
    Returns a scalar bool — True iff every real set verifies. With
    `lane_map`, pubkeys and key_mask are (L, W) key lanes folded into
    the S sets (`aggregate_pubkeys`)."""
    g1_side, g2_side, pair_mask = miller_inputs(
        msgs_g2_aff, sigs_g2_aff, pubkeys_g1_aff, key_mask, rand_bits,
        set_mask, lane_map,
    )
    return pairing.multi_pairing_is_one(g1_side, g2_side, pair_mask)


def _grouped_pair_inputs(pk_aff, sig_aff, group_msgs_g2_aff, group_mask):
    return _assemble_pairs(group_msgs_g2_aff, group_mask, pk_aff, sig_aff)


def grouped_miller_inputs(
    group_msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    rand_bits,
    set_mask,
    group_mask,
):
    """Multi-pairing inputs for the MESSAGE-GROUPED batch check.

    Sets sharing one message merge into one pair by bilinearity:

        prod_i e(r_i*pk_i, H(m_i))
          = prod_g e( sum_{i in g} r_i*pk_i, H(M_g) )

    so G distinct messages need G Miller loops instead of S — the real
    mainnet slot load is ~64 committees over >=30k attestation sets
    (SURVEY §3.3), a ~500x reduction of the dominant pairing work. The
    RLC stays PER SET (r_i sampled per set, exactly the ungrouped
    check's product reassociated), so soundness is unchanged.

    Grid layout (host bins sets by message): sigs/pubkeys/key_mask/
    rand_bits/set_mask carry leading (G, Sg) axes; group_msgs and
    group_mask are (G,)-shaped. Padding sets have all-False key masks
    and set_mask False; their aggregates enter group folds as the
    identity."""
    G_, Sg = set_mask.shape

    # per-set aggregate over K keys, then the per-set RLC ladder — all
    # on the (G, Sg) grid (the group primitives take any leading batch)
    with span("trace/pubkey_aggregation"):
        agg_pk = curve.PG1.sum_axis(
            curve.PG1.from_affine(pubkeys_g1_aff, key_mask), axis=2
        )
    with span("trace/rlc_ladder_g1"):
        agg_pk_r = wl.ladder(curve.PG1, agg_pk, rand_bits)
    # fold each group's RLC'd pubkeys into one point per message
    with span("trace/msm_group_fold"):
        grp_pk = curve.PG1.sum_axis(agg_pk_r, axis=1)  # (G,)
    pk_aff = curve.PG1.to_affine(grp_pk)

    # signature side is unchanged by grouping: one global RLC sum
    with span("trace/rlc_ladder_g2"):
        sig_proj = curve.PG2.from_affine(sigs_g2_aff, set_mask)
        sig_r = wl.ladder(curve.PG2, sig_proj, rand_bits)
        sig_acc = curve.PG2.sum_axis(
            curve.PG2.sum_axis(sig_r, axis=1), axis=0
        )
    sig_aff = curve.PG2.to_affine(_expand0(sig_acc))
    return _grouped_pair_inputs(
        pk_aff, sig_aff, group_msgs_g2_aff, group_mask
    )


def verify_signature_sets_grouped(
    group_msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    rand_bits,
    set_mask,
    group_mask,
):
    """Batched verification with message-grouped pairing merge: (G+1)
    Miller loops for S sets over G distinct messages. Verdict-equivalent
    to verify_signature_sets on the flattened sets (tested)."""
    g1_side, g2_side, pair_mask = grouped_miller_inputs(
        group_msgs_g2_aff, sigs_g2_aff, pubkeys_g1_aff, key_mask,
        rand_bits, set_mask, group_mask,
    )
    return pairing.multi_pairing_is_one(g1_side, g2_side, pair_mask)


def verify_signature_sets_grouped_pallas(
    group_msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    rand_bits,
    set_mask,
    group_mask,
    block_b: int = 128,
    interpret: bool = False,
    tail: bool = False,
):
    """The grouped check with the RLC ladders and the (G+1)-pair Miller
    loop running as the same fused Pallas kernels the flat path uses —
    ladders over the flattened (G*Sg) lane axis, Miller over the G+1
    merged pairs (via the shared _pairs_to_verdict_pallas tail; with
    `tail=True` the fold + final exponentiation run in-kernel, same
    knob as the flat path — part of the backend's unified dispatch)."""
    from lighthouse_tpu.ops import tcurve, tfield as tf
    from lighthouse_tpu.ops.pallas_ladder import ladder_pallas

    G_, Sg = set_mask.shape
    S = G_ * Sg

    def flat(c):
        return c.reshape((S,) + c.shape[2:])

    bits_t = jnp.transpose(
        rand_bits.reshape(S, rand_bits.shape[-1])
    ).astype(jnp.int32)

    # G1 ladders over all S sets (padding sets ride as identities)
    agg_pk = curve.PG1.sum_axis(
        curve.PG1.from_affine(pubkeys_g1_aff, key_mask), axis=2
    )
    agg_t = tuple(tf.from_batchlead(flat(c)) for c in agg_pk)
    agg_t = _pad_lanes_projective(agg_t, block_b, tcurve.TPG1)
    padded = agg_t[0].shape[-1] - S
    bits_pad = jnp.pad(bits_t, ((0, 0), (0, padded)))
    pk_r_t = ladder_pallas(
        agg_t, bits_pad, group_name="G1", block_b=block_b,
        interpret=interpret,
    )
    pk_r = tuple(tf.to_batchlead(c)[:S] for c in pk_r_t)
    pk_r = tuple(c.reshape((G_, Sg) + c.shape[1:]) for c in pk_r)
    grp_pk = curve.PG1.sum_axis(pk_r, axis=1)  # (G,)
    pk_aff = curve.PG1.to_affine(grp_pk)

    # G2 ladders over the signatures + global fold
    sx, sy = (tf.from_batchlead(flat(c)) for c in sigs_g2_aff)
    sig_t = tcurve.TPG2.from_affine((sx, sy), set_mask.reshape(S))
    sig_t = _pad_lanes_projective(sig_t, block_b, tcurve.TPG2)
    sig_r_t = ladder_pallas(
        sig_t, bits_pad, group_name="G2", block_b=block_b,
        interpret=interpret,
    )
    sig_r = tuple(tf.to_batchlead(c)[:S] for c in sig_r_t)
    sig_acc = curve.PG2.sum_axis(sig_r, axis=0)
    sig_aff = curve.PG2.to_affine(_expand0(sig_acc))

    g1_side, g2_side, pair_mask = _grouped_pair_inputs(
        pk_aff, sig_aff, group_msgs_g2_aff, group_mask
    )
    return _pairs_to_verdict_pallas(
        g1_side, g2_side, pair_mask, block_b=block_b,
        interpret=interpret, tail=tail,
    )


def _pairs_to_verdict_pallas(
    g1_side, g2_side, pair_mask, block_b: int = 128,
    interpret: bool = False, tail: bool = False,
):
    """Pad the pair axis to a lane-tile multiple, run the fused Miller
    kernel, fold + final-exp (in-kernel with tail=True) — the shared
    back half of every Pallas verify variant."""
    from lighthouse_tpu.ops import tfield as tf, tower
    from lighthouse_tpu.ops.pallas_miller import miller_loop_pallas

    n_pairs = g1_side[0].shape[0]
    pad = (-n_pairs) % block_b
    if pad:
        def pad0(c):
            widths = [(0, pad)] + [(0, 0)] * (c.ndim - 1)
            return jnp.pad(c, widths)

        g1_side = tuple(pad0(c) for c in g1_side)
        g2_side = tuple(pad0(c) for c in g2_side)
        pair_mask = jnp.pad(pair_mask, (0, pad))
    p_t = tuple(tf.from_batchlead(c) for c in g1_side)
    q_t = tuple(tf.from_batchlead(c) for c in g2_side)
    f_t = miller_loop_pallas(
        p_t, q_t, pair_mask, block_b=block_b, interpret=interpret
    )
    if tail:
        from lighthouse_tpu.ops.pallas_tail import fold_final_exp_pallas

        res_t = fold_final_exp_pallas(f_t, interpret=interpret)
        res = tf.to_batchlead(res_t)[0]  # (12, NB)
        return tower.fp12_is_one(res)
    f = tf.to_batchlead(f_t)
    prod = tower.fp12_product_axis(f, axis=0)
    return pairing.final_exp_is_one(prod)


def verify_signature_sets_individual(
    msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    set_mask,
    lane_map=None,
):
    """Per-set verdicts in ONE device call (the batch-failure fallback —
    SURVEY §7 hard part 5, attestation batch.rs:115-131 semantics without
    the per-set round trips): set i passes iff

        e(agg_pk_i, H_i) * e(-G1, sig_i) == 1.

    No RLC is needed — each set is its own independent pairing check; the
    Miller loop runs over 2S pairs (so one poisoned batch costs ~2x a
    full batch verify — accepted: batch failures are rare and the
    alternative, residue bisection, would cost device round trips the
    <=2-call bound forbids) and the final exponentiation is
    batched per set. Returns a (S,) bool array (padding lanes True)."""
    S = set_mask.shape[0]
    agg_pk = aggregate_pubkeys(pubkeys_g1_aff, key_mask, lane_map)
    pk_x, pk_y, pk_inf = curve.PG1.to_affine(agg_pk)

    neg_g1 = (
        jnp.broadcast_to(jnp.asarray(NEG_G1_AFFINE[0]), pk_x.shape),
        jnp.broadcast_to(jnp.asarray(NEG_G1_AFFINE[1]), pk_y.shape),
    )
    g1_side = (
        jnp.concatenate([pk_x, neg_g1[0]], axis=0),
        jnp.concatenate([pk_y, neg_g1[1]], axis=0),
    )
    g2_side = (
        jnp.concatenate([msgs_g2_aff[0], sigs_g2_aff[0]], axis=0),
        jnp.concatenate([msgs_g2_aff[1], sigs_g2_aff[1]], axis=0),
    )
    # e(inf, .) == 1 exactly; a masked padding lane contributes 1 to both
    # of its pairs and trivially passes
    pair_mask = jnp.concatenate(
        [set_mask & ~pk_inf, set_mask], axis=0
    )
    f = pairing.miller_loop(g1_side, g2_side, valid_mask=pair_mask)
    from lighthouse_tpu.ops import tower

    f_set = tower.fp12_mul(f[:S], f[S:])
    ok = tower.fp12_is_one(pairing.final_exponentiation(f_set))
    return ok | ~set_mask


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def verify_signature_sets_t(
    msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    rand_bits,
    set_mask,
):
    """Same verdict as verify_signature_sets, computed entirely in the
    transposed batch-on-lanes layout (ops.tfield/tcurve/tpairing/tfexp)
    at the XLA level — no Pallas. The batch-leading layout's trailing
    33-limb axis wastes ~3/4 of each VPU register row; here the batch
    rides the 128-lane axis end to end (RLC ladders, Miller loop, pair
    fold, final exponentiation). Only the per-set K-key aggregation and
    the two to-affine inversions stay batch-leading (they are small and
    already lane-efficient over S)."""
    from lighthouse_tpu.ops import tcurve, tfexp, tfield as tf
    from lighthouse_tpu.ops import tower
    from lighthouse_tpu.ops import tpairing as tp

    S = set_mask.shape[0]
    bits_t = jnp.transpose(rand_bits).astype(jnp.int32)  # (64, S)

    # G1: per-set aggregate (tree fold over K), transposed RLC ladder
    # (the shared window kernel via the transposed dispatcher)
    agg_pk = aggregate_pubkeys(pubkeys_g1_aff, key_mask)
    agg_t = tuple(tf.from_batchlead(c) for c in agg_pk)
    pk_r_t = wl.ladder_t(tcurve.TPG1, agg_t, bits_t)
    pk_r = tuple(tf.to_batchlead(c) for c in pk_r_t)
    pk_aff = curve.PG1.to_affine(pk_r)

    # G2: transposed RLC ladder over the signatures + lane-tree fold.
    # The ladder runs on exactly S lanes; only sum_lanes needs a
    # power-of-two count, so identity-pad its INPUT, not the ladder's.
    sx, sy = (tf.from_batchlead(c) for c in sigs_g2_aff)
    sig_t = tcurve.TPG2.from_affine((sx, sy), set_mask)
    sig_r_t = wl.ladder_t(tcurve.TPG2, sig_t, bits_t)
    pad = _next_pow2(S) - S
    if pad:
        ident = tcurve.TPG2.identity(pad)
        sig_r_t = tuple(
            jnp.concatenate([c, i], axis=-1)
            for c, i in zip(sig_r_t, ident)
        )
    sig_folded = tcurve.TPG2.sum_lanes(sig_r_t)  # 1-lane bundles
    sig_acc = tuple(tf.to_batchlead(c)[0] for c in sig_folded)
    sig_aff = curve.PG2.to_affine(_expand0(sig_acc))

    g1_side, g2_side, pair_mask = _assemble_pairs(
        msgs_g2_aff, set_mask, pk_aff, sig_aff
    )

    # transposed Miller loop on exactly S+1 pair lanes — no padding:
    # tfexp.fold_lanes carries odd counts, and pow2-padding here would
    # nearly double the dominant Miller work at S=1024 (1025 -> 2048)
    p_t = tuple(tf.from_batchlead(c) for c in g1_side)
    q_t = tuple(tf.from_batchlead(c) for c in g2_side)
    f_t = tp.miller_loop_t(p_t, q_t, pair_mask)
    prod_t = tfexp.fold_lanes(f_t)
    frob = jnp.asarray(tfexp.frob_consts())[:, :, None]
    res_t = tfexp.final_exponentiation_t(prod_t, frob[:12], frob[12:])
    return tower.fp12_is_one(tf.to_batchlead(res_t)[0])


def g2_points_in_subgroup(points_g2_aff, mask):
    """(S,) bool — [r]·P == identity per lane, the batched device form of
    the host-side signature subgroup check (blst.rs:72-81 policy;
    ref_curve.in_subgroup is the ground truth).

    Runs a fully-general double-add ladder on the UNIFIED Jacobian
    plane: the inputs are by definition UNCHECKED points. Neither the
    RCB complete formulas (complete only on the odd-order r-torsion) nor
    the lean `add_nonexceptional` ladder (whose no-collision argument
    assumes the base has order r — an adversarial small-order twist
    point breaks it) may be used here; `JacobianGroup.add` handles every
    exceptional case. Masked lanes pass."""
    import jax

    G = curve.G2
    x, y = points_g2_aff
    F = G.F
    one = jnp.broadcast_to(jnp.asarray(F.ONE), x.shape)
    zero = jnp.zeros_like(x)
    m = mask
    # affine -> Jacobian (z = 1); masked lanes to infinity (z = 0)
    pt = (
        F.select(m, x, zero),
        F.select(m, y, one),
        F.select(m, one, zero),
    )
    batch = pt[0].shape[:-2]
    bits_seq = jnp.asarray(_R_BITS[0], dtype=jnp.int32)  # (255,) LSB-first

    def step(carry, bit):
        acc, addend = carry
        added = G.add(acc, addend)
        use = jnp.broadcast_to(bit == 1, batch)
        acc = G.select(use, added, acc)
        addend = G.double(addend)
        return (acc, addend), None

    init = (G.infinity_like(pt), pt)
    (acc, _), _ = jax.lax.scan(step, init, bits_seq)
    return G.is_infinity(acc) | ~mask


def _pad_lanes_projective(pt_t, block_b: int, group):
    """Pad the lane axis of a transposed projective point to a block
    multiple with identity lanes."""
    B = pt_t[0].shape[-1]
    pad = (-B) % block_b
    if not pad:
        return pt_t
    ix, iy, iz = group.identity(pad)
    return tuple(
        jnp.concatenate([c, i], axis=-1)
        for c, i in zip(pt_t, (ix, iy, iz))
    )


def miller_inputs_pallas(
    msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    rand_bits,
    set_mask,
    lane_map=None,
    block_b: int = 128,
    interpret: bool = False,
):
    """miller_inputs with the per-set G1 and per-signature G2 RLC ladders
    running as fused Pallas VMEM kernels (ops.pallas_ladder); MSM folds
    and the to-affine inversions stay on the XLA path."""
    from lighthouse_tpu.ops import tcurve, tfield as tf
    from lighthouse_tpu.ops.pallas_ladder import ladder_pallas

    bits_t = jnp.transpose(rand_bits).astype(jnp.int32)  # (64, S)

    # ---- G1: aggregate per set (XLA fold), then the pallas ladder
    # (S,) projective
    agg_pk = aggregate_pubkeys(pubkeys_g1_aff, key_mask, lane_map)
    agg_t = tuple(tf.from_batchlead(c) for c in agg_pk)
    agg_t = _pad_lanes_projective(agg_t, block_b, tcurve.TPG1)
    padded = agg_t[0].shape[-1] - agg_pk[0].shape[0]
    bits_pad = jnp.pad(bits_t, ((0, 0), (0, padded)))
    pk_r_t = ladder_pallas(
        agg_t, bits_pad, group_name="G1", block_b=block_b,
        interpret=interpret,
    )
    n_sets = agg_pk[0].shape[0]
    pk_r = tuple(tf.to_batchlead(c)[:n_sets] for c in pk_r_t)
    pk_aff = curve.PG1.to_affine(pk_r)

    # ---- G2: pallas ladder over the signatures, then the XLA fold
    # (sliced back to the real lane count first — folding identity
    # padding would widen every tree level for nothing)
    sx, sy = (tf.from_batchlead(c) for c in sigs_g2_aff)
    sig_t = tcurve.TPG2.from_affine((sx, sy), set_mask)
    sig_t = _pad_lanes_projective(sig_t, block_b, tcurve.TPG2)
    sig_r_t = ladder_pallas(
        sig_t, bits_pad, group_name="G2", block_b=block_b,
        interpret=interpret,
    )
    sig_r = tuple(tf.to_batchlead(c)[:n_sets] for c in sig_r_t)
    sig_acc = curve.PG2.sum_axis(sig_r, axis=0)
    sig_aff = curve.PG2.to_affine(_expand0(sig_acc))
    return _assemble_pairs(msgs_g2_aff, set_mask, pk_aff, sig_aff)


def verify_signature_sets_pallas(
    msgs_g2_aff,
    sigs_g2_aff,
    pubkeys_g1_aff,
    key_mask,
    rand_bits,
    set_mask,
    lane_map=None,
    block_b: int = 128,
    interpret: bool = False,
    tail: bool = False,
):
    """Same verdict as verify_signature_sets, with the Miller loop AND
    the RLC scalar ladders running as fused Pallas VMEM kernels. The
    pair axis is padded to a lane-tile multiple with masked identity
    pairs; MSM folds and the to-affine inversions stay on the XLA path.
    With `tail=True` the product fold + final exponentiation also run
    in-kernel (ops.pallas_tail) — without it they stay on XLA."""
    g1_side, g2_side, pair_mask = miller_inputs_pallas(
        msgs_g2_aff, sigs_g2_aff, pubkeys_g1_aff, key_mask, rand_bits,
        set_mask, lane_map, block_b=block_b, interpret=interpret,
    )
    return _pairs_to_verdict_pallas(
        g1_side, g2_side, pair_mask, block_b=block_b,
        interpret=interpret, tail=tail,
    )
