"""TPU backend for `verify_signature_sets`: host marshalling -> device batch.

The host side of the north-star boundary: converts heterogeneous
SignatureSets into the static-shaped, masked device arrays that
`ops.batch_verify.verify_signature_sets` consumes, with bucketed padding so
jit recompiles only per (set-bucket, key-bucket) shape class — the
TPU-native replacement for the reference's dynamic per-set heap vectors
(crypto/bls/src/impls/blst.rs:90-108).

Hot-path design (SURVEY §7 hard part 4, validator_pubkey_cache.rs:9-24):
  * pubkeys tagged by the chain's PubkeyCache ship as int32 table indices;
    the device gathers affine Montgomery limbs from the HBM-resident
    DevicePubkeyTable — zero per-pubkey Python work per batch. The few
    untagged keys of such a batch (the canary sentinel, withdrawal keys)
    are packed on the host as a small block of overflow rows, indexed
    past the table's capacity; only a batch with no tagged key at all
    packs every slot. The chain builds the table when it starts on this
    backend; a marshal never builds it, and packs every slot while the
    cache has no complete table.
  * a set wider than KEY_LANE_WIDTH keys (an Electra aggregate spans a
    slot's committees: up to 31,250 keys at 1M validators) is cut into
    key lanes of that width: the flat grid is (lane bucket, width) with
    a lane -> set map, and the device folds the lane sums per set, so
    the key axis is not padded to the widest set and one program serves
    every vote split. A batch whose sets all fit one lane keeps the
    one-lane-a-set grid and program.
  * message hash_to_g2 results are memoized — a slot's 30k attestation
    sets share ~committee-count distinct messages, so the cache collapses
    the per-set cost to a dict hit.
  * signature/message Jacobian->affine conversion uses one simultaneous
    (Montgomery-trick) inversion per batch instead of one Fp2 inversion
    per point.

Signature subgroup checks run host-side before dispatch, mirroring
blst.rs:72-81.
"""

import secrets
import threading
import time

import numpy as np

import jax

from lighthouse_tpu.bls.device_pubkey_table import limb_rows, tagging_cache
from lighthouse_tpu.bls.hash_to_curve import hash_to_g2
from lighthouse_tpu.common import device_attribution as attribution
from lighthouse_tpu.common.compile_ledger import LEDGER
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.common.tracing import span, tag
from lighthouse_tpu.crypto.ref_curve import G1 as G1_GROUP
from lighthouse_tpu.crypto.ref_curve import G2 as G2_GROUP
from lighthouse_tpu.device_plane import GUARD, host_device_scope
from lighthouse_tpu.ops import batch_verify, curve, fieldb as fb, fp2

# jit-compilation observability: "wrapper" events track the python-side
# impl-keyed cache (a miss means a NEW jax.jit object); "xla" events
# track the jitted object's own trace cache (a retrace means a new
# (shape-bucket, dtype) class compiled — the cost bucketed padding
# exists to bound)
_JIT_EVENTS = REGISTRY.counter_vec(
    "lighthouse_tpu_jit_cache_events_total",
    "jit cache hits vs (re)traces per jitted verify entry point",
    ("fn", "layer", "event"),
)
_MSG_CACHE_EVENTS = REGISTRY.counter_vec(
    "lighthouse_tpu_msg_cache_events_total",
    "hash_to_g2 memo hits vs misses during batch marshalling",
    ("event",),
)
_PUBKEY_SLOTS = REGISTRY.counter_vec(
    "lighthouse_tpu_pubkey_slots_total",
    "pubkey slots marshalled: live ones by where their limbs came from"
    " (the HBM table, the batch's overflow rows, or packed key by key),"
    " and the grid's padding slots",
    ("path",),
)


def _note_wrapper_event(fn_name: str, hit: bool):
    _JIT_EVENTS.labels(fn_name, "wrapper", "hit" if hit else "trace").inc()


def _note_xla_events(fn_name: str, jitted, shape="", duration_s=None):
    """Classify this dispatch as retrace (the jitted object's trace
    cache grew — a new shape class compiled) or hit, via the process
    compile LEDGER which owns the cache-size bookkeeping (read-modify-
    write under its lock — concurrent worker dispatches must not count
    one compile as two retraces) and records the structured entry with
    impl key, shape bucket, and dispatch wall time."""
    grew = LEDGER.note_dispatch(
        fn_name, jitted, _impl_key(), shape, duration_s=duration_s
    )
    if grew > 0:
        _JIT_EVENTS.labels(fn_name, "xla", "retrace").inc(grew)
    else:
        _JIT_EVENTS.labels(fn_name, "xla", "hit").inc()


_COMPILED: set = set()


def _compile_ahead(fn_name: str, jitted, args, shape: str):
    """Compile `jitted` for these argument shapes before dispatching
    it, so a cold bucket's trace+compile (minutes for the TPU kernels)
    is never timed as execution: called ahead of the guarded crossing,
    or — when an outer crossing (the verification bus) already holds
    the watchdog — inside a compile window the watchdog does not count.
    The dispatch that follows reuses the executable; the ledger records
    the compile as the bucket's cold entry, which also zeroes the
    guard's cold allowance for it."""
    key = (
        fn_name,
        id(jitted),
        tuple(
            (np.shape(a), str(getattr(a, "dtype", type(a))))
            for a in jax.tree.leaves(args)
        ),
    )
    if key in _COMPILED:
        return 0.0
    t0 = time.perf_counter()
    with GUARD.compile_window(), span(
        "verify/compile", fn=fn_name, shape=shape
    ):
        jitted.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    LEDGER.note_compile(fn_name, jitted, _impl_key(), shape, compile_s)
    _COMPILED.add(key)
    return compile_s


# jit caches keyed by the full impl choice — the LIGHTHOUSE_TPU_IMPL
# selection AND the MXU knobs (MXU_REDC/MXU_CONV) that fieldb reads at
# trace time — so flipping ANY of them mid-process retraces instead of
# silently reusing a stale trace
_jitted: dict = {}
_jitted_indexed: dict = {}
_JIT_LOCK = threading.Lock()

# device-dispatch counters (read by tests asserting the <=2-call fallback)
CALL_COUNTS = {"batch": 0, "individual": 0}


def _use_pallas() -> bool:
    """The fused VMEM kernels (5,425-9,824 sigs/s measured vs the XLA
    graph's 1,470 — PERF_NOTES.md) lower only on real TPU hardware; the
    CPU mesh keeps the XLA graph. LIGHTHOUSE_TPU_IMPL=xla|pallas
    overrides the choice; any other value raises (fail-loud, matching
    bench_impl's exit-4 rule — a typo must not silently measure the
    auto-selected path)."""
    import os

    forced = os.environ.get("LIGHTHOUSE_TPU_IMPL")
    if forced == "pallas":
        return True
    if forced == "xla":
        return False
    # "" follows the shell convention for unset (tfield.use_mxu_redc
    # treats its knob the same way)
    if forced:
        raise ValueError(
            f"LIGHTHOUSE_TPU_IMPL={forced!r}: expected 'xla', 'pallas',"
            " or unset"
        )
    return jax.default_backend() == "tpu"


def _impl_key():
    """(use_pallas, MXU_REDC form, MXU_CONV on, ladder kind, FP12
    squaring form, fused tail) — everything read at trace time that
    changes the compiled program, NORMALIZED the way the kernels
    consume it (tfield.use_mxu_redc maps "1"/"i8" to one form and
    resolves the on-TPU default; window_ladder.ladder_impl resolves
    the default window kernel; fieldb only tests MXU_CONV == "1") so
    equivalent spellings share one trace instead of recompiling."""
    from lighthouse_tpu.ops import tfield, tower
    from lighthouse_tpu.ops.pallas_tail import use_fused_tail
    from lighthouse_tpu.ops.window_ladder import ladder_impl

    import os

    return (
        _use_pallas(),
        tfield.use_mxu_redc(),
        os.environ.get("LIGHTHOUSE_TPU_MXU_CONV") == "1",
        ladder_impl(),
        tower.use_fp12_sqr(),
        use_fused_tail(),
    )


def _verify_impl(use_pallas: bool):
    if use_pallas:
        import functools

        from lighthouse_tpu.ops.pallas_tail import use_fused_tail

        return functools.partial(
            batch_verify.verify_signature_sets_pallas,
            tail=use_fused_tail(),
        )
    return batch_verify.verify_signature_sets


def _get_fn():
    """Jitted verify fn for the CURRENT impl choice. Keyed by the choice
    (not cached once) so flipping LIGHTHOUSE_TPU_IMPL or an MXU knob
    mid-process takes effect on the next dispatch instead of being baked
    into the first trace."""
    key = _impl_key()
    # under the lock: threads compiling ahead in parallel must all get
    # the one jit object whose executables they warm
    with _JIT_LOCK:
        fn = _jitted.get(key)
        _note_wrapper_event("verify", fn is not None)
        if fn is None:
            fn = _jitted[key] = jax.jit(_verify_impl(key[0]))
    return fn


def _gather_pubkeys(table_x, table_y, over_x, over_y, indices):
    """Pubkey limb rows for an index array: index < capacity reads the
    table, index capacity + j the batch's overflow row j. Both gathers
    run on clipped indices and the select picks one, so the table is
    never copied."""
    import jax.numpy as jnp

    cap = table_x.shape[0]
    t_idx = jnp.clip(indices, 0, cap - 1)
    o_idx = jnp.clip(indices - cap, 0, over_x.shape[0] - 1)
    in_table = (indices < cap)[..., None, None]
    return tuple(
        jnp.where(
            in_table,
            jnp.take(table, t_idx, axis=0),
            jnp.take(over, o_idx, axis=0),
        )
        for table, over in ((table_x, over_x), (table_y, over_y))
    )


def _indexed_verify(
    use_pallas, msgs, sigs, table_x, table_y, over_x, over_y, indices,
    key_mask, rand_bits, set_mask, lane_map=None,
):
    """Gather pubkey limb rows by table index on device, then verify."""
    pks = _gather_pubkeys(table_x, table_y, over_x, over_y, indices)
    return _verify_impl(use_pallas)(
        msgs, sigs, pks, key_mask, rand_bits, set_mask, lane_map
    )


def _grouped_impl(use_pallas: bool):
    if use_pallas:
        import functools

        from lighthouse_tpu.ops.pallas_tail import use_fused_tail

        return functools.partial(
            batch_verify.verify_signature_sets_grouped_pallas,
            tail=use_fused_tail(),
        )
    return batch_verify.verify_signature_sets_grouped


def _grouped_indexed_verify(
    use_pallas, msgs, sigs, table_x, table_y, over_x, over_y, indices,
    key_mask, rand_bits, set_mask, group_mask,
):
    pks = _gather_pubkeys(table_x, table_y, over_x, over_y, indices)
    return _grouped_impl(use_pallas)(
        msgs, sigs, pks, key_mask, rand_bits, set_mask, group_mask,
    )


_jitted_grouped: dict = {}


def _get_grouped_fns():
    import functools

    key = _impl_key()
    with _JIT_LOCK:
        pair = _jitted_grouped.get(key)
        _note_wrapper_event("verify_grouped", pair is not None)
        if pair is None:
            pair = _jitted_grouped[key] = (
                jax.jit(_grouped_impl(key[0])),
                jax.jit(
                    functools.partial(_grouped_indexed_verify, key[0])
                ),
            )
    return pair


def _get_indexed_fn():
    import functools

    key = _impl_key()
    with _JIT_LOCK:
        fn = _jitted_indexed.get(key)
        _note_wrapper_event("verify_indexed", fn is not None)
        if fn is None:
            fn = _jitted_indexed[key] = jax.jit(
                functools.partial(_indexed_verify, key[0])
            )
    return fn


# keys a lane holds: a flat batch with a set wider than this splits each
# set into lanes of at most this many keys, folded back per set on device
KEY_LANE_WIDTH = 512


def _bucket(n: int, minimum: int) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


# --------------------------------------------------------- message hashing

_MSG_CACHE: dict = {}
_MSG_CACHE_MAX = 16_384


def _msg_affine(message: bytes):
    """Memoized hash_to_g2 -> affine ints. Attestation batches repeat the
    same signing root across a whole committee."""
    message = bytes(message)
    hit = _MSG_CACHE.get(message)
    if hit is None:
        _MSG_CACHE_EVENTS.labels("miss").inc()
        with span("verify/marshal/hash_to_g2"):
            hit = G2_GROUP.to_affine(hash_to_g2(message))
        if len(_MSG_CACHE) >= _MSG_CACHE_MAX:
            _MSG_CACHE.clear()
        _MSG_CACHE[message] = hit
    else:
        _MSG_CACHE_EVENTS.labels("hit").inc()
    return hit


# ----------------------------------------------- batched affine conversion

_F2 = G2_GROUP.F


def batch_to_affine_g2(points):
    """Jacobian G2 points -> affine, ONE Fp2 inversion total (Montgomery
    simultaneous-inversion trick). Infinity points -> None."""
    zs, keep = [], []
    for i, pt in enumerate(points):
        if not G2_GROUP.is_infinity(pt):
            zs.append(pt[2])
            keep.append(i)
    out = [None] * len(points)
    if not zs:
        return out
    # prefix products
    prefix = [zs[0]]
    for z in zs[1:]:
        prefix.append(_F2.mul(prefix[-1], z))
    acc = _F2.inv(prefix[-1])
    invs = [None] * len(zs)
    for j in range(len(zs) - 1, 0, -1):
        invs[j] = _F2.mul(acc, prefix[j - 1])
        acc = _F2.mul(acc, zs[j])
    invs[0] = acc
    for j, i in enumerate(keep):
        x, y, _ = points[i]
        zi2 = _F2.sqr(invs[j])
        out[i] = (_F2.mul(x, zi2), _F2.mul(y, _F2.mul(zi2, invs[j])))
    return out


def _pack_g1_affine(affs):
    xs = np.stack([fb.pack_ints([a[0] if a else 0]) for a in affs])
    ys = np.stack([fb.pack_ints([a[1] if a else 0]) for a in affs])
    return fb.to_mont(xs), fb.to_mont(ys)


def _pack_g2_affine(affs):
    zero = ((0, 0), (0, 0))
    xs = fp2.pack([(a or zero)[0] for a in affs])
    ys = fp2.pack([(a or zero)[1] for a in affs])
    return (fb.to_mont(xs), fb.to_mont(ys))


def _rlc_scalars(n: int, seed):
    """Full 64-bit RLC coefficients (blst.rs:15 RAND_BITS), seeded for
    deterministic tests or from the OS entropy pool in production."""
    top = 1 << batch_verify.RAND_BITS
    if seed is not None:
        rng = np.random.default_rng(seed)
        return [
            int(rng.integers(1, top, dtype=np.uint64)) for _ in range(n)
        ]
    return [1 + secrets.randbelow(top - 1) for _ in range(n)]


def _table_for(lanes):
    """(cache, DevicePubkeyTable) of the PubkeyCache that tags the
    batch's first tagged pubkey (`lanes`: each lane's pubkeys, None for
    padding), when that cache's table is built and holds every key it
    does; else None (no tagged key, or no table yet: a marshal never
    builds one). Keys that cache does not tag become the batch's
    overflow rows."""
    for pks in lanes:
        for p in pks or ():
            cache = tagging_cache(p)
            if cache is not None:
                table = cache.ready_table()
                return None if table is None else (cache, table)
    return None


class _Marshalled:
    """Static-shaped device inputs for one batch of SignatureSets."""

    __slots__ = (
        "msgs",
        "sigs",
        "key_mask",
        "set_mask",
        # table path: the table's device (x, y) rows, the index array
        # and the (E, 1, NB) overflow rows; packed path: pubkeys
        "table",
        "indices",
        "overflow",
        "pubkeys",
        "s_bucket",
        "k_bucket",
        # key lanes (flat batches with a set wider than KEY_LANE_WIDTH):
        # the (L,) int32 lane -> set map and each set's (S,) int32 last
        # lane, else None
        "lane_map",
        # message-grouped layout (None/False when flat)
        "grouped",
        "group_mask",
        "n_groups",
    )


def _grouping_enabled() -> bool:
    import os

    return os.environ.get("LIGHTHOUSE_TPU_GROUPED") != "0"


def _group_plan(sets):
    """Order-preserving message→set-index grouping, or None when the
    merge does not pay: grouping must at least HALVE the pair count
    (G*2 <= S), and the padded (G, Sg) grid must not blow past twice
    the flat bucket (pathologically skewed group sizes)."""
    by_msg: dict[bytes, list] = {}
    for i, s in enumerate(sets):
        by_msg.setdefault(bytes(s.message), []).append(i)
    n_sets = len(sets)
    G = len(by_msg)
    if G * 2 > n_sets:
        return None
    sg_b = _bucket(max(len(ix) for ix in by_msg.values()), 1)
    g_b = _bucket(G, 1)
    if g_b * sg_b > 2 * _bucket(n_sets, 4):
        return None
    return list(by_msg.items())


def _marshal(sets, allow_grouped: bool = True) -> _Marshalled:
    """Marshal a batch, preferring the message-grouped grid layout
    (G distinct messages -> G+1 Miller loops instead of S+1; the
    committee-shaped attestation load has S/G >= 100). The per-set
    fallback path marshals with allow_grouped=False — per-set verdicts
    need per-set pairs. A batch with a set wider than KEY_LANE_WIDTH
    takes the flat layout's key lanes."""
    wide = max(len(s.pubkeys) for s in sets) > KEY_LANE_WIDTH
    if allow_grouped and not wide and _grouping_enabled():
        plan = _group_plan(sets)
        if plan is not None:
            return _marshal_grouped(sets, plan)
    return _marshal_flat(sets)


def _marshal_grouped(sets, groups) -> _Marshalled:
    """Grid marshal: groups -> (g_bucket, sg_bucket) lanes, messages one
    per group. Padding lanes carry None sigs + all-False key masks."""
    m = _Marshalled()
    G = len(groups)
    g_b = _bucket(G, 1)
    sg_b = _bucket(max(len(ix) for _, ix in groups), 1)
    m.grouped = True
    m.n_groups = G
    m.s_bucket = g_b * sg_b
    m.k_bucket = _bucket(max(len(s.pubkeys) for s in sets), 1)
    m.lane_map = None

    with span("verify/marshal/points"):
        group_msgs = [_msg_affine(sets[ix[0]].message) for _, ix in groups]
        group_msgs += [None] * (g_b - G)
        m.group_mask = np.array(
            [True] * G + [False] * (g_b - G), dtype=bool
        )

        # lane order: group-major, each group padded to sg_b
        order: list = []
        for _, ix in groups:
            order += list(ix) + [None] * (sg_b - len(ix))
        order += [None] * ((g_b - G) * sg_b)

        sig_aff = batch_to_affine_g2([s.signature.point for s in sets])
        sigs = [None if i is None else sig_aff[i] for i in order]

    with span("verify/marshal/pack"):
        m.set_mask = np.array(
            [i is not None for i in order], dtype=bool
        ).reshape(g_b, sg_b)
        lanes = [None if i is None else sets[i].pubkeys for i in order]
        m.key_mask = _key_mask(lanes, m.k_bucket).reshape(
            g_b, sg_b, m.k_bucket
        )
        _marshal_pubkeys(m, lanes, (g_b, sg_b), wide_sets=0)
        m.msgs = _pack_g2_affine(group_msgs)
        m.sigs = tuple(
            np.asarray(c).reshape(g_b, sg_b, 2, fb.NB)
            for c in _pack_g2_affine(sigs)
        )
    return m


def _key_lanes(sets, s_bucket):
    """(lanes, lane map or None, key bucket) of a flat batch. Sets of
    at most KEY_LANE_WIDTH keys are one lane each, padded to the set
    bucket, with a key bucket of the widest set: the layout and program
    of a batch without key lanes. Otherwise every set is cut into runs
    of KEY_LANE_WIDTH keys, one lane each, in set order, padded to a
    power-of-two lane bucket; the lane map is the (L,) lane -> set map
    (padding lanes -1) and each of the s_bucket sets' last lane
    (padding sets -1)."""
    widest = max(len(s.pubkeys) for s in sets)
    if widest <= KEY_LANE_WIDTH:
        lanes = [s.pubkeys for s in sets] + [None] * (s_bucket - len(sets))
        return lanes, None, _bucket(widest, 1)
    w = KEY_LANE_WIDTH
    lanes, owner = [], []
    last = np.full(s_bucket, -1, dtype=np.int32)
    for i, s in enumerate(sets):
        pks = list(s.pubkeys)
        for lo in range(0, len(pks), w):
            lanes.append(pks[lo:lo + w])
            owner.append(i)
        last[i] = len(lanes) - 1
    pad = _bucket(len(lanes), 4) - len(lanes)
    lane_set = np.array(owner + [-1] * pad, dtype=np.int32)
    return lanes + [None] * pad, (lane_set, last), w


def _key_mask(lanes, k_bucket):
    """(len(lanes), k_bucket) bool: each lane's first len(keys) slots."""
    n = np.array([len(pks) if pks else 0 for pks in lanes])
    return np.arange(k_bucket)[None, :] < n[:, None]


def _marshal_flat(sets) -> _Marshalled:
    n_sets = len(sets)
    m = _Marshalled()
    m.grouped = False
    m.n_groups = None
    m.group_mask = None
    m.s_bucket = _bucket(n_sets, 4)
    lanes, m.lane_map, m.k_bucket = _key_lanes(sets, m.s_bucket)

    with span("verify/marshal/points"):
        msgs = [_msg_affine(s.message) for s in sets]
        sigs = batch_to_affine_g2([s.signature.point for s in sets])
        msgs += [None] * (m.s_bucket - n_sets)
        sigs += [None] * (m.s_bucket - n_sets)

    with span("verify/marshal/pack"):
        m.set_mask = np.array(
            [True] * n_sets + [False] * (m.s_bucket - n_sets), dtype=bool
        )
        m.key_mask = _key_mask(lanes, m.k_bucket)
        wide = sum(len(s.pubkeys) > KEY_LANE_WIDTH for s in sets)
        _marshal_pubkeys(m, lanes, (len(lanes),), wide_sets=wide)
        m.msgs = _pack_g2_affine(msgs)
        m.sigs = _pack_g2_affine(sigs)
    return m


def _marshal_pubkeys(m, lanes, lanes_shape, wide_sets):
    """Fill a marshal's pubkey slots for its lanes (`lanes`: each lane's
    pubkeys, None for padding; `lanes_shape` the layout's lane grid;
    `wide_sets` the sets cut into several lanes). A batch with a tagged
    key takes the table path: indices into the HBM table, the untagged
    keys as overflow rows in a power-of-two bucket of at least 8, so a
    batch with up to 8 of them (the canary sentinel) reaches one
    program. A batch with no tagged key packs every slot on the host."""
    live = sum(len(pks) for pks in lanes if pks)
    _PUBKEY_SLOTS.labels("padding").inc(len(lanes) * m.k_bucket - live)
    n_lanes = sum(1 for pks in lanes if pks)
    with span(
        "verify/marshal/pubkeys", slots=live, lanes=n_lanes,
        wide_sets=wide_sets,
    ) as sp:
        found = _table_for(lanes)
        if found is None:
            pk_flat = []
            for pks in lanes:
                row = [G1_GROUP.to_affine(p.point) for p in pks or ()]
                pk_flat += row + [None] * (m.k_bucket - len(row))
            shape = lanes_shape + (m.k_bucket, 1, fb.NB)
            m.table = m.indices = m.overflow = None
            m.pubkeys = tuple(
                np.asarray(c).reshape(shape)
                for c in _pack_g1_affine(pk_flat)
            )
            _PUBKEY_SLOTS.labels("packed").inc(live)
            tag(sp, path="packed", overflow=0)
            return
        cache, table = found
        m.table, indices, over = table.index_lanes(lanes, m.k_bucket, cache)
        m.indices = indices.reshape(lanes_shape + (m.k_bucket,))
        m.overflow = limb_rows(over, _bucket(len(over), 8))
        m.pubkeys = None
        _PUBKEY_SLOTS.labels("table").inc(live - len(over))
        _PUBKEY_SLOTS.labels("overflow").inc(len(over))
        tag(sp, path="table", overflow=len(over))


def _marshal_attrs(m) -> dict:
    """The `verify/marshal` span's attributes: the layout and program
    bucket a marshalled batch takes."""
    return {
        "layout": "grouped" if m.grouped else "flat",
        "n_groups": m.n_groups,
        "indexed": m.table is not None,
        "shape": _shape_key(m),
    }


def compile_ahead(sets) -> float:
    """Compile the device program `verify_signature_sets_tpu` would
    dispatch for `sets` (same marshal, same program key), without
    dispatching. Safe to call from several threads at once — the
    compiler releases the GIL — so a caller that knows its buckets can
    warm them in parallel. Returns compile seconds (0.0 when the
    program was already compiled)."""
    m = _marshal(list(sets))
    rand_bits = curve.scalars_to_bits(
        _rlc_scalars(m.s_bucket, 0), batch_verify.RAND_BITS
    )
    return _compile_ahead(*_program(m, rand_bits), _shape_key(m))


def _shape_key(m) -> str:
    """Shape-bucket string for the compile ledger: the (set, key)
    bucket class this marshal compiled/hit (with key lanes, the set
    bucket, the lane bucket and the lane width), and on the table path
    the overflow-row bucket."""
    if m.grouped:
        g_b, sg_b = m.set_mask.shape
        key = f"g{g_b}x{sg_b}k{m.k_bucket}"
    elif m.lane_map is not None:
        key = f"s{m.s_bucket}l{m.lane_map[0].shape[0]}k{m.k_bucket}"
    else:
        key = f"s{m.s_bucket}k{m.k_bucket}"
    if m.table is not None:
        key += f"e{m.overflow[0].shape[0]}"
    return key


def verify_signature_sets_tpu(
    sets, seed: int | None = None, consumer: str | None = None
) -> bool:
    # host-side policy checks (exact reference semantics)
    with span("verify/subgroup_check", n_sets=len(sets)):
        ok = all(
            not s.signature.is_infinity() and s.signature.in_subgroup()
            for s in sets
        )
    if not ok:
        return False

    with span("verify/marshal", n_sets=len(sets)) as sp:
        m = _marshal(sets)
        tag(sp, **_marshal_attrs(m))
    with span("verify/rlc_sample"):
        rand_bits = curve.scalars_to_bits(
            _rlc_scalars(m.s_bucket, seed), batch_verify.RAND_BITS
        )
    t_marshal = time.perf_counter()
    program = _program(m, rand_bits)
    compile_s = _compile_ahead(*program, _shape_key(m))

    def device_attempt(plan):
        with span(
            "verify/device",
            s_bucket=m.s_bucket,
            grouped=bool(m.grouped),
            indexed=m.table is not None,
        ):
            return bool(
                plan.verdict(bool(np.asarray(_dispatch(m, program))))
            )

    def xla_host_tier():
        # same program, pinned to the host CPU device
        with host_device_scope(), span(
            "verify/device", s_bucket=m.s_bucket, failover="xla-host"
        ):
            return bool(np.asarray(_dispatch(m, program)))

    def ref_tier():
        from lighthouse_tpu.bls.api import _verify_one_ref

        return all(_verify_one_ref(s) for s in sets)

    result = GUARD.dispatch(
        "bls",
        _shape_key(m),
        device_attempt,
        fallbacks=[("xla-host", xla_host_tier), ("ref", ref_tier)],
    )
    t_end = time.perf_counter()
    attribution.note_batch(
        consumer,
        "bls",
        lanes=m.s_bucket,
        live=len(sets),
        duration_s=t_end - t_marshal - compile_s,
    )
    return result


# stream-dispatch telemetry for the last verify_signature_set_batches_tpu
LAST_STREAM_STATS: dict = {}


def _program(m, rand_bits):
    """(ledger name, jitted verify fn, args) for a marshalled batch —
    the one place that picks the program family (grouped / flat,
    table-indexed / packed pubkeys)."""
    if m.grouped:
        # rand bits were sampled for s_bucket lanes; the grouped verify
        # takes them on the (G, Sg) grid
        rand_bits = np.asarray(rand_bits).reshape(
            m.set_mask.shape + (batch_verify.RAND_BITS,)
        )
        plain, indexed = _get_grouped_fns()
        if m.table is not None:
            return "verify_grouped_indexed", indexed, (
                m.msgs, m.sigs, *m.table, *m.overflow, m.indices,
                m.key_mask, rand_bits, m.set_mask, m.group_mask,
            )
        return "verify_grouped", plain, (
            m.msgs, m.sigs, m.pubkeys, m.key_mask, rand_bits,
            m.set_mask, m.group_mask,
        )
    lanes = () if m.lane_map is None else (m.lane_map,)
    if m.table is not None:
        return "verify_indexed", _get_indexed_fn(), (
            m.msgs, m.sigs, *m.table, *m.overflow, m.indices,
            m.key_mask, rand_bits, m.set_mask, *lanes,
        )
    return "verify", _get_fn(), (
        m.msgs, m.sigs, m.pubkeys, m.key_mask, rand_bits, m.set_mask,
        *lanes,
    )


def _dispatch(m, program):
    """Async device dispatch of a marshalled batch's (compiled-ahead)
    program — returns the unforced device value. The dispatch call is
    timed for the compile ledger (JAX dispatch is async, so a warm
    call's wall is dispatch overhead)."""
    CALL_COUNTS["batch"] += 1
    name, fn, args = program
    t0 = time.perf_counter()
    out = fn(*args)
    _note_xla_events(name, fn, _shape_key(m), time.perf_counter() - t0)
    return out


def verify_signature_set_batches_tpu(
    batches, seed=None, consumer: str | None = None
) -> list:
    """Streamed (double-buffered) verification of several batches: batch
    N+1 is marshalled on the host WHILE batch N runs on the device.

    JAX dispatch is asynchronous — the device value is not forced until
    `np.asarray`. The loop therefore: dispatch batch N, marshal batch
    N+1 (device busy the whole time), dispatch N+1, only then force N.
    At 30k sigs/slot the host marshal would otherwise add directly to
    the 200 ms budget (SURVEY §2.6 pipeline row; the reference overlaps
    the same way with rayon in block_verification.rs:21-44).

    Returns one bool per batch (empty batches are False, matching
    verify_signature_sets)."""
    t_wall0 = time.perf_counter()
    batches = [list(b) for b in batches]
    stream = {"host_ms": 0.0, "n_dispatched": 0}

    def stream_attempt(plan):
        """The whole double-buffered pipeline is ONE guarded crossing:
        per-force watchdogs would serialize exactly the overlap the
        stream exists for, so the guard wraps the stream and the
        failover re-verifies every batch on the host."""
        results = [None] * len(batches)
        pending = None  # (batch_index, unforced device verdict)
        stream["host_ms"] = 0.0
        stream["n_dispatched"] = 0
        for bi, sets in enumerate(batches):
            if not sets or any(
                s.signature.is_infinity()
                or not s.signature.in_subgroup()
                for s in sets
            ):
                results[bi] = False
                continue
            t0 = time.perf_counter()
            m = _marshal(sets)
            rand_bits = curve.scalars_to_bits(
                _rlc_scalars(
                    m.s_bucket, None if seed is None else seed + bi
                ),
                batch_verify.RAND_BITS,
            )
            program = _program(m, rand_bits)
            stream["host_ms"] += time.perf_counter() - t0
            # inside the stream's one crossing: a cold bucket compiles
            # under the guard's cold allowance
            _compile_ahead(*program, _shape_key(m))
            ok = _dispatch(m, program)
            # per-batch economics; duration omitted — the
            # double-buffered overlap makes per-batch device time
            # unmeasurable (the whole call's wall is observed once
            # below)
            attribution.note_batch(
                consumer, "bls", lanes=m.s_bucket, live=len(sets)
            )
            stream["n_dispatched"] += 1
            if pending is not None:
                results[pending[0]] = bool(
                    plan.verdict(bool(np.asarray(pending[1])))
                )
            pending = (bi, ok)
        if pending is not None:
            results[pending[0]] = bool(
                plan.verdict(bool(np.asarray(pending[1])))
            )
        return results

    def ref_tier():
        from lighthouse_tpu.bls.api import _verify_one_ref

        return [
            bool(b) and all(_verify_one_ref(s) for s in b)
            for b in batches
        ]

    results = GUARD.dispatch(
        "bls",
        "stream",
        stream_attempt,
        fallbacks=[("ref", ref_tier)],
    )
    host_ms = stream["host_ms"]
    n_dispatched = stream["n_dispatched"]
    wall_ms = (time.perf_counter() - t_wall0) * 1e3
    if n_dispatched:
        attribution.observe_seconds(consumer, "bls", wall_ms / 1e3)
    LAST_STREAM_STATS.clear()
    LAST_STREAM_STATS.update(
        {
            "batches": len(batches),
            "dispatched": n_dispatched,
            "host_marshal_ms": round(host_ms * 1e3, 2),
            "wall_ms": round(wall_ms, 2),
            # fraction of host marshal hidden behind device time:
            # 1 - (wall - device-only-lower-bound)/... reported raw; the
            # bench derives overlap = (host + device - wall)/host using
            # its own device-only calibration
        }
    )
    return results


def _indexed_individual(
    msgs, sigs, table_x, table_y, over_x, over_y, indices, key_mask,
    set_mask, lane_map=None,
):
    pks = _gather_pubkeys(table_x, table_y, over_x, over_y, indices)
    return batch_verify.verify_signature_sets_individual(
        msgs, sigs, pks, key_mask, set_mask, lane_map
    )


_jitted_individual = None
_jitted_individual_indexed = None


def _get_individual_fns():
    global _jitted_individual, _jitted_individual_indexed
    _note_wrapper_event("verify_individual", _jitted_individual is not None)
    if _jitted_individual is None:
        _jitted_individual = jax.jit(
            batch_verify.verify_signature_sets_individual
        )
        _jitted_individual_indexed = jax.jit(_indexed_individual)
    return _jitted_individual, _jitted_individual_indexed


def verify_signature_sets_tpu_individual(
    sets, consumer: str | None = None
) -> list:
    """Per-set verdicts in ONE device call — the batch-failure fallback
    without per-set round trips (attestation batch.rs:115-131 made
    device-shaped; SURVEY §7 hard part 5)."""
    verdicts = [True] * len(sets)
    live = []
    with span("verify/subgroup_check", n_sets=len(sets)):
        for i, s in enumerate(sets):
            if s.signature.is_infinity() or not s.signature.in_subgroup():
                verdicts[i] = False
            else:
                live.append(i)
    if not live:
        return verdicts

    subset = [sets[i] for i in live]
    with span("verify/marshal", n_sets=len(subset)) as sp:
        m = _marshal(subset, allow_grouped=False)  # per-set pairs needed
        tag(sp, **_marshal_attrs(m))
    t_marshal = time.perf_counter()

    plain_fn, indexed_fn = _get_individual_fns()
    CALL_COUNTS["individual"] += 1
    shape = _shape_key(m)
    lanes = () if m.lane_map is None else (m.lane_map,)
    if m.table is not None:
        name, fn, args = "verify_individual_indexed", indexed_fn, (
            m.msgs, m.sigs, *m.table, *m.overflow, m.indices,
            m.key_mask, m.set_mask, *lanes,
        )
    else:
        name, fn, args = "verify_individual", plain_fn, (
            m.msgs, m.sigs, m.pubkeys, m.key_mask, m.set_mask, *lanes
        )
    compile_s = _compile_ahead(name, fn, args, shape)

    def run_device():
        t0 = time.perf_counter()
        ok = fn(*args)
        _note_xla_events(name, fn, shape, time.perf_counter() - t0)
        return np.asarray(ok)

    def device_attempt(plan):
        with span(
            "verify/device", s_bucket=m.s_bucket, individual=True
        ):
            return list(
                plan.verdict([bool(v) for v in run_device()[: len(live)]])
            )

    def xla_host_tier():
        with host_device_scope(), span(
            "verify/device", s_bucket=m.s_bucket, individual=True,
            failover="xla-host",
        ):
            return [bool(v) for v in run_device()[: len(live)]]

    def ref_tier():
        from lighthouse_tpu.bls.api import _verify_one_ref

        return [_verify_one_ref(sets[i]) for i in live]

    ok_live = GUARD.dispatch(
        "bls",
        shape,
        device_attempt,
        fallbacks=[("xla-host", xla_host_tier), ("ref", ref_tier)],
    )
    t_end = time.perf_counter()
    for j, i in enumerate(live):
        verdicts[i] = bool(ok_live[j])
    attribution.note_batch(
        consumer,
        "bls",
        lanes=m.s_bucket,
        live=len(live),
        duration_s=t_end - t_marshal - compile_s,
    )
    return verdicts
