"""Device-resident decompressed validator pubkey table.

Role of beacon_node/beacon_chain/src/validator_pubkey_cache.rs:9-24 on
the TPU plane (SURVEY §7 hard part 4): decompression and limb packing
happen ONCE per validator at registration; a signature batch then ships
(S, K) int32 row indices instead of 48-byte points, and the device
gathers affine Montgomery limbs from HBM-resident tables. At 30k sigs a
slot this removes all per-pubkey Python bigint work from the hot path;
the few keys a batch holds that the table lacks (the canary sentinel,
withdrawal keys) ride along as overflow rows.
"""

import threading

import numpy as np

import jax.numpy as jnp

from lighthouse_tpu.crypto.constants import P
from lighthouse_tpu.crypto.ref_curve import G1 as G1_GROUP
from lighthouse_tpu.ops import fieldb as fb


def _mont_limbs(values) -> np.ndarray:
    """ints -> (N, NB) Montgomery-domain canonical limbs, host-side
    (cheap: one bigint mulmod per value; avoids a device round-trip per
    append)."""
    return fb.pack_ints([(v << 384) % P for v in values])


def limb_rows(pubkeys, n_rows: int):
    """Decompressed `bls.PublicKey`s -> host (x, y) int32 arrays of
    shape (n_rows, 1, NB): affine Montgomery limbs, one key a row, zero
    rows past the last key."""
    xs = np.zeros((n_rows, 1, fb.NB), dtype=np.int32)
    ys = np.zeros((n_rows, 1, fb.NB), dtype=np.int32)
    if pubkeys:
        affs = [G1_GROUP.to_affine(p.point) for p in pubkeys]
        xs[: len(affs), 0] = _mont_limbs([a[0] for a in affs])
        ys[: len(affs), 0] = _mont_limbs([a[1] for a in affs])
    return xs, ys


def tagging_cache(pk):
    """The PubkeyCache that tagged `pk` (its `cache`, when it also
    carries a `validator_index`), or None for an untagged key."""
    if getattr(pk, "validator_index", None) is None:
        return None
    return getattr(pk, "cache", None)


def _capacity(rows: int) -> int:
    cap = 8
    while cap < rows:
        cap *= 2
    return cap


class DevicePubkeyTable:
    """(capacity, 1, NB) x/y Montgomery limb arrays on device, indexed by
    validator index + 1. Row 0 is a zero row so masked-out gather lanes
    read a harmless (0, 0); capacity grows in powers of two so the jitted
    gather-verify graph recompiles O(log N) times over a chain's life.
    An append uploads the table again on the next rows() (21.7 ms for
    2^19 rows on a v5e host).

    A batch's row indices run past the table: index capacity + j names
    row j of the batch's own overflow rows (`limb_rows` of the keys the
    table does not hold), which the gather programs select on
    `index >= capacity`."""

    def __init__(self):
        # host mirror at the device capacity; rows past count + 1 are 0
        self._x_np = np.zeros((8, 1, fb.NB), dtype=np.int32)
        self._y_np = np.zeros((8, 1, fb.NB), dtype=np.int32)
        self._rows = None  # device (x, y), swapped whole
        self.count = 0  # validator rows (excludes the zero row)
        # import_new appends on the chain's thread while bus batches
        # read rows() on theirs
        self._lock = threading.Lock()

    def append(self, pubkeys) -> None:
        """Append decompressed `bls.PublicKey`s (one-time per validator)."""
        if not pubkeys:
            return
        xs, ys = limb_rows(pubkeys, len(pubkeys))
        with self._lock:
            lo = self.count + 1
            hi = lo + len(pubkeys)
            if hi > self._x_np.shape[0]:
                pad = _capacity(hi) - self._x_np.shape[0]
                widths = ((0, pad), (0, 0), (0, 0))
                self._x_np = np.pad(self._x_np, widths)
                self._y_np = np.pad(self._y_np, widths)
            self._x_np[lo:hi] = xs
            self._y_np[lo:hi] = ys
            self._rows = None
            self.count += len(pubkeys)

    def rows(self):
        """(x, y) device arrays, shape (capacity, 1, NB); validator i
        lives at row i+1."""
        with self._lock:
            if self._rows is None:
                self._rows = (
                    jnp.asarray(self._x_np), jnp.asarray(self._y_np)
                )
            return self._rows

    def index_lanes(self, lanes, k_bucket: int, owner):
        """Host side of one batch's gather, in one walk over its keys.

        `lanes` holds each lane's pubkeys (None for a padding lane).
        Returns (rows, indices, overflow): `rows` this table's device
        arrays, `indices` an int32 (len(lanes), k_bucket) array, and
        `overflow` the keys the table does not hold. A key tagged by
        `owner`, the PubkeyCache this table mirrors, maps to its row;
        the j-th other key maps to capacity + j, with capacity read
        from `rows`, so a concurrent append cannot move that range;
        unused slots stay 0, the zero row."""
        rows = self.rows()
        cap = rows[0].shape[0]
        indices = np.zeros((len(lanes), k_bucket), dtype=np.int32)
        overflow = []
        for lane, pks in enumerate(lanes):
            if not pks:
                continue
            row = []
            for p in pks:
                if tagging_cache(p) is owner:
                    row.append(p.validator_index + 1)
                else:
                    row.append(cap + len(overflow))
                    overflow.append(p)
            indices[lane, : len(row)] = row
        return rows, indices, overflow
