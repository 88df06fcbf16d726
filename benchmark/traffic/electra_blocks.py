"""Closed-loop import of Electra blocks: one block outstanding, each block
fresh, its attestations a whole slot's committees wide.

Since EIP-7549 an on-chain attestation carries `committee_bits` and one
aggregate covers every committee of a slot that signed the same
AttestationData; a block holds at most MAX_ATTESTATIONS_ELECTRA of them.
Block j sits at slot 2j + 2 and carries the aggregates of slots 2j and
2j + 1, as a block after a missed slot does. A slot's attesters (the
members of its MAX_COMMITTEES_PER_SLOT committees) split over four
votes: the head vote holds a share drawn per slot, uniform in the mix's
`head_share` range, and the rest splits over three minority votes in
the ratio `minority_split`. So a block has 11 signature sets, in the
order the block collector gathers them: the proposer's signature, the
RANDAO reveal (one message per epoch, as on chain), the first slot's
four aggregates (head vote first), the second slot's, and the sync
aggregate.

Blocks are driven, timed and checked as `blocks.py` does it; `drive`
also reads the program's pubkey-slot counter around the window, so the
result line can give the share of padding key slots.

The process is large: a 1M-key registry, the programs compiled in
set-up, and in a traced run the profiler's trace of the window, which
the harness reduces in this process. So `drive` and `harness_readings`
hand the C heap's free pages back to the OS before the window and
before the reduction (`release_free_heap`): on a one-chip v5e host
that takes the memory in use at the window's start from 21-24 GB to
under 10 GB after a cold set-up.
"""

import ctypes

import numpy as np

from benchmark.traffic import blocks
from benchmark.traffic.blocks import (  # noqa: F401  (the generator API)
    MODS,
    check,
    committee_size,
    counts,
    end_to_end,
)


def _slot_attesters(cfg, slot):
    spe, cps = cfg["SLOTS_PER_EPOCH"], cfg["MAX_COMMITTEES_PER_SLOT"]
    return sum(
        committee_size(cfg["validators"], spe, cps, (slot % spe) * cps + c)
        for c in range(cps)
    )


def votes(n, share, split):
    """Keys per vote of a slot with n attesters: the head vote holds
    round(n * share) (halves up), the minority votes the rest in the
    ratio `split`, rounded down, the remainder to the first."""
    head = int(np.floor(n * share + 0.5))
    rest = n - head
    parts = [rest * w // sum(split) for w in split]
    parts[0] += rest - sum(parts)
    return [head] + parts


def _sets_shape(cfg, mix, share):
    """Keys per set of a block whose two slots' head votes hold `share`."""
    per_slot = [
        votes(_slot_attesters(cfg, s), share, mix["minority_split"])
        for s in (0, 1)
    ]
    return [1, 1] + per_slot[0] + per_slot[1] + [cfg["SYNC_COMMITTEE_SIZE"]]


def warm_batches(cfg, mix):
    """Blocks at the narrowest and the widest head vote: the bus adds
    the sentinel and the canary pair, so these warm every program a
    block of the mix reaches."""
    return [("bus", _sets_shape(cfg, mix, s)) for s in mix["head_share"]]


def generate(cfg, mix, seed, seconds, pool, delta):
    spe = cfg["SLOTS_PER_EPOCH"]
    n_val = cfg["validators"]
    lo, hi = mix["head_share"]
    if 2 * (1 + len(mix["minority_split"])) != cfg["MAX_ATTESTATIONS_ELECTRA"]:
        raise ValueError("a block holds the aggregates of two slots")
    rng = np.random.default_rng(int(seed) % 2**64)
    sync = tuple(
        rng.choice(n_val, cfg["SYNC_COMMITTEE_SIZE"], replace=False).tolist()
    )
    invalid = {e["block"]: e for e in mix["invalid"]}
    epoch_root = {}
    out = []
    for j in range(mix["blocks"]):
        slot = 2 * j + 2
        proposer = (int(rng.integers(n_val)),)
        epoch = slot // spe
        if epoch not in epoch_root:
            epoch_root[epoch] = rng.bytes(32)
        sets = [(rng.bytes(32), proposer, 0), (epoch_root[epoch], proposer, 0)]
        for s in (slot - 2, slot - 1):
            n = _slot_attesters(cfg, s)
            sizes = votes(n, rng.uniform(lo, hi), mix["minority_split"])
            vs = rng.choice(n_val, n, replace=False).tolist()
            pos = 0
            for size in sizes:
                sets.append((rng.bytes(32), tuple(vs[pos:pos + size]), 0))
                pos += size
        sets.append((rng.bytes(32), sync, 0))
        bad = invalid.get(j)
        if bad is not None:
            for i, mod in zip(bad["sets"], MODS[bad["kind"]], strict=True):
                sets[i] = sets[i][:2] + (mod,)
        out.append(sets)
    sigs = pool.map(blocks._sign, [(sets, delta) for sets in out])
    return {"blocks": out, "sigs": sigs}


def _slots():
    """The program's pubkey-slot counter by path ({} where the program
    has none)."""
    from lighthouse_tpu.common.metrics import REGISTRY

    fam = REGISTRY.get("lighthouse_tpu_pubkey_slots_total")
    if fam is None:
        return {}
    return {lbl[0]: c.value for lbl, c in fam.children().items()}


def release_free_heap():
    """Return the free pages of the C heap to the OS (glibc's
    `malloc_trim`), which compiling the programs leaves resident. A
    no-op where the C library has no `malloc_trim`."""
    trim = getattr(ctypes.CDLL(None), "malloc_trim", None)
    if trim is not None:
        trim(0)


def drive(node, traffic, seconds, annotate=None):
    release_free_heap()
    before = _slots()
    out = blocks.drive(node, traffic, seconds, annotate)
    after = _slots()
    out["pubkey_slots"] = {
        path: v - before.get(path, 0) for path, v in after.items()
    }
    return out


def harness_readings(traffic, out):
    """`blocks.py`'s readings and the slot counter's. The harness takes
    them after the window and before it reduces a traced run's trace,
    so the heap freed since the window goes back first."""
    release_free_heap()
    return dict(
        blocks.harness_readings(traffic, out),
        pubkey_slots=out["pubkey_slots"],
    )
