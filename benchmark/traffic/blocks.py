"""Closed-loop block import: one block outstanding, each block fresh.

A block's signature sets, in the order the block collector gathers them:
the proposer's signature over the block root, the RANDAO reveal over the
epoch (one message per epoch, as on chain), MAX_ATTESTATIONS aggregate
attestations with full participation, and the sync aggregate of
SYNC_COMMITTEE_SIZE keys. Block j sits at slot 2j + 2 and carries the
committees of slots 2j and 2j + 1, as a block after a missed slot does,
so no two blocks share an attestation message; the sync aggregate signs
a fresh root in every block. Every block is one `bus.submit(consumer=
"gossip_single")`, as `chain.process_block`'s signature collector makes
it; the state transition is not run.

Every seed gets the same blocks by shape; the seed picks members,
proposers and messages. The mix names its invalid blocks by place
(`invalid`: block, kind, sets): a "pair" (+Delta and -Delta on two sets,
which cancel in an unweighted sum) or a "torsion" signature (a point of
order 13 off G2 added; see `benchmark.signing`).
"""

import time
from contextlib import nullcontext

import numpy as np

from benchmark.signing import TORSION

MODS = {"pair": (1, -1), "torsion": (TORSION,)}


def committee_size(n_validators, slots_per_epoch, committees_per_slot, i):
    """Size of committee `i` of an epoch (compute_committee's split)."""
    count = slots_per_epoch * committees_per_slot
    return n_validators * (i + 1) // count - n_validators * i // count


def _sets_shape(cfg):
    """Keys per set of one block: proposer, randao, the aggregates, sync."""
    spe, cps, n = (
        cfg["SLOTS_PER_EPOCH"], cfg["MAX_COMMITTEES_PER_SLOT"],
        cfg["validators"],
    )
    atts = [
        committee_size(n, spe, cps, i)
        for i in range(cfg["MAX_ATTESTATIONS"])
    ]
    return [1, 1] + atts + [cfg["SYNC_COMMITTEE_SIZE"]]


def warm_batches(cfg, mix):
    """One block-shaped batch: the bus adds the sentinel and the canary
    pair, so this warms every bucket a block reaches."""
    return [("bus", _sets_shape(cfg))]


def generate(cfg, mix, seed, seconds, pool, delta):
    spe = cfg["SLOTS_PER_EPOCH"]
    cps = cfg["MAX_COMMITTEES_PER_SLOT"]
    n_val = cfg["validators"]
    if 2 * cps != cfg["MAX_ATTESTATIONS"]:
        raise ValueError("a block holds the committees of two slots")
    rng = np.random.default_rng(int(seed) % 2**64)
    sync = tuple(
        rng.choice(n_val, cfg["SYNC_COMMITTEE_SIZE"], replace=False).tolist()
    )
    invalid = {e["block"]: e for e in mix["invalid"]}
    epoch_root = {}
    blocks = []
    for j in range(mix["blocks"]):
        slot = 2 * j + 2
        proposer = (int(rng.integers(n_val)),)
        epoch = slot // spe
        if epoch not in epoch_root:
            epoch_root[epoch] = rng.bytes(32)
        sets = [(rng.bytes(32), proposer, 0), (epoch_root[epoch], proposer, 0)]
        for s in (slot - 2, slot - 1):
            sizes = [
                committee_size(n_val, spe, cps, (s % spe) * cps + c)
                for c in range(cps)
            ]
            vs = rng.choice(n_val, sum(sizes), replace=False).tolist()
            pos = 0
            for size in sizes:
                sets.append((rng.bytes(32), tuple(vs[pos:pos + size]), 0))
                pos += size
        sets.append((rng.bytes(32), sync, 0))
        bad = invalid.get(j)
        if bad is not None:
            for i, mod in zip(bad["sets"], MODS[bad["kind"]], strict=True):
                sets[i] = sets[i][:2] + (mod,)
        blocks.append(sets)
    sigs = pool.map(_sign, [(sets, delta) for sets in blocks])
    return {"blocks": blocks, "sigs": sigs}


def _sign(job):
    from benchmark.signing import sign_sets

    return sign_sets(job)


def _expected(sets):
    from benchmark.signing import expected_sigs

    return expected_sigs(sets)


def drive(node, traffic, seconds, annotate=None):
    """Import blocks back to back until the window closes (or the blocks
    run out); the block in flight at the close is waited for."""
    from lighthouse_tpu import bls
    from lighthouse_tpu.device_plane import GUARD

    ann = annotate or (lambda name: nullcontext())
    cache, bus = node.cache, node.bus
    records = []
    decode = {"s": 0.0, "n": 0}

    def failovers():
        return sum(GUARD.stats()["failovers"].values())

    t0 = node.open_window(annotate)
    t_end = t0 + seconds
    for sets, sigs in zip(traffic["blocks"], traffic["sigs"]):
        if time.perf_counter() >= t_end:
            break
        t_b = time.perf_counter()
        with ann("bench/decode"):
            decoded = [bls.Signature.from_bytes(s) for s in sigs]
        t_d = time.perf_counter()
        ssets = [
            bls.SignatureSet(sig, [cache.get(v) for v in vs], m)
            for sig, (m, vs, _) in zip(decoded, sets)
        ]
        f0 = failovers()
        with ann("bench/bus_submit"):
            ok = bus.submit(
                ssets, consumer="gossip_single", backend="tpu",
                journal=node.journal,
            )
        t_v = time.perf_counter()
        decode["s"] += t_d - t_b
        decode["n"] += len(sigs)
        records.append({
            "t_start": t_b, "t_verdict": t_v, "verdict": bool(ok),
            "host_tier": failovers() != f0,
        })
    node.close_window()
    return {
        "t0": t0, "t_end": t_end, "t_close": time.perf_counter(),
        "records": records, "decode": decode,
    }


def _completed(out):
    return [r for r in out["records"] if r["t_verdict"] <= out["t_end"]]


def end_to_end(traffic, out, seconds):
    done = _completed(out) or out["records"][:1]
    return {
        "block_ms": sum(r["t_verdict"] - r["t_start"] for r in done)
        / len(done) * 1e3,
    }


def harness_readings(traffic, out):
    recs = out["records"]
    return {
        "decode_s": out["decode"]["s"],
        "decode_n": out["decode"]["n"],
        "blocks_completed": len(_completed(out)),
        "blocks_run": len(recs),
        "blocks_generated": len(traffic["blocks"]),
        # per block, start (bytes in hand) to verdict, in seconds
        "block_s": [round(r["t_verdict"] - r["t_start"], 4) for r in recs],
    }


def counts(traffic, out):
    """(attempted, failed): blocks started in the window; failed = a
    block answered by a host failover tier."""
    recs = out["records"]
    return len(recs), sum(r["host_tier"] for r in recs)


def check(traffic, out, pool):
    """Every block's verdict against the reference."""
    n = len(out["records"])
    expected = pool.map(_expected, traffic["blocks"][:n])
    wrong = 0
    for rec, sigs, exp in zip(out["records"], traffic["sigs"], expected):
        want = all(a == b for a, b in zip(sigs, exp))
        wrong += rec["verdict"] != want
    return {"wrong_verdicts": (wrong, 0)}
