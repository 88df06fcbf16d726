#!/usr/bin/env python3
"""Chip smoke: the beacon node's signature-verification data plane on one
TPU chip, through the entry points the node itself calls.

Phases, all in this one process (one process holds the chip):

  device     `jax.devices()[0].platform` must be "tpu" — nothing else, no
             CPU switch, no probe child.
  slot       the mainnet slot shape (BASELINE.json north star): 30720
             single-key sets over 64 distinct messages through
             `bls.api.verify_signature_sets(..., backend="tpu")` — marshal,
             message grouping, bucketing and the guarded device plane.
             The valid batch must verify, the batch with one forged
             signature must not, and sampled sets must agree one by one
             with the `ref` oracle.
  distinct   1024 distinct-message single-key sets (BASELINE config 1).
  sync       one 512-key sync-committee aggregate (BASELINE config 2).
  bn         `lighthouse_tpu bn --network mainnet --bls-backend tpu
             --validators 8192 --slots 4`: every block imports and the
             head advances every slot, and the node's batches gather
             pubkeys from the table its chain built at start-up. The
             phase reports that build and the first bus batch's costs.

After every phase the guard must show no failover, device fault, open
breaker or abandoned dispatch, and the phase must have recorded at least
one `bls`-plane device batch (`lighthouse_tpu_device_batches_total` with
device lanes). The programs the run dispatches are compiled up front, in
parallel threads, by the same compile-ahead the verify path runs before
its guarded crossing; their compile seconds are reported, never counted as
dispatch time.

Output: one JSON line per phase, then as the LAST line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}`.
Any failure prints no such line and exits non-zero. There is no four-chip
phase: no node path uses a device mesh.
"""

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


NODE_VALIDATORS = 8192  # the bn phase's registry


class SmokeFailure(Exception):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _device_batches():
    """{lanes: count} of bls-plane device batches so far (host backends
    record lanes="host")."""
    from lighthouse_tpu.common.device_attribution import _BATCHES

    out = {}
    for labels, child in _BATCHES.children().items():
        consumer, plane, lanes = labels
        if plane == "bls" and lanes != "host":
            out[lanes] = out.get(lanes, 0) + int(child.value)
    return out


def _guard_report(phase, batches_before):
    """The guard counters after a phase; raises on any failover, fault,
    open breaker or abandoned dispatch, or a phase with no device batch."""
    from lighthouse_tpu.device_plane import GUARD

    st = GUARD.stats()
    after = _device_batches()
    delta = {
        k: v - batches_before.get(k, 0)
        for k, v in after.items()
        if v - batches_before.get(k, 0)
    }
    not_closed = {k: v for k, v in st["breaker"]["state"].items()
                  if v != "closed"}
    report = {
        "dispatches": st["dispatches"],
        "faults": st["faults"],
        "failovers": st["failovers"],
        "abandoned": st["abandoned"],
        "breaker_not_closed": not_closed,
        "device_batches_by_lanes": delta,
    }
    _check(not st["faults"], f"{phase}: device faults {st['faults']}")
    _check(not st["failovers"], f"{phase}: failovers {st['failovers']}")
    _check(not st["abandoned"], f"{phase}: abandoned dispatches")
    _check(not not_closed, f"{phase}: breaker not closed {not_closed}")
    _check(sum(delta.values()) > 0, f"{phase}: no bls device batch")
    return report


def _verify(sets, seed):
    from lighthouse_tpu.bls import api
    from lighthouse_tpu.common.tracing import TRACER

    t0 = time.perf_counter()
    ok = api.verify_signature_sets(
        sets, backend="tpu", seed=seed, consumer="bench"
    )
    wall = time.perf_counter() - t0
    root = next(r for r in reversed(TRACER.recent()) if r["name"] == "verify")
    return ok, wall, _batch_stats(root)


def _batch_stats(root):
    """One batch's host and device phases, from its `verify` span tree."""
    from lighthouse_tpu.common.tracing import find

    def ms(*names):
        return 1e3 * sum(
            s["duration_s"] for n in names for s in find(root, n)
        )

    marshal = find(root, "verify/marshal")
    attrs = marshal[0].get("attrs", {}) if marshal else {}
    return {
        "shape": attrs.get("shape"),
        "grouped": attrs.get("layout") == "grouped",
        "subgroup_ms": ms("verify/subgroup_check"),
        "host_ms": ms(
            "verify/subgroup_check", "verify/marshal", "verify/rlc_sample"
        ),
        "compile_ms": ms("verify/compile"),
        "device_ms": ms("verify/device"),
    }


def _batch_phase(name, sets, ref_sample, fillers, seed):
    """cold + warm valid runs, one forged run, per-set ref agreement.
    `fillers` are sets this run has already verified valid; None means
    the phase's own shape is a single set."""
    from lighthouse_tpu import testing as td
    from lighthouse_tpu.bls import api

    before = _device_batches()
    ok_cold, cold_s, cold_stats = _verify(sets, seed)
    _check(ok_cold is True, f"{name}: valid batch (cold) returned {ok_cold}")
    ok_warm, warm_s, warm_stats = _verify(sets, seed + 1)
    _check(ok_warm is True, f"{name}: valid batch (warm) returned {ok_warm}")
    bad_index = len(sets) // 2
    forged = td.forge_signature_set(sets[bad_index])
    bad = list(sets)
    bad[bad_index] = forged
    ok_bad, bad_s, _ = _verify(bad, seed + 2)
    _check(ok_bad is False, f"{name}: forged batch returned {ok_bad}")

    # per-set agreement with the ref oracle: each sampled set is
    # verified on the device in a batch whose other members are sets
    # this run has already verified valid (`fillers`), so the batch
    # verdict is that set's verdict — on an already-compiled bucket
    agree = []
    for s in [sets[i] for i in ref_sample] + [forged]:
        ref = api.verify_signature_sets([s], backend="ref")
        dev = _verify([s] + (fillers or []), seed + 3)[0]
        _check(dev == ref, f"{name}: device {dev} vs ref {ref} on a set")
        agree.append(ref)
    _check(agree[-1] is False and all(agree[:-1]), f"{name}: ref {agree}")

    return {
        "phase": name,
        "n_sets": len(sets),
        "n_messages": len({bytes(s.message) for s in sets}),
        "max_keys": max(len(s.pubkeys) for s in sets),
        "bucket": cold_stats.get("shape"),
        "grouped": cold_stats.get("grouped"),
        "cold_wall_s": cold_s,
        "cold_subgroup_ms": cold_stats.get("subgroup_ms"),
        "cold_compile_s": cold_stats.get("compile_ms", 0.0) / 1e3,
        "warm_wall_s": warm_s,
        "warm_host_ms": warm_stats.get("host_ms"),
        "warm_device_ms": warm_stats.get("device_ms"),
        "forged_wall_s": bad_s,
        "ref_agreement": len(agree),
        "guard": _guard_report(name, before),
    }


def _node_batch(sets, validators):
    """`sets` in the shape of the node phase's canaried bus batches: each
    key tagged by a PubkeyCache of `validators` keys whose device table
    is built (the node's table shape), then the canary's valid sentinel,
    which rides the batch as an overflow row."""
    from lighthouse_tpu import bls
    from lighthouse_tpu.device_plane import canary
    from lighthouse_tpu.state_processing.pubkey_cache import PubkeyCache

    points = [pk.point for s in sets for pk in s.pubkeys]
    cache = PubkeyCache()
    for v in range(validators):
        pk = bls.PublicKey(points[v % len(points)])
        pk.validator_index = v
        pk.cache = cache
        cache._by_index.append(pk)
    cache.device_table()
    tagged = []
    for s in sets:
        keys = cache._by_index[: len(s.pubkeys)]
        tagged.append(bls.SignatureSet(s.signature, keys, s.message))
    return tagged + [canary.bls_sentinels()[0]]


def _pubkey_slots():
    """{path: live pubkey slots} marshalled so far."""
    from lighthouse_tpu.bls import tpu_backend

    return {
        path: int(tpu_backend._PUBKEY_SLOTS.labels(path).value)
        for path in ("table", "overflow", "packed")
    }


def _node_costs(t_phase):
    """The node's start-up table build and its first canaried bus batch,
    from the span trees opened since `t_phase` (wall clock)."""
    from lighthouse_tpu.common.tracing import TRACER, find

    roots = [r for r in TRACER.recent() if r["wall_start"] >= t_phase]
    builds = [r for r in roots if r["name"] == "chain/pubkey_table"]
    batches = sorted(
        (b for r in roots for b in find(r, "bus/batch")),
        key=lambda b: b["wall_start"],
    )
    out = {
        "pubkey_table_build_s": [b["duration_s"] for b in builds],
        "pubkey_table_keys": [b["attrs"].get("keys") for b in builds],
        "bus_batches": len(batches),
    }
    if batches:
        first = batches[0]

        def ms(name):
            return 1e3 * sum(s["duration_s"] for s in find(first, name))

        out["first_bus_batch"] = {
            "wall_ms": 1e3 * first["duration_s"],
            "shapes": [
                m["attrs"].get("shape") for m in find(first, "verify/marshal")
            ],
            "pubkey_paths": [
                p["attrs"].get("path")
                for p in find(first, "verify/marshal/pubkeys")
            ],
            "marshal_ms": ms("verify/marshal"),
            "pubkeys_ms": ms("verify/marshal/pubkeys"),
            "compile_ms": ms("verify/compile"),
            "canary_ms": ms("verify/canary"),
            "device_ms": ms("verify/device"),
        }
    return out


def _bn_phase(validators, slots):
    from lighthouse_tpu import cli
    from lighthouse_tpu.common import tracing

    # room for every span tree of the phase, so its first batch stays
    tracing.configure(capacity=8192)
    before = _device_batches()
    slots_before = _pubkey_slots()
    t_phase = time.time()
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        rc = cli.main([
            "bn", "--network", "mainnet", "--bls-backend", "tpu",
            "--validators", str(validators), "--slots", str(slots),
            "--http-port", "0",
        ])
    wall = time.perf_counter() - t0
    text = out.getvalue()
    heads = re.findall(r"^slot (\d+) head=0x([0-9a-f]+)", text, re.M)
    _check(rc == 0, f"bn: exit code {rc}: {text[-2000:]}")
    _check("dev chain complete" in text, "bn: dev chain did not complete")
    _check(
        [int(s) for s, _ in heads] == list(range(1, slots + 1)),
        f"bn: slots imported {[s for s, _ in heads]}",
    )
    _check(
        len({h for _, h in heads}) == slots,
        f"bn: head did not advance every slot {heads}",
    )
    slots_after = _pubkey_slots()
    _check(
        slots_after["table"] > slots_before["table"],
        "bn: no pubkey slot gathered from the chain's table",
    )
    return {
        "phase": "bn",
        "validators": validators,
        "slots": slots,
        "heads": [h for _, h in heads],
        "wall_s": wall,
        "pubkey_slots": {
            k: slots_after[k] - slots_before[k] for k in slots_after
        },
        **_node_costs(t_phase),
        "guard": _guard_report("bn", before),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    dev = devices[0]
    _check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }
    print(json.dumps({"phase": "device", **device}), flush=True)

    from lighthouse_tpu import testing as td
    from lighthouse_tpu.backend import enable_compile_cache
    from lighthouse_tpu.bls import tpu_backend

    enable_compile_cache()

    t0 = time.perf_counter()
    slot = td.make_api_signature_sets(64, 480, seed=args.seed)
    distinct = td.make_api_signature_sets(1024, 1, seed=args.seed + 1)
    sync = td.make_api_signature_sets(1, 1, keys_per_set=512,
                                      seed=args.seed + 2)
    build_s = time.perf_counter() - t0

    # every program the run dispatches, compiled ahead in parallel
    # threads (the compiler releases the GIL; on the chip host one
    # verify program is minutes of Mosaic compile, and concurrent
    # compiles finish several times sooner than back to back). Besides
    # the three API shapes: the buckets of the node phase's canaried bus
    # batches at 8192 validators — chain-tagged keys gathered from the
    # node's pubkey table plus the canary's untagged sentinel, at most 4
    # single-key sets, and 5-8 sets of at most 128 keys (a bucket not
    # warmed here is compiled on first use, outside the watchdog, just
    # later)
    buckets = {
        "slot": slot,
        "distinct": distinct,
        "sync": sync,
        "node_s4k1": _node_batch(
            td.make_api_signature_sets(3, 1, seed=args.seed + 3),
            NODE_VALIDATORS,
        ),
        "node_s8k128": _node_batch(
            td.make_api_signature_sets(
                5, 1, keys_per_set=128, seed=args.seed + 4
            ),
            NODE_VALIDATORS,
        ),
    }
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(buckets)) as pool:
        compile_s = dict(
            zip(buckets, pool.map(tpu_backend.compile_ahead,
                                  buckets.values()))
        )
    print(json.dumps({
        "phase": "compile_ahead",
        "fixture_build_s": build_s,
        "wall_s": time.perf_counter() - t0,
        "compile_s": compile_s,
    }), flush=True)
    print(json.dumps(_bn_phase(NODE_VALIDATORS, 4)), flush=True)
    fillers = distinct[1:]
    for phase in (
        ("distinct", distinct, [0, 1023], fillers),
        ("slot", slot, [0, 30719], fillers),
        ("sync", sync, [0], None),
    ):
        print(json.dumps(_batch_phase(*phase, seed=args.seed)), flush=True)
    from lighthouse_tpu.common.compile_ledger import LEDGER

    print(json.dumps({
        "phase": "total",
        "wall_s": time.perf_counter() - t_start,
        "compiles": [
            [e["fn"], e["shape"], e.get("duration_s")]
            for e in LEDGER.entries()
            if e["event"] == "cold"
        ],
    }), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        sys.exit(1)
