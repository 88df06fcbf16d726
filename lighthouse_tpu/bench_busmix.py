"""BENCH_CONFIG=busmix: mixed-consumer replay through the verification
bus vs direct dispatch, on the REAL backend.

The serve-config A/B (bench_serve, fake backend) proves the bus's
scheduling; THIS config prices it on hardware: N gossip-single
verifications dispatched one-by-one (the pre-bus shape — every single
pays the ~90 ms fixed device cost alone) vs the same N submitted
concurrently through the bus (coalesced into shared batches on the
bucketed-pow2 lanes). The headline value is the wall-clock speedup
direct/bus; the record carries the measured per-batch economics
(batches formed, mean live sets, cumulative modeled fixed cost).

BENCH_NSETS controls the single count (default 64 — enough waves to
learn the wall model without burning a compile per pow2 bucket).
"""

import json
import os
import threading
import time

CONSUMER_CYCLE = ("gossip_single", "sidecar_header", "oppool")


def _make_sets(n_keys: int = 8):
    from lighthouse_tpu import bls

    keypairs = bls.interop_keypairs(n_keys)
    sets = []
    for i, kp in enumerate(keypairs):
        msg = f"busmix:{i}".encode()
        sets.append(bls.SignatureSet(kp.sk.sign(msg), [kp.pk], msg))
    return sets


def measure(jax, platform):
    from lighthouse_tpu import bls
    from lighthouse_tpu.common import device_attribution as attribution
    from lighthouse_tpu.verification_bus import VerificationBus

    n_singles = int(os.environ.get("BENCH_NSETS", "64"))
    backend = "tpu"
    n_threads = 4
    sets = _make_sets()

    # ---- direct dispatch: every single pays the fixed cost alone ----
    amort0 = attribution.amortized_totals()
    # warm the N=1 bucket once so the direct loop measures dispatch,
    # not compile (the bus phase pays its own bucket compiles and the
    # ledger attributes them)
    bls.verify_signature_sets(
        [sets[0]], backend=backend, consumer="bench"
    )
    t0 = time.perf_counter()
    for i in range(n_singles):
        bls.verify_signature_sets(
            [sets[i % len(sets)]], backend=backend, consumer="bench"
        )
    direct_wall = time.perf_counter() - t0
    direct_amort = sum(
        v - amort0.get(k, 0.0)
        for k, v in attribution.amortized_totals().items()
        if k[0] == "bench"
    )

    # ---- the same traffic through the bus, mixed consumers ----------
    bus = VerificationBus(backend=backend, max_hold_ms=30.0)
    amort1 = attribution.amortized_totals()
    per_thread = max(1, n_singles // n_threads)
    t0 = time.perf_counter()

    def worker(tid: int):
        for i in range(per_thread):
            consumer = CONSUMER_CYCLE[(tid + i) % len(CONSUMER_CYCLE)]
            bus.submit(
                [sets[(tid * per_thread + i) % len(sets)]],
                consumer=consumer,
            )

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    bus_wall = time.perf_counter() - t0
    bus_amort = sum(
        v - amort1.get(k, 0.0)
        for k, v in attribution.amortized_totals().items()
        if k[0] in CONSUMER_CYCLE
    )
    stats = bus.stats()

    n_bus = per_thread * n_threads
    speedup = (
        (direct_wall / n_singles) / (bus_wall / n_bus)
        if bus_wall > 0
        else 0.0
    )
    return {
        "metric": "bus_amortization_speedup",
        "value": round(speedup, 4),
        "unit": "x (per-verification wall, direct/bus)",
        "vs_baseline": 0.0,
        "platform": platform,
        "impl": os.environ.get("BENCH_IMPL", "xla"),
        "n_sets": n_singles,
        "direct_wall_s": round(direct_wall, 4),
        "bus_wall_s": round(bus_wall, 4),
        "direct_amortized_fixed_ms": round(direct_amort, 1),
        "bus_amortized_fixed_ms": round(bus_amort, 1),
        "bus_batches": stats["batches_formed"],
        "bus_mean_live": stats["mean_live_per_batch"],
        "bus_coalesced": stats["coalesced_batches"],
        "bus_triggers": stats["triggers"],
        "valid_for_headline": False,
    }


if __name__ == "__main__":
    print(json.dumps(measure(None, "cpu"), indent=2))
