"""BENCH_CONFIG=slotpath: the slot-budget decomposition harness.

Boots ONE full `BeaconNode` (fake crypto backend — the CPU proxy),
drives BENCH_NSETS block imports
through `chain.process_block`, and reports the slot-budget recorder's
decomposition: per-stage medians, import wall p50/p99 against the
200 ms budget, the serial-dispatch count, and the fusable gap — the
host time between consecutive device round trips that the ROADMAP's
one-dispatch-slot item would erase. `scripts/perf_gate.py` diffs this
line against its committed baseline.

On the fake backend the STAGE TIMINGS are a CPU proxy (the structure —
stage set, serial-dispatch count, accounting identity — is exact; the
milliseconds are not hardware), so the line is `valid_for_headline`
only on the tpu.
"""

import os

from lighthouse_tpu.common.slot_budget import SLOT_BUDGET_MS

N_VALIDATORS = 16
# bellatrix activates at epoch 1 (minimal: slot 8); every 4th slot
# after that carries blobs so the import pays the KZG-settle round trip
# on top of the signature fold — the two-dispatch shape whose gap the
# fusable-gap ledger exists to measure. SLOTPATH_BLOB_PERIOD/
# SLOTPATH_BLOBS override the cadence and per-slot blob count so the
# fused path can be benched at heavier blob geometries without
# editing this file.
BLOB_PERIOD = 4


def _geometry():
    """(n_imports, blob_period, blobs_per_slot) from the env:
    SLOTPATH_BLOCKS (BENCH_NSETS keeps working as the legacy name),
    SLOTPATH_BLOB_PERIOD, SLOTPATH_BLOBS."""
    n_imports = int(
        os.environ.get("SLOTPATH_BLOCKS")
        or os.environ.get("BENCH_NSETS")
        or 16
    )
    period = int(os.environ.get("SLOTPATH_BLOB_PERIOD") or BLOB_PERIOD)
    blobs = int(os.environ.get("SLOTPATH_BLOBS") or 2)
    return n_imports, max(1, period), max(1, blobs)


def _build_node(backend: str):
    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.node import BeaconNode
    from lighthouse_tpu.types.spec import minimal_spec

    spec = minimal_spec(
        name="bench-slotpath",
        ALTAIR_FORK_EPOCH=0,
        BELLATRIX_FORK_EPOCH=1,
    )
    h = Harness(spec, N_VALIDATORS, backend=backend)
    node = BeaconNode("bench0", h.state, spec, backend=backend)
    return h, node


def _blob(spec, seed: int) -> bytes:
    n = int(spec.FIELD_ELEMENTS_PER_BLOB)
    return b"".join(
        ((seed * 2654435761 + i * 31 + 7) % (2**200)).to_bytes(32, "big")
        for i in range(n)
    )


def measure(jax, platform):
    from lighthouse_tpu import kzg
    from lighthouse_tpu.state_processing.per_block import (
        BlockSignatureStrategy,
    )

    on_tpu = platform == "tpu"
    # the import pipeline's crypto backend: real kernels on hardware,
    # the fake backend as the CPU proxy (BENCH_SLOTPATH_BACKEND
    # overrides, e.g. =ref to time the host reference pairing)
    backend = os.environ.get(
        "BENCH_SLOTPATH_BACKEND", "tpu" if on_tpu else "fake"
    )
    n_imports, blob_period, blobs_per_slot = _geometry()

    h, node = _build_node(backend)
    chain = node.chain
    # BENCH_SLOTFUSE=off restores the serial three-dispatch path (the
    # A/B partner bench_slotfuse drives both arms itself)
    if os.environ.get("BENCH_SLOTFUSE") == "off":
        chain.slot_fuse = False
    recorder = chain.slot_budget
    recorder.configure(ring=max(n_imports + 8, 128))
    blob_start = int(h.spec.SLOTS_PER_EPOCH)
    blob_imports = 0
    for slot in range(1, n_imports + 1):
        node.on_slot(slot)
        if slot >= blob_start and slot % blob_period == 0:
            blob_imports += 1
            blobs = [
                _blob(h.spec, slot * 16 + i)
                for i in range(blobs_per_slot)
            ]
            comms = [
                kzg.blob_to_kzg_commitment(b, consumer="bench")
                for b in blobs
            ]
            block = h.produce_block(
                slot, [], blob_kzg_commitments=comms
            )
            h.import_block(
                block, strategy=BlockSignatureStrategy.NO_VERIFICATION
            )
            for sc in h.make_blob_sidecars(block, blobs):
                chain.process_blob_sidecar(sc)
        else:
            block = h.produce_block(slot, [])
            h.import_block(
                block, strategy=BlockSignatureStrategy.NO_VERIFICATION
            )
        chain.process_block(block)

    recs = recorder.recent()
    summary = recorder.summary()
    # the recorder's defining identity must close on every import —
    # a gate run with broken accounting is not a timing regression,
    # it is a broken instrument
    accounting_complete = bool(recs) and all(
        abs(r["union_s"] + r["unattributed_s"] - r["wall_s"]) <= 1e-3
        and r["serial_dispatches"] == len(r["dispatches"])
        for r in recs
    )
    wall_p50_ms = round((summary["wall_p50_s"] or 0.0) * 1000.0, 3)
    # the gap is only defined between round trips: report its median
    # over the imports that paid >= 2 serial dispatches (blob slots —
    # settle then fold), where a fused slot-program would collapse them
    multi_gaps = sorted(
        r["fusable_gap_s"]
        for r in recs
        if r["serial_dispatches"] >= 2
    )
    gap_multi_ms = round(
        multi_gaps[len(multi_gaps) // 2] * 1000.0, 3
    ) if multi_gaps else 0.0
    # one-dispatch-slot evidence: how many imports went out as a fused
    # chained program (dispatch kind "fused") vs the serial shape
    fused_imports = sum(
        1
        for r in recs
        if any(d.get("kind") == "fused" for d in r["dispatches"])
    )
    return {
        "metric": "slotpath_wall_p50_ms",
        "value": wall_p50_ms,
        "unit": "ms",
        "vs_baseline": 0.0,
        "budget_utilization": round(wall_p50_ms / SLOT_BUDGET_MS, 4),
        "platform": platform,
        "impl": backend,
        "n_sets": n_imports,
        "p50_s": round(wall_p50_ms / 1000.0, 4),
        "wall_p99_ms": round(
            (summary["wall_p99_s"] or 0.0) * 1000.0, 3
        ),
        "stages_p50_ms": {
            name: round(s["p50_s"] * 1000.0, 3)
            for name, s in summary["stages"].items()
        },
        "fusable_gap_p50_ms": round(
            (summary["fusable_gap_p50_s"] or 0.0) * 1000.0, 3
        ),
        "fusable_gap_multi_dispatch_p50_ms": gap_multi_ms,
        "multi_dispatch_imports": len(multi_gaps),
        "serial_dispatches_p50": summary["serial_dispatches_p50"],
        "serial_dispatches_max": summary["serial_dispatches_max"],
        "accounting_complete": accounting_complete,
        "slot_fuse": bool(chain.slot_fuse),
        "blob_imports": blob_imports,
        "fused_imports": fused_imports,
        "blob_period": blob_period,
        "blobs_per_slot": blobs_per_slot,
        "valid_for_headline": bool(on_tpu and n_imports >= 16),
    }
