"""BENCH_CONFIG=slotfuse: serial vs one-dispatch-slot A/B.

Drives the SAME deterministic blob-import schedule through two full
`BeaconNode` stacks — one with `--slot-fuse` off (the serial
settle-then-fold shape, two device round trips per blob import) and
one with it on (the chained slot-program, one round trip) — and
reports both arms side by side:

  * wall p50/p99 per arm and the fused/serial speedup ratio;
  * serial-dispatch counts per import (the fused arm must show
    `serial_dispatches_max == 1` with the settle riding a dispatch of
    kind ``fused``);
  * verdict byte-identity: the two arms' canonical journal
    projections (sim/verdict.py — block_import, da_settle, sidecar,
    ... with volatile fields stripped) must be byte-equal, and the two
    head roots must match. A fused run that is fast but diverges is a
    FAILED measurement, not a win.

Geometry comes from bench_slotpath's env knobs (SLOTPATH_BLOCKS /
SLOTPATH_BLOB_PERIOD / SLOTPATH_BLOBS), so the A/B can be pushed to
heavier blob counts without editing either file. Fake backend off
hardware (the CPU proxy: structure exact, milliseconds not hardware),
tpu backend on the chip.
"""

import os

from lighthouse_tpu.bench_slotpath import _blob, _build_node, _geometry
from lighthouse_tpu.sim.verdict import canonical_jsonl


def _drive(backend: str, fuse: bool) -> dict:
    """One arm: boot a node, toggle the fuse, import the schedule, and
    return its timing + forensic summary."""
    from lighthouse_tpu.state_processing.per_block import (
        BlockSignatureStrategy,
    )
    from lighthouse_tpu import kzg

    n_imports, blob_period, blobs_per_slot = _geometry()
    h, node = _build_node(backend)
    chain = node.chain
    chain.slot_fuse = fuse
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
    budget_complete = bool(recs) and all(
        abs(r["union_s"] + r["unattributed_s"] - r["wall_s"]) <= 1e-3
        and r["serial_dispatches"] == len(r["dispatches"])
        for r in recs
    )
    fused_imports = sum(
        1
        for r in recs
        if any(d.get("kind") == "fused" for d in r["dispatches"])
    )
    return {
        "wall_p50_ms": round((summary["wall_p50_s"] or 0.0) * 1e3, 3),
        "wall_p99_ms": round((summary["wall_p99_s"] or 0.0) * 1e3, 3),
        "serial_dispatches_p50": summary["serial_dispatches_p50"],
        "serial_dispatches_max": summary["serial_dispatches_max"],
        "budget_complete": budget_complete,
        "blob_imports": blob_imports,
        "fused_imports": fused_imports,
        "canonical": canonical_jsonl(chain.journal.query()),
        "head_root": chain.head_root.hex(),
    }


def measure(jax, platform):
    on_tpu = platform == "tpu"
    backend = os.environ.get(
        "BENCH_SLOTPATH_BACKEND", "tpu" if on_tpu else "fake"
    )
    n_imports, blob_period, blobs_per_slot = _geometry()

    serial = _drive(backend, fuse=False)
    fused = _drive(backend, fuse=True)

    # the byte-identity gate: identical canonical forensic record and
    # identical head — the fused path changed the dispatch shape, not
    # one observable verdict
    identical = (
        serial["canonical"] == fused["canonical"]
        and serial["head_root"] == fused["head_root"]
    )
    speedup = (
        round(serial["wall_p50_ms"] / fused["wall_p50_ms"], 3)
        if fused["wall_p50_ms"] > 0
        else 0.0
    )

    def arm(d):
        return {k: v for k, v in d.items() if k != "canonical"}

    return {
        "metric": "slotfuse_speedup",
        "value": speedup,
        "unit": "x",
        "vs_baseline": 0.0,
        "platform": platform,
        "impl": backend,
        "n_sets": n_imports,
        "blob_period": blob_period,
        "blobs_per_slot": blobs_per_slot,
        "serial": arm(serial),
        "fused": arm(fused),
        "verdicts_identical": identical,
        "fused_single_dispatch": fused["serial_dispatches_max"] <= 1,
        "budget_complete": (
            serial["budget_complete"] and fused["budget_complete"]
        ),
        "valid_for_headline": bool(
            on_tpu and identical and n_imports >= 16
        ),
    }
