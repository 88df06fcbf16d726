"""BENCH_CONFIG=das: the data-availability sampling plane's kernels.

One line covering both device workloads of `lighthouse_tpu/da`:

  * Reed-Solomon blob extension (`ops/rs_extend` via
    `da.erasure.extend_blobs(backend="tpu")`) — the column-sidecar
    production path: every blob polynomial evaluated over the 2x
    extended domain in ONE batched Horner scan, checked byte-identical
    against the host bigint oracle every iteration.
  * Batched cell-multiproof verification
    (`da.cells.verify_cell_proof_batch(backend="tpu")`) — the sampling
    hot path: all cells of all blobs folded into ONE two-pair pairing
    on the guarded device plane, cross-checked against the ref verdict
    (and a corrupted batch must be REJECTED by both tiers — agreement
    on accept alone would not prove soundness).

Both paths go through the guarded executor (GUARD.dispatch with
xla-host -> ref failover), so a device fault degrades the number,
never the correctness assertions. The headline `value` is cell proofs
verified per second through the fold; the extension throughput rides
as `extend_evals_per_sec`.

Shape knobs: BENCH_NSETS = blob count (default 8). The geometry is the
dev preset scaled up (64-element blobs, 32-element cells -> 4 columns);
mainnet-scale blob counts are the ROADMAP's remaining DA item, not this
config's claim.
"""

import json
import os
import time

N_BLOB_ELEMENTS = 64
N_CELL_ELEMENTS = 32


def _blob(geo, seed: int) -> bytes:
    return b"".join(
        ((seed * 997 + i * 2654435761 + 13) % (2**200)).to_bytes(32, "big")
        for i in range(geo.blob_elements)
    )


def measure(jax, platform):
    from lighthouse_tpu import kzg
    from lighthouse_tpu.da import cells as da_cells
    from lighthouse_tpu.da import erasure
    from lighthouse_tpu.da.domain import geometry

    if platform == "cpu":
        n_blobs, blob_n, cell_m, reps = 2, 8, 4, 2  # prove the path only
    else:
        n_blobs = int(os.environ.get("BENCH_NSETS") or 8)
        blob_n, cell_m, reps = N_BLOB_ELEMENTS, N_CELL_ELEMENTS, 5

    geo = geometry(blob_n, cell_m)
    setup = kzg.dev_setup(blob_n)
    blobs = [_blob(geo, k) for k in range(n_blobs)]

    # ---- RS extension: device vs host oracle, then steady-state p50
    oracle = erasure.extend_blobs(blobs, geo, consumer="bench")
    t0 = time.perf_counter()
    got = erasure.extend_blobs(blobs, geo, backend="tpu", consumer="bench")
    compile_s = time.perf_counter() - t0
    if got != oracle:
        raise RuntimeError("device RS extension diverged from host oracle")
    extend_t = []
    for _ in range(reps):
        t0 = time.perf_counter()
        got = erasure.extend_blobs(
            blobs, geo, backend="tpu", consumer="bench"
        )
        extend_t.append(time.perf_counter() - t0)
        if got != oracle:
            raise RuntimeError(
                "device RS extension diverged from host oracle"
            )
    extend_p50 = sorted(extend_t)[len(extend_t) // 2]

    # ---- cell multiproofs: one item per (blob, cell), one fold
    items = []
    for blob in blobs:
        comm = kzg.blob_to_kzg_commitment(blob, setup, consumer="bench")
        cells, proofs = da_cells.compute_cells_and_kzg_proofs(
            blob, geo, setup=setup, consumer="bench"
        )
        items.extend(
            (comm, k, cells[k], proofs[k]) for k in range(geo.num_cells)
        )

    def verify(batch, backend):
        return da_cells.verify_cell_proof_batch(
            batch, geo, backend=backend, setup=setup, seed=7,
            consumer="bench",
        )

    t0 = time.perf_counter()
    dev_ok = verify(items, "tpu")
    verify_compile_s = time.perf_counter() - t0
    if not (dev_ok and verify(items, "ref")):
        raise RuntimeError("honest cell batch rejected (tpu/ref disagree)")
    # soundness half of the oracle check: one flipped cell byte must be
    # rejected on BOTH tiers
    comm, k, cell, proof = items[0]
    bad = [(comm, k, bytes([cell[0] ^ 1]) + cell[1:], proof)] + items[1:]
    if verify(bad, "tpu") or verify(bad, "ref"):
        raise RuntimeError("corrupted cell batch accepted")

    verify_t = []
    for _ in range(reps):
        t0 = time.perf_counter()
        ok = verify(items, "tpu")
        verify_t.append(time.perf_counter() - t0)
        if not ok:
            raise RuntimeError("cell batch rejected mid-measurement")
    verify_p50 = sorted(verify_t)[len(verify_t) // 2]

    on_tpu = platform == "tpu"
    return {
        "metric": "das_cell_verify_throughput",
        "value": round(len(items) / verify_p50, 2),
        "unit": "cells/sec",
        "vs_baseline": 0.0,  # no published reference number for this shape
        "platform": platform,
        "impl": "rs_horner+cell_fold",
        "n_sets": n_blobs,
        "n_cells": len(items),
        "blob_elements": geo.blob_elements,
        "cell_elements": geo.cell_elements,
        "p50_s": round(verify_p50, 4),
        "extend_evals_per_sec": round(
            n_blobs * geo.ext_elements / extend_p50, 2
        ),
        "extend_p50_s": round(extend_p50, 4),
        "compile_s": round(compile_s + verify_compile_s, 1),
        "byte_identical": True,
        "valid_for_headline": bool(on_tpu and n_blobs >= 8),
    }


if __name__ == "__main__":
    print(json.dumps(measure(None, "cpu"), indent=2))
