"""Benchmark config #4 (BASELINE.md): 32k gossip attestations across 64
committees — the operation-pool ingest pipeline, measured end to end.

Role of /root/reference/beacon_node/operation_pool/src/lib.rs:276 +
the gossip attestation path: every attestation arrives with a fresh
compressed signature; the pipeline is

  1. signature DECOMPRESSION (host, per signature — nothing memoizes),
  2. signature SUBGROUP CHECKS (batched on DEVICE:
     ops.batch_verify.g2_points_in_subgroup — host-side python checks
     would cost ~30 ms/sig),
  3. batched RLC VERIFY in chunks with the double-buffered stream
     dispatch (message hash_to_curve memoized: 64 distinct committee
     messages across the whole load),
  4. per-committee AGGREGATION (G2 adds + bit OR) into the naive pool
     shape.

The phase split is reported so the bottleneck is explicit (host python
decompression today). Pubkey decompression is NOT in the measured path —
the validator pubkey cache decompresses once at startup, exactly like
validator_pubkey_cache.rs.

Fixture batches are expensive to build (tens of seconds at 32k), so they
are cached in .bench_cache/ keyed by (n, seed) and reused across bench
runs.

Env knobs: BENCH_OPPOOL_N (default 32768 on TPU, 256 on the CPU),
BENCH_OPPOOL_COMMITTEES (default 64).
"""

import os
import pickle
import time

TARGET_SIGS_PER_SEC = 150_000.0

_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".bench_cache",
)


def _build_fixture(n_atts: int, n_committees: int, seed: int):
    """(msgs_by_committee, pk_bytes, sig_bytes, committee_of) — valid
    single-validator attestation signatures, sequential-key construction
    (O(n) point adds, like testing.make_signature_set_batch)."""
    os.makedirs(_CACHE_DIR, exist_ok=True)
    path = os.path.join(
        _CACHE_DIR, f"oppool_{n_atts}_{n_committees}_{seed}.pkl"
    )
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)

    from lighthouse_tpu.bls import point_serde
    from lighthouse_tpu.bls.hash_to_curve import hash_to_g2
    from lighthouse_tpu.crypto.ref_curve import G1 as RG1, G2 as RG2

    msgs = [
        bytes([seed & 0xFF, c & 0xFF]) + b"\x00" * 30
        for c in range(n_committees)
    ]
    h_points = [hash_to_g2(m) for m in msgs]

    pk_bytes, sig_bytes = [], []
    committee_of = [i % n_committees for i in range(n_atts)]
    # sk_i = i+1, committee c = i % C, sig_i = (i+1)*H_c. Within a
    # committee consecutive scalars differ by C, so each signature is one
    # point ADD of a precomputed stride point — O(n) total, like
    # testing.make_signature_set_batch's fast_sequential construction.
    stride_points = [
        RG2.mul_scalar(h, n_committees) for h in h_points
    ]
    first_points = [
        RG2.mul_scalar(h_points[c], c + 1) for c in range(n_committees)
    ]
    cur = [None] * n_committees
    running_pk = RG1.infinity
    for i in range(n_atts):
        c = i % n_committees
        running_pk = RG1.add(running_pk, RG1.generator)
        if cur[c] is None:
            cur[c] = first_points[c]
        else:
            cur[c] = RG2.add(cur[c], stride_points[c])
        pk_bytes.append(point_serde.g1_compress(running_pk))
        sig_bytes.append(point_serde.g2_compress(cur[c]))
    fixture = (msgs, pk_bytes, sig_bytes, committee_of)
    with open(path, "wb") as f:
        pickle.dump(fixture, f)
    return fixture


def measure(jax, platform) -> dict:
    import sys

    import numpy as np

    from lighthouse_tpu import bls
    from lighthouse_tpu.bls import tpu_backend
    from lighthouse_tpu.ops import batch_verify, fieldb as fb, fp2
    from lighthouse_tpu.crypto.ref_curve import G2 as RG2

    on_tpu = platform == "tpu"

    # ---- impl selection FIRST (same contract as bench_replay): the
    # verify phase goes through the bls backend dispatch, which knows
    # the xla|pallas program pair plus the MXU env knobs. txla/ptail
    # exist only as standalone bench programs — accepting them would
    # record the plain path under their label (exit-4 rule).
    impl = os.environ.get("BENCH_IMPL")
    if impl is not None:
        from lighthouse_tpu.bench_impl import apply_impl_env

        apply_impl_env(impl, what="oppool32k")
        # ptail is dispatchable now (the fused tail rides the backend's
        # unified dispatch via LIGHTHOUSE_TPU_TAIL); only the
        # bench-only transposed program stays out of reach
        if impl == "txla":
            print(
                f"oppool32k: BENCH_IMPL={impl} has no backend dispatch;"
                " use xla|mxu|pallas|ptail|predc|chain|vredc|mulsqr",
                file=sys.stderr,
            )
            sys.exit(4)
        if on_tpu:
            os.environ["LIGHTHOUSE_TPU_IMPL"] = (
                "xla" if impl in ("xla", "mxu") else "pallas"
            )
        impl_label = impl
    else:
        impl_label = "auto:pallas" if on_tpu else "auto:xla"

    n_committees = int(
        os.environ.get("BENCH_OPPOOL_COMMITTEES", "64" if on_tpu else "8")
    )
    # the CPU run is a path-proof only: compiles dominate at any size.
    # BENCH_NSETS (the bench's generic size knob) maps to the
    # attestation count; BENCH_OPPOOL_N takes precedence when both set.
    default_n = 32_768 if on_tpu else 64
    n_atts = int(
        os.environ.get("BENCH_OPPOOL_N")
        or os.environ.get("BENCH_NSETS")
        or default_n
    )
    chunk = 1024 if on_tpu else 32

    msgs, pk_bytes, sig_bytes, committee_of = _build_fixture(
        n_atts, n_committees, seed=1
    )
    # pubkey cache (startup cost, unmeasured — validator_pubkey_cache.rs)
    pubkeys = [bls.PublicKey.from_bytes(b) for b in pk_bytes]

    t0 = time.perf_counter()
    # -- phase 1: decompression (host, per signature)
    sigs = [bls.Signature.from_bytes(b) for b in sig_bytes]
    t_decompress = time.perf_counter()

    # -- phase 2: device batched subgroup checks
    sub_fn = jax.jit(batch_verify.g2_points_in_subgroup)
    for start in range(0, n_atts, chunk):
        part = sigs[start : start + chunk]
        affs = tpu_backend.batch_to_affine_g2([s.point for s in part])
        pad = chunk - len(part)
        zero = ((0, 0), (0, 0))
        xs = fb.to_mont(fp2.pack([(a or zero)[0] for a in affs]))
        ys = fb.to_mont(fp2.pack([(a or zero)[1] for a in affs]))
        mask = np.array(
            [a is not None for a in affs] + [False] * pad, dtype=bool
        )
        if pad:
            xs = np.concatenate([xs, np.zeros((pad,) + xs.shape[1:],
                                              xs.dtype)])
            ys = np.concatenate([ys, np.zeros((pad,) + ys.shape[1:],
                                              ys.dtype)])
        ok = np.asarray(sub_fn((xs, ys), mask))
        assert bool(ok.all()), "benchmark signatures must be in-subgroup"
        for s in part:  # record the verdict like the host check would
            s._subgroup_ok = True
    t_subgroup = time.perf_counter()

    # -- phase 3: streamed batched RLC verify (messages memoized)
    batches = []
    for start in range(0, n_atts, chunk):
        batches.append(
            [
                bls.SignatureSet(
                    sigs[i], [pubkeys[i]], msgs[committee_of[i]]
                )
                for i in range(start, min(start + chunk, n_atts))
            ]
        )
    verdicts = bls.verify_signature_set_batches(
        batches, backend="tpu", seed=7, consumer="oppool"
    )
    assert all(verdicts), "benchmark batch failed to verify"
    t_verify = time.perf_counter()

    # -- phase 4: per-committee aggregation (naive-pool shape)
    agg = [RG2.infinity] * n_committees
    for i in range(n_atts):
        c = committee_of[i]
        agg[c] = RG2.add(agg[c], sigs[i].point)
    t_aggregate = time.perf_counter()

    total_s = t_aggregate - t0
    sigs_per_sec = n_atts / total_s
    return {
        "metric": "oppool32k_throughput",
        "value": round(sigs_per_sec, 2),
        "unit": "sigs/sec",
        "vs_baseline": round(sigs_per_sec / TARGET_SIGS_PER_SEC, 4),
        "platform": platform,
        "impl": impl_label,
        "n_sets": n_atts,
        "committees": n_committees,
        "phase_s": {
            "decompress": round(t_decompress - t0, 2),
            "subgroup_device": round(t_subgroup - t_decompress, 2),
            "verify": round(t_verify - t_subgroup, 2),
            "aggregate": round(t_aggregate - t_verify, 2),
        },
        "stream_stats": dict(tpu_backend.LAST_STREAM_STATS),
        "valid_for_headline": bool(on_tpu and n_atts >= 32_768),
    }
