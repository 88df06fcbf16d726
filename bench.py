"""Headline benchmark: BLS signature verification throughput on one chip.

Config #1 from BASELINE.json: `verify_signature_sets` over 1024 independent
single-key signature sets (the gossip-attestation shape — the >=30k sigs/slot
hot path of the reference client, crypto/bls/src/impls/blst.rs:36-119).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline is measured against the north-star target rate of 150k sigs/sec
(30k signatures in <200 ms on one chip, BASELINE.json/BASELINE.md) — 1.0
means the target is met.

One process, no fallback: the line names the device it ran on
(`platform`, `device_kind`, `device_count`). Without a TPU the run exits
3, unless the caller asked for the CPU with JAX_PLATFORMS=cpu (the perf
gate does); a failed measurement exits non-zero.

Env knobs:
  BENCH_IMPL=xla|txla|mxu|pallas|ptail|predc   kernel path (default xla)
  BENCH_IMPL=chain|vredc|mulsqr   legacy-form A/B partners of the
      defaults (double-add ladders / VPU REDC / generic-mul squaring);
      pw2 and predcbf are RETIRED labels (now the defaults) and exit(4)
  BENCH_NSETS=N             batch size override
  BENCH_SMOKE=1             small batch
  BENCH_CONFIG=oppool32k|sync512|block|replay32   BASELINE configs #4/#2/#3/#5
  BENCH_CONFIG=kzg|kzgfold  KZG producer MSM / verify fold-factor configs
  BENCH_CONFIG=ladder       unified window-kernel vs legacy-ladder A/B
                            at 64-bit and 255-bit scalar widths
  BENCH_CONFIG=serve        mixed REST+gossip+RPC load against a live
                            node: per-class p50/p99, hot-read cache,
                            shed counts (BENCH_SERVE_SHED=0 = A/B off)
  BENCH_CONFIG=lcserve      light-client read flood against one live
                            node: per-class p50/p99, TTL cache-miss <=
                            window assertion, streamed-bytes totals
  BENCH_CONFIG=lcproof      batched device Merkle-proof kernel at
                            BENCH_NSETS queries (byte-identical fold)
  BENCH_CONFIG=das          DA sampling plane: Reed-Solomon blob
                            extension + batched cell-multiproof fold
                            over the guarded device plane at
                            BENCH_NSETS blobs, byte-identical to the
                            host oracle (corrupt batch must reject)
  BENCH_CONFIG=slotpath     per-import critical-path decomposition
                            from the slot-budget recorder over
                            BENCH_NSETS imports: stage medians, wall
                            p50/p99 vs the 200 ms budget, serial
                            dispatches, fusable gap (perf_gate.py
                            diffs this against its committed baseline)
  BENCH_CONFIG=slotfuse     one-dispatch-slot A/B: the same blob
                            import schedule with --slot-fuse off vs
                            on — wall p50/p99 per arm, dispatches per
                            import, and canonical verdict
                            byte-identity between the two arms
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lighthouse_tpu.backend import enable_compile_cache  # noqa: E402

enable_compile_cache()

TARGET_SIGS_PER_SEC = 150_000.0  # north star: 30k sigs in 200 ms on one chip


def main():
    from lighthouse_tpu.bench_impl import validate_impl

    validate_impl(os.environ.get("BENCH_IMPL", "xla"))
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(
            f"bench: no TPU (JAX found {platform!r}); set JAX_PLATFORMS=cpu"
            " to measure the CPU",
            file=sys.stderr,
        )
        sys.exit(3)
    out = _measure(jax, platform)
    out["device_kind"] = devices[0].device_kind
    out["device_count"] = len(devices)
    print(json.dumps(out))


def _measure(jax, platform):
    config = os.environ.get("BENCH_CONFIG", "sigsets")
    if config == "oppool32k":
        try:
            from lighthouse_tpu import bench_oppool
        except ImportError as e:
            print(f"bench: oppool32k config unavailable: {e}", file=sys.stderr)
            sys.exit(4)
        return bench_oppool.measure(jax, platform)
    if config == "sync512":
        return _measure_sync512(jax, platform)
    if config == "block":
        return _measure_block(jax, platform)
    if config == "replay32":
        from lighthouse_tpu import bench_replay

        return bench_replay.measure(jax, platform)
    if config == "grouped64":
        return _measure_grouped(jax, platform)
    if config == "kzg":
        return _measure_kzg_msm(jax, platform)
    if config == "kzgfold":
        return _measure_kzg_fold(jax, platform)
    if config == "ladder":
        return _measure_ladder(jax, platform)
    if config == "serve":
        # the serving-plane load harness never needs the accelerator:
        # it measures the HTTP/gossip/RPC edges on the fake backend
        from lighthouse_tpu import bench_serve

        return bench_serve.measure(jax, platform)
    if config == "busmix":
        # mixed-consumer replay through the verification bus vs direct
        # dispatch — the real-hardware amortization A/B
        from lighthouse_tpu import bench_busmix

        return bench_busmix.measure(jax, platform)
    if config == "slotpath":
        # full-import critical-path decomposition from the slot-budget
        # recorder (fake-backend CPU proxy off hardware; perf_gate.py
        # diffs the line against its committed baseline)
        from lighthouse_tpu import bench_slotpath

        return bench_slotpath.measure(jax, platform)
    if config == "slotfuse":
        # one-dispatch-slot A/B: serial vs chained slot-program over
        # the same deterministic blob schedule, with verdict
        # byte-identity asserted between the arms
        from lighthouse_tpu import bench_slotfuse

        return bench_slotfuse.measure(jax, platform)
    if config == "das":
        # DA sampling plane: device RS extension + cell-multiproof
        # fold, host-oracle-checked every iteration
        from lighthouse_tpu import bench_das

        return bench_das.measure(jax, platform)
    if config == "lcserve":
        # light-client read flood against one live node (serving edge
        # on the fake backend; never a hardware headline)
        from lighthouse_tpu import bench_lcserve

        return bench_lcserve.measure(jax, platform)
    if config == "lcproof":
        # batched device Merkle-proof kernel at BENCH_NSETS queries,
        # byte-identical to the host oracle every iteration
        from lighthouse_tpu import bench_lcserve

        return bench_lcserve.measure_proofs(jax, platform)
    return _measure_sigsets(jax, platform)


def _resolve_impl_fn(jax, platform, grouped: bool = False):
    """Validate BENCH_IMPL, apply its env side effects, and return
    (impl, jitted verify fn) — shared by every config so an impl added
    in one place cannot be mislabeled in another. Exits 4 on unknown
    impls (a typo must not measure the xla path under its label) and on
    impls the requested program family does not have (the grouped check
    has no transposed-XLA or in-kernel-tail program)."""
    import functools

    from lighthouse_tpu.bench_impl import apply_impl_env
    from lighthouse_tpu.ops import batch_verify

    impl = os.environ.get("BENCH_IMPL", "xla")
    apply_impl_env(impl)
    if grouped and impl == "txla":
        print(
            "bench: grouped64 has no txla program; use "
            "xla|mxu|pallas|ptail|predc|chain|vredc|mulsqr",
            file=sys.stderr,
        )
        sys.exit(4)
    if impl in ("pallas", "ptail", "predc", "chain", "vredc", "mulsqr"):
        # the legacy-form A/B labels (chain/vredc/mulsqr) measure the
        # default program family — pallas on hardware — with ONE form
        # flipped back by the env knob apply_impl_env just set
        fn = jax.jit(
            functools.partial(
                batch_verify.verify_signature_sets_grouped_pallas
                if grouped
                else batch_verify.verify_signature_sets_pallas,
                # the TPU kernel cannot lower on the CPU — run the
                # kernel body in interpret mode there
                interpret=(platform == "cpu"),
                tail=impl == "ptail",
            )
        )
    elif impl == "txla":
        # fully-transposed batch-on-lanes pipeline, no Pallas
        fn = jax.jit(batch_verify.verify_signature_sets_t)
    else:
        # xla | mxu (mxu = the xla program with the MXU_CONV env knob
        # apply_impl_env just set, honored by both program families)
        fn = jax.jit(
            batch_verify.verify_signature_sets_grouped
            if grouped
            else batch_verify.verify_signature_sets
        )
    return impl, fn


def _compile_and_time(jax, fn, args, reps, what):
    """Compile+warm (asserting the batch verifies), then return
    (p50 seconds, compile seconds)."""
    import numpy as np

    t0 = time.perf_counter()
    ok = bool(np.asarray(fn(*args)))
    compile_s = time.perf_counter() - t0
    assert ok, f"{what}: benchmark batch failed to verify"
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2], compile_s


def _measure_sync512(jax, platform):
    """BASELINE config #2: 512-key aggregate verification (the
    sync-committee fast_aggregate_verify shape) — exercises the per-set
    G1 MSM fold the single-key headline config does not. BENCH_NSETS
    overrides the aggregate count; the 512-key width is the config."""
    from lighthouse_tpu import testing as td

    if platform == "cpu":
        n_sets, n_keys, reps = 2, 8, 3  # prove the path only
    else:
        n_sets = int(os.environ.get("BENCH_NSETS") or 64)
        n_keys, reps = 512, 5

    args = jax.device_put(
        td.make_aggregate_set_batch(n_sets, n_keys, seed=0)
    )
    impl, fn = _resolve_impl_fn(jax, platform)
    p50, compile_s = _compile_and_time(jax, fn, args, reps, "sync512")
    on_tpu = platform == "tpu"
    return {
        "metric": "fast_aggregate_verify_throughput",
        "value": round(n_sets / p50, 2),
        "unit": "aggregates/sec",
        "vs_baseline": 0.0,  # no published reference number for this shape
        "platform": platform,
        "impl": impl,
        "n_sets": n_sets,
        "n_keys": n_keys,
        "p50_s": round(p50, 4),
        "compile_s": round(compile_s, 1),
        "valid_for_headline": bool(on_tpu and n_keys >= 512),
    }


def _measure_block(jax, platform):
    """BASELINE config #3: one full mainnet-ish block's signature sets
    (proposal + randao + 128 committee-aggregate attestations + exits)
    verified in one batch — the BlockSignatureVerifier
    (block_signature_verifier.rs:120-131) shape."""
    from lighthouse_tpu import testing as td

    if platform == "cpu":
        n_att, committee, reps = 4, 8, 3  # prove the path only
    else:
        # BENCH_NSETS = total sets; 4 are the proposal/randao/exit singles
        n_sets_env = os.environ.get("BENCH_NSETS")
        if n_sets_env and int(n_sets_env) < 5:
            print(
                f"bench: block config needs BENCH_NSETS >= 5, got "
                f"{n_sets_env}", file=sys.stderr,
            )
            sys.exit(4)
        n_att = (int(n_sets_env) - 4) if n_sets_env else 128
        committee, reps = 256, 5

    args = jax.device_put(
        td.make_block_sets_batch(
            seed=0, n_attestations=n_att, committee_size=committee
        )
    )
    impl, fn = _resolve_impl_fn(jax, platform)
    p50, compile_s = _compile_and_time(jax, fn, args, reps, "block")
    on_tpu = platform == "tpu"
    return {
        "metric": "block_signature_verify_throughput",
        "value": round(1.0 / p50, 2),
        "unit": "blocks/sec",
        "vs_baseline": 0.0,  # no published reference number for this shape
        "platform": platform,
        "impl": impl,
        "n_sets": n_att + 4,
        "n_attestations": n_att,
        "committee_size": committee,
        "p50_s": round(p50, 4),
        "compile_s": round(compile_s, 1),
        "valid_for_headline": bool(on_tpu and n_att >= 128),
    }


def _measure_grouped(jax, platform):
    """The committee-shaped full-slot load: S sets over G distinct
    messages, verified with the message-grouped pairing merge (G+1
    Miller loops instead of S+1 — ops.batch_verify.grouped_miller_inputs
    docstring). This is the honest shape of the 30k-sig mainnet slot:
    ~64 committees per slot, so the north-star 150k sigs/s applies to
    THIS config; the plain sigsets config keeps measuring the
    distinct-message general case.

    BENCH_NSETS = total sets (default 30720), BENCH_GROUPS = distinct
    messages (default 64)."""
    from lighthouse_tpu import testing as td

    on_tpu = platform == "tpu"
    if platform == "cpu":
        n_sets, n_groups, reps = 32, 4, 3  # prove the path only
    else:
        n_sets = int(os.environ.get("BENCH_NSETS") or 30720)
        n_groups = int(os.environ.get("BENCH_GROUPS") or 64)
        reps = 5
    if n_sets < n_groups:
        print(
            f"bench: grouped64 needs BENCH_NSETS >= BENCH_GROUPS "
            f"({n_sets} < {n_groups})",
            file=sys.stderr,
        )
        sys.exit(4)
    sets_per_group = n_sets // n_groups
    n_sets = n_groups * sets_per_group

    grouped, _ = td.make_grouped_signature_set_batch(
        n_groups, sets_per_group, max_keys=1, seed=0,
        fast_sequential=True, build_flat=False,
    )
    args = jax.device_put(grouped)

    impl, fn = _resolve_impl_fn(jax, platform, grouped=True)
    p50, compile_s = _compile_and_time(jax, fn, args, reps, "grouped64")
    sigs_per_sec = n_sets / p50
    return {
        "metric": "grouped_verify_throughput",
        "value": round(sigs_per_sec, 2),
        "unit": "sigs/sec",
        "vs_baseline": round(sigs_per_sec / TARGET_SIGS_PER_SEC, 4),
        "platform": platform,
        "impl": impl,
        "n_sets": n_sets,
        "n_groups": n_groups,
        "p50_s": round(p50, 4),
        "compile_s": round(compile_s, 1),
        # >= on BOTH work knobs: fewer groups than the mainnet 64 would
        # mean fewer Miller loops and an inflated number
        "valid_for_headline": bool(
            on_tpu and n_sets >= 30720 and n_groups >= 64
        ),
    }


def _measure_kzg_msm(jax, platform):
    """KZG producer-path commit MSM: blob -> commitment on the
    fixed-base windowed device graph (ops/msm.py) at blob size
    BENCH_NSETS field elements (default 4096, the mainnet shape; the
    minimal preset uses 4). Warm-up pays the
    one-time setup/table build and compile; timed reps measure the
    steady-state dispatch the block producer sees (one MSM per blob
    plus one per proof)."""
    from lighthouse_tpu import kzg

    if platform == "cpu":
        n, reps = 8, 3  # prove the path only
    else:
        n = int(os.environ.get("BENCH_NSETS") or 4096)
        reps = 5
    setup = kzg.dev_setup(n)
    blob = b"".join(
        ((i * 2654435761 + 11) % (2**200)).to_bytes(32, "big")
        for i in range(n)
    )
    t0 = time.perf_counter()
    first = kzg.blob_to_kzg_commitment(blob, setup, backend="tpu", consumer="bench")
    compile_s = time.perf_counter() - t0
    assert first == kzg.blob_to_kzg_commitment(blob, setup, consumer="bench"), (
        "kzg: device commitment disagrees with the host oracle"
    )
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        kzg.blob_to_kzg_commitment(blob, setup, backend="tpu", consumer="bench")
        times.append(time.perf_counter() - t0)
    p50 = sorted(times)[len(times) // 2]
    on_tpu = platform == "tpu"
    return {
        "metric": "kzg_commit_msm_throughput",
        "value": round(n / p50, 2),
        "unit": "points/sec",
        "vs_baseline": 0.0,  # no published reference number for this shape
        "platform": platform,
        "impl": "msm_fixed_base",
        "n_sets": n,
        "p50_s": round(p50, 4),
        "compile_s": round(compile_s, 1),
        "valid_for_headline": bool(on_tpu and n >= 4096),
    }


def _measure_kzg_fold(jax, platform):
    """ops/kzg_verify fold factor on device (the ROADMAP's pending
    hardware numbers): N sidecar proof checks folded into ONE two-pair
    multi-pairing vs N independent N=1 batch checks, both on the tpu
    backend. BENCH_NSETS = N (default 8; PERF_NOTES has the
    ref-backend curve: 0.89x/2.69x/5.10x at N=1/4/8)."""
    from lighthouse_tpu import kzg

    if platform == "cpu":
        n, blob_n, reps = 2, 4, 2  # prove the path only
    else:
        n = int(os.environ.get("BENCH_NSETS") or 8)
        blob_n, reps = 4, 5
    setup = kzg.dev_setup(blob_n)
    blobs, comms, proofs = [], [], []
    for k in range(n):
        blob = b"".join(
            ((k * 997 + i * 31 + 1) % (2**128)).to_bytes(32, "big")
            for i in range(blob_n)
        )
        comm = kzg.blob_to_kzg_commitment(blob, setup, consumer="bench")
        blobs.append(blob)
        comms.append(comm)
        proofs.append(kzg.compute_blob_kzg_proof(blob, comm, setup, consumer="bench"))

    def batch_once():
        assert kzg.verify_blob_kzg_proof_batch(
            blobs, comms, proofs, backend="tpu", setup=setup, seed=7,
            consumer="bench"
        )

    def singles_once():
        for b, c, p in zip(blobs, comms, proofs):
            assert kzg.verify_blob_kzg_proof_batch(
                [b], [c], [p], backend="tpu", setup=setup, seed=7,
                consumer="bench"
            )

    t0 = time.perf_counter()
    batch_once()
    singles_once()
    compile_s = time.perf_counter() - t0
    batch_t, singles_t = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        batch_once()
        batch_t.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        singles_once()
        singles_t.append(time.perf_counter() - t0)
    batch_p50 = sorted(batch_t)[len(batch_t) // 2]
    singles_p50 = sorted(singles_t)[len(singles_t) // 2]
    on_tpu = platform == "tpu"
    return {
        "metric": "kzg_batch_fold_factor",
        "value": round(singles_p50 / batch_p50, 3),
        "unit": "x",
        "vs_baseline": 0.0,
        "platform": platform,
        "impl": "kzg_rlc_fold",
        "n_sets": n,
        "p50_s": round(batch_p50, 4),
        "singles_p50_s": round(singles_p50, 4),
        "compile_s": round(compile_s, 1),
        "valid_for_headline": bool(on_tpu and n >= 8),
    }


def _measure_ladder(jax, platform):
    """Unified windowed-ladder vs legacy double-add chain A/B at the
    two production scalar widths: 64-bit (the RLC width, at the
    grouped64-shaped lane count — on the grouped shape the ladders ARE
    the cost floor) and 255-bit (the KZG lane width, at the flat-4096
    shape). Reports the throughput ratio unified/legacy per width;
    `value` is the MIN of the two (>= 1.0 = the unified kernel
    dominates at both widths). Point equality of the two kernels is
    asserted at warm-up on every run."""
    import functools  # noqa: F401  (parity with the other configs)
    import random as _random

    import numpy as np

    from lighthouse_tpu.ops import curve
    from lighthouse_tpu.ops import window_ladder as wl

    if platform == "cpu":
        # CPU-XLA A/B path-proof shapes; the chip uses the full lane
        # counts.
        # 256 lanes is the smallest width where per-op dispatch
        # overhead stops swamping the op-count cut (at 64 lanes the
        # two kernels measure ~equal on XLA:CPU; 2026-08-04 diag)
        shapes = ((64, 256, "grouped64"), (255, 256, "flat4096"))
        reps = 3
    else:
        n64 = int(os.environ.get("BENCH_NSETS") or 30720)
        shapes = ((64, n64, "grouped64"), (255, 4096, "flat4096"))
        reps = 5

    rnd = _random.Random(11)
    eq_fn = jax.jit(curve.PG1.eq)
    fields = {}
    ratios = []
    for width, lanes, shape_name in shapes:
        scalars = [rnd.getrandbits(width) for _ in range(lanes)]
        bits = jax.device_put(
            jax.numpy.asarray(curve.scalars_to_bits(scalars, width))
        )
        pt = curve.PG1.generator_like((lanes,))
        fn_w = wl.jitted_ladder("G1", impl="window")
        fn_c = wl.jitted_ladder("G1", impl="chain")
        out_w = jax.block_until_ready(fn_w(pt, bits))
        out_c = jax.block_until_ready(fn_c(pt, bits))
        assert bool(np.asarray(eq_fn(out_w, out_c)).all()), (
            f"ladder: unified kernel disagrees with the chain at "
            f"{width}-bit"
        )
        p50 = {}
        for label, fn in (("window", fn_w), ("chain", fn_c)):
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(pt, bits))
                times.append(time.perf_counter() - t0)
            p50[label] = sorted(times)[len(times) // 2]
        ratio = p50["chain"] / p50["window"]
        ratios.append(ratio)
        fields[f"ratio_w{width}"] = round(ratio, 3)
        fields[f"p50_window_w{width}_s"] = round(p50["window"], 4)
        fields[f"p50_chain_w{width}_s"] = round(p50["chain"], 4)
        fields[f"lanes_w{width}"] = lanes

    on_tpu = platform == "tpu"
    return {
        "metric": "ladder_unified_speedup",
        "value": round(min(ratios), 3),
        "unit": "x",
        "vs_baseline": 0.0,
        "platform": platform,
        "impl": "window_vs_chain",
        "n_sets": shapes[0][1],
        **fields,
        "valid_for_headline": bool(on_tpu and shapes[0][1] >= 30720),
    }


def _measure_sigsets(jax, platform):
    from lighthouse_tpu import testing as td

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if os.environ.get("BENCH_NSETS"):
        n_sets, reps = int(os.environ["BENCH_NSETS"]), 5
    elif platform == "cpu":
        n_sets, reps = 16, 3  # CPU: just prove the path end to end
    elif smoke:
        n_sets, reps = 128, 3
    else:
        n_sets, reps = 1024, 5

    args = td.make_signature_set_batch(
        n_sets, max_keys=1, seed=0, fast_sequential=True
    )
    args = jax.device_put(args)

    impl, fn = _resolve_impl_fn(jax, platform)
    p50, compile_s = _compile_and_time(jax, fn, args, reps, "sigsets")
    sigs_per_sec = n_sets / p50
    on_tpu = platform == "tpu"
    out = {
        "metric": "verify_signature_sets_throughput",
        "value": round(sigs_per_sec, 2),
        "unit": "sigs/sec",
        "vs_baseline": round(sigs_per_sec / TARGET_SIGS_PER_SEC, 4),
        "platform": platform,
        "impl": impl,
        "n_sets": n_sets,
        "p50_s": round(p50, 4),
        "compile_s": round(compile_s, 1),
        "valid_for_headline": bool(on_tpu and n_sets >= 1024),
    }
    return out


if __name__ == "__main__":
    main()
