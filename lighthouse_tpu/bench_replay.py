"""Benchmark config #5 (BASELINE.md): epoch-transition replay — 32 slots
of blocks re-imported through the full state transition with BULK
signature verification streamed to the device.

Role of the reference's BlockReplayer + signature_verify_chain_segment
(consensus/state_processing/src/block_replayer.rs,
beacon_node/beacon_chain/src/block_verification.rs:509): a node catching
up replays block ranges, batch-verifying every signature in the segment
while the per-block state transition runs on the host. This config
measures that whole loop end to end: Python state transition +
per-block device signature batches, on a minimal-preset chain built by
the in-process harness.

The build phase (producing and signing the 32 blocks with the pure
reference crypto) is NOT in the measured window; only the replay is.
Reported: slots/sec over the replay, plus the verified-signature count.

Env knobs: BENCH_REPLAY_SLOTS (default 32), BENCH_REPLAY_VALIDATORS
(default 64 on TPU, 16 on the CPU).
"""

import os
import time


def measure(jax, platform):
    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.state_processing.per_block import (
        BlockSignatureStrategy,
    )
    from lighthouse_tpu.types.spec import minimal_spec

    on_tpu = platform == "tpu"

    # ---- impl selection FIRST (cheap; a typo must fail before the
    # minutes-long segment build). The harness verifies through the bls
    # backend dispatch, steered by LIGHTHOUSE_TPU_IMPL. With BENCH_IMPL
    # unset the dispatch keeps its own auto-selection (Pallas on real
    # TPU) — pinning xla here would silently regress the default replay
    # measurement several-fold.
    impl = os.environ.get("BENCH_IMPL")
    if impl is not None:
        import sys

        from lighthouse_tpu.bench_impl import apply_impl_env

        apply_impl_env(impl, what="replay32")
        # The harness verifies through the bls backend dispatch, which
        # knows the xla|pallas program pair plus every form knob
        # apply_impl_env just set (ladder/REDC/squaring/tail — all part
        # of _impl_key now, so ptail IS dispatchable). txla (bench-only
        # transposed layout) exists only as a standalone bench program —
        # accepting it would measure the plain path under its label,
        # the exact mislabeling the exit-4 rule exists to prevent.
        if impl == "txla":
            print(
                f"replay32: BENCH_IMPL={impl} has no backend dispatch;"
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

    # BENCH_NSETS (the bench's generic size knob) maps to the slot
    # count; BENCH_REPLAY_SLOTS takes precedence when both are set.
    n_slots = int(
        os.environ.get("BENCH_REPLAY_SLOTS")
        or os.environ.get("BENCH_NSETS")
        or 32
    )
    default_v = 64 if on_tpu else 16
    n_validators = int(
        os.environ.get("BENCH_REPLAY_VALIDATORS") or default_v
    )
    if not on_tpu:
        n_slots = min(n_slots, 8)  # prove the path only

    spec = minimal_spec()

    # ---- build the segment (unmeasured): produce + import n_slots
    # blocks. The builder skips signature verification — it signed the
    # blocks itself one line earlier, and the measured replay verifies
    # every set anyway; re-verifying here through the pure-Python
    # pairing would burn minutes of a chip run's time limit.
    builder = Harness(spec, n_validators, backend="ref")
    blocks = []
    start = builder.state.slot + 1
    for slot in range(start, start + n_slots):
        blocks.append(
            builder.advance_slot_with_block(
                slot, strategy=BlockSignatureStrategy.NO_VERIFICATION
            )
        )

    n_sigs = 0
    for b in blocks:
        # proposal + randao + one set per attestation (+ sync aggregate)
        n_sigs += 2 + len(b.message.body.attestations)
        if getattr(b.message.body, "sync_aggregate", None) is not None:
            n_sigs += 1

    def replay_once():
        replayer = Harness(spec, n_validators, backend="tpu")
        t0 = time.perf_counter()
        for b in blocks:
            replayer.import_block(
                b,
                strategy=BlockSignatureStrategy.VERIFY_BULK,
                consumer="bench",
            )
        return time.perf_counter() - t0

    # first pass compiles every (s_bucket, k_bucket) shape class — the
    # other configs separate compile via _compile_and_time; here the
    # warm-up IS a full unmeasured replay, and the second pass is timed
    warm_s = replay_once()
    wall = replay_once()

    return {
        "metric": "epoch_replay_slots_per_sec",
        "value": round(n_slots / wall, 3),
        "unit": "slots/sec",
        "vs_baseline": 0.0,  # no published reference number for this shape
        "platform": platform,
        "impl": impl_label,
        "n_sets": n_slots,  # the bench's generic size field
        "n_slots": n_slots,
        "n_validators": n_validators,
        "n_signature_sets": n_sigs,
        "wall_s": round(wall, 3),
        "compile_s": round(warm_s, 1),  # warm-up pass incl. compiles
        "valid_for_headline": bool(on_tpu and n_slots >= 32),
    }
