#!/usr/bin/env python3
"""Perf regression gate over the slot-budget decomposition.

Runs `BENCH_CONFIG=slotpath` (the full-import critical path on the
fake-backend CPU proxy) and diffs the line against the committed
baseline `scripts/perf_gate_baseline.json`. Two classes of check, kept
deliberately separate:

  * STRUCTURE (exact, no timing in them — these never flake): the
    expected stage set is present, the accounting identity closed on
    every import, and the dispatch shape matches the import mode —
    with `--slot-fuse` on (the default: the bench line carries
    `slot_fuse: true`) every blob import must ride ONE chained
    dispatch (`serial_dispatches_max == 1`, zero multi-dispatch
    imports, every blob import fused); with the fuse off the blob
    shape must pay its >= 2 serial dispatches. A structure failure
    means the instrument (or the import pipeline) broke, not that the
    machine was slow.
  * TIMING (tolerance-banded): wall p50 and each stage median must
    stay within `1 + rel_tolerance` of the baseline, with an absolute
    floor so sub-millisecond stages can't fail on scheduler noise.
    CPU-proxy medians over 16 imports are stable to ~tens of percent;
    the default band (+100%, 2 ms floor) only trips on structural
    slowdowns (an accidental resync, a lost cache), which is the
    gate's job — kernel-level wins/losses are measured on hardware.

Baseline lifecycle:
  perf_gate.py                      run bench, compare, exit 0/1
  perf_gate.py --input line.json    compare an existing bench line
  perf_gate.py --update-baseline    re-measure and rewrite the baseline

Exit codes: 0 green, 1 regression/structure failure, 2 usage or the
bench itself failed.
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "scripts", "perf_gate_baseline.json")

# stages every healthy import decomposes into on the bench chain (the
# decode stage only appears on the HTTP publish path, so it is not
# required here)
EXPECTED_STAGES = (
    "structural",
    "kzg_settle",
    "slots",
    "block_processing",
    "state_root",
    "store_write",
    "head_update",
)

REL_TOLERANCE = 1.0   # timing may grow to (1 + this) x baseline
ABS_FLOOR_MS = 2.0    # ... or by this many ms, whichever is larger


def run_bench(n_imports: int = 16) -> dict:
    """One slotpath bench line from a subprocess pinned to the CPU
    proxy (the gate must produce the same decomposition on every
    machine)."""
    env = dict(
        os.environ,
        BENCH_CONFIG="slotpath",
        BENCH_NSETS=str(n_imports),
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True,
        timeout=600,
        env=env,
    )
    lines = [
        ln
        for ln in r.stdout.decode(errors="replace").splitlines()
        if ln.startswith("{")
    ]
    if r.returncode != 0 or not lines:
        sys.stderr.write(r.stderr.decode(errors="replace"))
        raise RuntimeError(f"bench failed (rc={r.returncode})")
    return json.loads(lines[-1])


def check_structure(line: dict) -> list:
    """Exact assertions with no timing content — exempt from the
    tolerance band and expected to hold on any machine."""
    out = []
    stages = line.get("stages_p50_ms") or {}
    for name in EXPECTED_STAGES:
        if name not in stages:
            out.append(f"stage {name!r} missing from the decomposition")
    for name in stages:
        if name not in EXPECTED_STAGES and name != "decode":
            out.append(f"unexpected stage {name!r} in the decomposition")
    if not line.get("accounting_complete"):
        out.append(
            "accounting identity broken: union + unattributed != wall "
            "on at least one import"
        )
    if line.get("slot_fuse"):
        # one-dispatch slot: the settle rides the signature fold's
        # dispatch, so NO import may pay a second serial round trip
        blob_imports = line.get("blob_imports") or 0
        if blob_imports < 1:
            out.append(
                "fused run imported no blob block — nothing "
                "exercised the chained settle"
            )
        if (line.get("serial_dispatches_max") or 0) != 1:
            out.append(
                "fused run: serial_dispatches_max != 1 — a blob "
                "import paid a separate settle round trip (or the "
                "dispatch ledger lost the fused dispatch)"
            )
        if (line.get("multi_dispatch_imports") or 0) != 0:
            out.append(
                "fused run still has multi-dispatch imports — the "
                "one-dispatch slot did not engage"
            )
        if (line.get("fused_imports") or 0) != blob_imports:
            out.append(
                "not every blob import rode a fused dispatch "
                f"({line.get('fused_imports')} fused vs "
                f"{blob_imports} blob imports)"
            )
    else:
        if (line.get("serial_dispatches_max") or 0) < 2:
            out.append(
                "no import paid >= 2 serial dispatches — the blob "
                "settle round trip went missing from the dispatch "
                "ledger"
            )
        if (line.get("multi_dispatch_imports") or 0) < 1:
            out.append("no multi-dispatch import in the run")
    if (line.get("serial_dispatches_p50") or 0) < 1:
        out.append("median import paid no device dispatch at all")
    return out


def check_timing(line: dict, baseline: dict,
                 rel=REL_TOLERANCE, abs_floor_ms=ABS_FLOOR_MS) -> list:
    """Tolerance-banded comparisons of the CPU-proxy medians."""
    out = []

    def band(name, got, base):
        if base is None or got is None:
            return
        limit = max(base * (1.0 + rel), base + abs_floor_ms)
        if got > limit:
            out.append(
                f"{name}: {got:.3f} ms exceeds the gate "
                f"({base:.3f} ms baseline, limit {limit:.3f} ms)"
            )

    band("wall_p50", line.get("value"), baseline.get("value"))
    base_stages = baseline.get("stages_p50_ms") or {}
    for name, got in (line.get("stages_p50_ms") or {}).items():
        band(f"stage {name}", got, base_stages.get(name))
    band(
        "fusable_gap_multi_dispatch_p50",
        line.get("fusable_gap_multi_dispatch_p50_ms"),
        baseline.get("fusable_gap_multi_dispatch_p50_ms"),
    )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--input", help="compare an existing bench JSON line")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--n-imports", type=int, default=16)
    ap.add_argument("--rel-tolerance", type=float, default=REL_TOLERANCE)
    args = ap.parse_args(argv)

    if args.input:
        with open(args.input) as f:
            line = json.load(f)
    else:
        try:
            line = run_bench(args.n_imports)
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            print(f"perf_gate: {e}")
            return 2

    problems = check_structure(line)
    if args.update_baseline:
        if problems:
            for p in problems:
                print(f"perf_gate: STRUCTURE {p}")
            print("perf_gate: refusing to commit a broken baseline")
            return 1
        with open(args.baseline, "w") as f:
            json.dump(line, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"perf_gate: baseline updated ({line['value']} ms wall p50)")
        return 0

    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"perf_gate: cannot read baseline {args.baseline}: {e}")
        return 2
    problems += check_timing(line, baseline, rel=args.rel_tolerance)
    for p in problems:
        print(f"perf_gate: FAIL {p}")
    if problems:
        return 1
    print(
        f"perf_gate: OK wall p50 {line['value']} ms "
        f"(baseline {baseline['value']} ms, "
        f"+{int(args.rel_tolerance * 100)}% band), "
        f"{len(line.get('stages_p50_ms') or {})} stages, "
        f"accounting complete"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
