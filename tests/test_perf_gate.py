"""Perf-gate mechanics (tier-1, no timing in any assertion).

The gate's job splits in two: structure checks that must hold on any
machine (stage vocabulary, accounting identity, dispatch shape) and
tolerance-banded timing checks against the committed baseline. These
tests drive both through synthetic bench lines and the CLI round trip
— never through wall-clock measurement, so they cannot flake — and
pin the committed baseline itself to the structure contract."""

import copy
import json
import os
import sys

import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from scripts.perf_gate import (  # noqa: E402
    BASELINE_PATH,
    EXPECTED_STAGES,
    check_structure,
    check_timing,
    main,
)


def _line() -> dict:
    """A structurally healthy synthetic slotpath bench line."""
    return {
        "metric": "slotpath_wall_p50_ms",
        "value": 9.0,
        "unit": "ms",
        "platform": "cpu",
        "impl": "fake",
        "n_sets": 16,
        "stages_p50_ms": {name: 1.0 for name in EXPECTED_STAGES},
        "fusable_gap_p50_ms": 0.0,
        "fusable_gap_multi_dispatch_p50_ms": 4.0,
        "multi_dispatch_imports": 3,
        "serial_dispatches_p50": 1,
        "serial_dispatches_max": 2,
        "accounting_complete": True,
        "valid_for_headline": False,
    }


# ------------------------------------------------------- structure checks


def test_structure_ok():
    assert check_structure(_line()) == []


def test_structure_missing_stage():
    line = _line()
    del line["stages_p50_ms"]["kzg_settle"]
    assert any("kzg_settle" in p for p in check_structure(line))


def test_structure_unexpected_stage():
    line = _line()
    line["stages_p50_ms"]["mystery"] = 1.0
    assert any("mystery" in p for p in check_structure(line))


def test_structure_decode_stage_tolerated():
    # the HTTP publish path adds decode; not an error
    line = _line()
    line["stages_p50_ms"]["decode"] = 0.5
    assert check_structure(line) == []


def test_structure_broken_accounting_fails_despite_good_timing():
    line = _line()
    line["accounting_complete"] = False
    assert any("accounting" in p for p in check_structure(line))


def test_structure_lost_dispatch_ledger():
    line = _line()
    line["serial_dispatches_max"] = 1
    assert any("serial dispatches" in p for p in check_structure(line))


def _fused_line() -> dict:
    """A structurally healthy bench line from a --slot-fuse run: every
    blob import rode ONE chained dispatch."""
    line = _line()
    line.update(
        slot_fuse=True,
        blob_imports=3,
        fused_imports=3,
        multi_dispatch_imports=0,
        serial_dispatches_max=1,
        fusable_gap_multi_dispatch_p50_ms=0.0,
    )
    return line


def test_structure_fused_ok():
    assert check_structure(_fused_line()) == []


def test_structure_fused_extra_dispatch_fails():
    # a blob import paying a second serial round trip means the
    # one-dispatch slot silently fell apart
    line = _fused_line()
    line["serial_dispatches_max"] = 2
    line["multi_dispatch_imports"] = 1
    problems = check_structure(line)
    assert any("serial_dispatches_max != 1" in p for p in problems)
    assert any("multi-dispatch" in p for p in problems)


def test_structure_fused_needs_blob_imports():
    line = _fused_line()
    line["blob_imports"] = 0
    line["fused_imports"] = 0
    assert any(
        "imported no blob block" in p for p in check_structure(line)
    )


def test_structure_fused_counts_every_blob_import():
    line = _fused_line()
    line["fused_imports"] = 2  # one blob import settled serially
    assert any(
        "not every blob import" in p for p in check_structure(line)
    )


def test_committed_baseline_is_fused():
    """The committed baseline records the default import mode — since
    the one-dispatch-slot PR that is --slot-fuse on, single-dispatch
    blob imports."""
    with open(BASELINE_PATH) as f:
        baseline = json.load(f)
    assert baseline["slot_fuse"] is True
    assert baseline["serial_dispatches_max"] == 1
    assert baseline["fusable_gap_multi_dispatch_p50_ms"] == 0.0


# --------------------------------------------------------- timing checks


def test_timing_within_band():
    assert check_timing(_line(), _line()) == []


def test_timing_regression_detected():
    doctored = _line()
    doctored["stages_p50_ms"]["block_processing"] = 50.0  # 50x
    problems = check_timing(doctored, _line())
    assert any("block_processing" in p for p in problems)


def test_timing_wall_regression_detected():
    doctored = _line()
    doctored["value"] = 99.0
    assert any("wall_p50" in p for p in check_timing(doctored, _line()))


def test_timing_abs_floor_forgives_small_stages():
    # a 0.005 -> 0.8 ms jump is 160x relative but under the 2 ms floor:
    # scheduler noise on a sub-ms stage must not trip the gate
    base = _line()
    base["stages_p50_ms"]["structural"] = 0.005
    got = copy.deepcopy(base)
    got["stages_p50_ms"]["structural"] = 0.8
    assert check_timing(got, base) == []


# -------------------------------------------------------- CLI round trip


def test_cli_baseline_round_trip_and_doctored_run(tmp_path, capsys):
    line_path = tmp_path / "line.json"
    baseline_path = tmp_path / "baseline.json"
    line_path.write_text(json.dumps(_line()))

    # --update-baseline from an input line writes the baseline
    rc = main([
        "--input", str(line_path), "--baseline", str(baseline_path),
        "--update-baseline",
    ])
    assert rc == 0
    assert json.loads(baseline_path.read_text())["value"] == 9.0

    # the same line against its own baseline is green
    assert main([
        "--input", str(line_path), "--baseline", str(baseline_path),
    ]) == 0
    assert "OK" in capsys.readouterr().out

    # a doctored run regresses
    doctored = _line()
    doctored["value"] = 99.0
    line_path.write_text(json.dumps(doctored))
    rc = main([
        "--input", str(line_path), "--baseline", str(baseline_path),
    ])
    assert rc == 1
    assert "wall_p50" in capsys.readouterr().out

    # a structure break fails even with identical timings
    broken = _line()
    broken["accounting_complete"] = False
    line_path.write_text(json.dumps(broken))
    assert main([
        "--input", str(line_path), "--baseline", str(baseline_path),
    ]) == 1


def test_cli_update_refuses_broken_structure(tmp_path):
    broken = _line()
    del broken["stages_p50_ms"]["slots"]
    line_path = tmp_path / "line.json"
    line_path.write_text(json.dumps(broken))
    rc = main([
        "--input", str(line_path),
        "--baseline", str(tmp_path / "baseline.json"),
        "--update-baseline",
    ])
    assert rc == 1
    assert not (tmp_path / "baseline.json").exists()


# ------------------------------------------------- the committed baseline


def test_committed_baseline_is_structurally_sound():
    """The baseline the gate ships with must itself satisfy the
    structure contract — a broken committed baseline would wave every
    regression through as 'matching'."""
    with open(BASELINE_PATH) as f:
        baseline = json.load(f)
    assert check_structure(baseline) == []
    assert baseline["metric"] == "slotpath_wall_p50_ms"
    assert baseline["value"] > 0


@pytest.mark.slow
def test_gate_green_end_to_end():
    """The full gate — bench subprocess on the fake backend against the
    committed baseline — runs green (slow: boots a node and imports 16
    blocks in a subprocess)."""
    assert main([]) == 0
