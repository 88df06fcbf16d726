"""Whole tiny runs of the Electra block cell on the CPU: the sound run
comes out correct, the control reads exactly its three pair blocks, and
a program whose key lanes are not folded per set comes out not correct.

At the tiny size a slot has 512 attesters, so the lane width is cut to
32 keys: the aggregates of 156-486 keys then span several lanes each, as
those of 14,063-29,688 keys span 512-key lanes at 1M validators, and the
invalid blocks sit where the mix places them.
"""

import time

import pytest

from benchmark import control, harness
from benchmark.traffic import electra_blocks

SEED = 2147483659 * 5
CELL = "mainnet-1m.electra-block-import"
LANE_WIDTH = 32


def tiny():
    spec = harness.load_spec()
    config, traffic = CELL.split(".", 1)
    cfg = dict(harness.load_config(config), validators=2048,
               SLOTS_PER_EPOCH=4, MAX_COMMITTEES_PER_SLOT=4,
               SYNC_COMMITTEE_SIZE=16)
    mix = dict(harness.load_mix(traffic), blocks=6)
    cell = {"name": CELL, "config": config, "traffic": traffic, "chips": 1}
    return spec, cell, cfg, mix, 90


@pytest.fixture(autouse=True)
def lane_width(monkeypatch):
    from lighthouse_tpu.bls import tpu_backend

    monkeypatch.setattr(tpu_backend, "KEY_LANE_WIDTH", LANE_WIDTH)


def run(seed=SEED):
    import jax

    spec, cell, cfg, mix, seconds = tiny()
    return harness.run_cell(
        spec, cell, cfg, mix, seed, seconds, 0, time.perf_counter(),
        jax.devices(), pool_processes=2, log=lambda msg: None,
    )


def test_tiny_blocks_span_lanes():
    _, _, cfg, mix, _ = tiny()
    for _, sizes in electra_blocks.warm_batches(cfg, mix):
        assert len(sizes) == 11 and sum(sizes[2:10]) == 2 * 512
        assert max(sizes) > LANE_WIDTH


def test_sound_run_is_correct():
    r = run()
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] == 6 and r["failed"] == 0
    slots = r["info"]["pubkey_slots"]
    assert slots["padding"] > 0 and slots["table"] > 0


def test_control_unit_scalars_is_not_correct(monkeypatch):
    from lighthouse_tpu.bls import tpu_backend

    monkeypatch.setattr(tpu_backend, "_rlc_scalars", control.unit_scalars)
    r = run()
    assert r["correct"] is False
    assert r["checks"]["wrong_verdicts"]["value"] == 3


def test_fault_lane_fold_dropped_is_not_correct(monkeypatch):
    """Each set takes its last lane's sum alone: the keys of its other
    lanes never reach the pairing. Planted from set-up on, the fault
    refuses the valid warm-up blocks and the run gives no result;
    planted after the warm-up, the window's blocks run it and the
    reference check refuses the run."""
    import jax.numpy as jnp

    from lighthouse_tpu.bls import tpu_backend
    from lighthouse_tpu.ops import batch_verify, curve

    def last_lane_only(lane_aggs, lane_set, last_lane):
        picked = tuple(
            jnp.take(c, jnp.maximum(last_lane, 0), axis=0) for c in lane_aggs
        )
        ident = curve.PG1.identity_like(picked)
        return curve.PG1.select(last_lane >= 0, picked, ident)

    def plant():
        monkeypatch.setattr(batch_verify, "fold_lanes", last_lane_only)
        # trace the programs afresh, with the fault in them
        for name in ("_jitted", "_jitted_indexed"):
            monkeypatch.setattr(tpu_backend, name, {})

    sound_warm = harness.warm_device

    def warm_then_plant(node, warm, log=None):
        sound_warm(node, warm, log)
        plant()

    monkeypatch.setattr(harness, "warm_device", warm_then_plant)
    r = run()
    assert r["correct"] is False
    assert r["checks"]["wrong_verdicts"]["value"] >= 1

    monkeypatch.setattr(harness, "warm_device", sound_warm)
    plant()
    with pytest.raises(harness.Fail, match="refused"):
        run()

