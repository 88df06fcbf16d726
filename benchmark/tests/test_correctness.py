"""The comparison that decides `correct` fails when it should: a sound run
at a tiny size on the CPU comes out correct, and the control and each
planted fault come out not correct. Each test drives a whole run, set-up,
window and reference, past the harness's look for a chip.

The faults a verification cell can have: an answer altered where it is
produced (the device batch verdict forced to "valid"), half of the batch
left out (the first half, the second half, the sets at even places or at
odd places never verified), and the G2 subgroup check skipped. A step
that returns its state unchanged and an exchange between chips left out
have no counterpart here: a verdict carries no state forward, and the
cell runs on one chip.
"""

import time

import numpy as np
import pytest

from benchmark import control, harness, signing

SEED = 2147483659 * 3
CELL = "gnosis-300k.block-import"

# the tiny block: proposer, randao, 8 aggregates, sync = 11 sets, and the
# bus's sentinel makes 12: a pair in the first half at odd places, one in
# the second half at even places, one across both, and two torsion blocks
TINY_INVALID = [
    {"block": 0, "kind": "pair", "sets": [1, 3]},
    {"block": 1, "kind": "torsion", "sets": [10]},
    {"block": 2, "kind": "pair", "sets": [6, 8]},
    {"block": 3, "kind": "pair", "sets": [2, 9]},
    {"block": 4, "kind": "torsion", "sets": [5]},
]


def tiny():
    """The cell at a size the CPU can run: the same generator and wiring,
    small committees, few blocks."""
    spec = harness.load_spec()
    config, traffic = CELL.split(".", 1)
    cfg = dict(harness.load_config(config), validators=2048,
               SLOTS_PER_EPOCH=4, MAX_COMMITTEES_PER_SLOT=4,
               MAX_ATTESTATIONS=8, SYNC_COMMITTEE_SIZE=16)
    mix = dict(harness.load_mix(traffic), blocks=6, invalid=TINY_INVALID)
    cell = {"name": CELL, "config": config, "traffic": traffic, "chips": 1}
    return spec, cell, cfg, mix, 60


def run(seed=SEED):
    import jax

    spec, cell, cfg, mix, seconds = tiny()
    return harness.run_cell(
        spec, cell, cfg, mix, seed, seconds, 0, time.perf_counter(),
        jax.devices(), pool_processes=2, log=lambda msg: None,
    )


def test_sound_run_is_correct():
    r = run()
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] == 6 and r["failed"] == 0


def test_control_unit_scalars_is_not_correct(monkeypatch):
    from lighthouse_tpu.bls import tpu_backend

    monkeypatch.setattr(tpu_backend, "_rlc_scalars", control.unit_scalars)
    r = run()
    assert r["correct"] is False
    # the three pairs are accepted; the torsion blocks still fail the
    # subgroup check
    assert r["checks"]["wrong_verdicts"]["value"] == 3


def test_fault_answer_altered_is_not_correct(monkeypatch):
    from lighthouse_tpu.bls import tpu_backend

    monkeypatch.setattr(
        tpu_backend, "verify_signature_sets_tpu",
        lambda sets, seed=None, consumer=None: True,
    )
    r = run()
    assert r["correct"] is False


@pytest.mark.parametrize("half", ["first", "second", "even", "odd"])
def test_fault_half_left_out_is_not_correct(half, monkeypatch):
    from lighthouse_tpu.bls import tpu_backend

    batch = tpu_backend.verify_signature_sets_tpu
    keep = {
        "first": lambda s: s[:len(s) // 2],
        "second": lambda s: s[len(s) // 2:],
        "even": lambda s: s[::2],
        "odd": lambda s: s[1::2],
    }[half]

    def half_batch(sets, seed=None, consumer=None):
        return batch(keep(sets), seed=seed, consumer=consumer)

    monkeypatch.setattr(tpu_backend, "verify_signature_sets_tpu", half_batch)
    r = run()
    assert r["correct"] is False


def test_fault_subgroup_check_skipped_is_not_correct(monkeypatch):
    """Skipped, the check lets a torsion signature into the batch, where
    its point of order 13 vanishes from the random linear combination
    exactly when the set's scalar is a multiple of 13: one draw in 13.
    The test takes that draw: the program's scalars come from its own
    seeded generator, at the first seed that gives the first torsion
    set (place 10 of the block) a multiple of 13. With the check in
    place the same draws refuse every torsion block."""
    from lighthouse_tpu.bls import api, tpu_backend

    draw = tpu_backend._rlc_scalars
    place = TINY_INVALID[1]["sets"][0]
    seeds = {}

    def seeded(n, seed):
        if n <= place:  # the canary pair's own bucket
            return draw(n, seed)
        if n not in seeds:
            seeds[n] = next(
                s for s in range(10_000)
                if draw(n, s)[place] % signing.TORSION_ORDER == 0
            )
        return draw(n, seeds[n])

    monkeypatch.setattr(tpu_backend, "_rlc_scalars", seeded)
    assert run()["correct"] is True
    monkeypatch.setattr(api.Signature, "in_subgroup", lambda self: True)
    r = run()
    assert r["correct"] is False
    assert r["checks"]["wrong_verdicts"]["value"] >= 1


def test_torsion_point_has_order_13_off_g2():
    from benchmark.crypto.curve import G2

    t = signing.torsion_point()
    assert not G2.is_infinity(t)
    assert G2.is_infinity(G2.mul_scalar(t, signing.TORSION_ORDER))
    assert not G2.in_subgroup(t)
    assert np.all([G2.is_on_curve(G2.mul_scalar(t, k)) for k in (1, 5)])
