"""MSM subsystem: the host Pippenger oracle against the retired naive
ladder (tier-1, fast) and the device MSM graphs against the host oracle
on the committed adversarial vectors (slow tier — first call compiles
the window-scan graphs, cached in .jax_cache afterwards).

Vector bytes themselves are pinned in tests/test_conformance_vectors.py
(kzg/msm runner, where the all-files-consumed gate tracks the files);
here the same committed cases feed the device agreement tests.
"""

import json
import os

import pytest

from lighthouse_tpu.crypto.constants import R
from lighthouse_tpu.crypto.ref_curve import G1
from lighthouse_tpu.kzg.api import _g1_lincomb, _g1_lincomb_naive

VECTOR_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "vectors", "kzg", "msm"
)


def _load_cases():
    out = {}
    for name in sorted(os.listdir(VECTOR_DIR)):
        with open(os.path.join(VECTOR_DIR, name)) as f:
            case = json.load(f)
        pts = [
            None if p is None else (int(p["x"], 16), int(p["y"], 16))
            for p in case["input"]["points"]
        ]
        scalars = [int(s, 16) for s in case["input"]["scalars"]]
        out[name.removesuffix(".json")] = (
            pts,
            scalars,
            bytes.fromhex(case["output"][2:]),
        )
    return out


def _mults_of_g(n):
    """[1]G .. [n]G as affine pairs (the shared add-chain helper)."""
    from lighthouse_tpu.kzg.trusted_setup import g1_generator_multiples

    return g1_generator_multiples(n)


def test_host_pippenger_matches_naive_ladder():
    """The Pippenger `_g1_lincomb` must be extensionally identical to
    the retired per-point ladder on random inputs plus every edge the
    committed vectors pin (zero scalars, infinity, r-1, duplicates)."""
    import random

    rng = random.Random(1234)
    pts = _mults_of_g(12)
    cases = [
        ([], []),
        ([pts[0]], [0]),
        ([pts[0]], [R - 1]),
        ([None, None], [5, 9]),
        (pts[:4], [0, 0, 0, 0]),
        ([pts[2], pts[2], pts[2]], [1, R - 1, 2**200]),
        (
            [pts[i] for i in range(12)],
            [rng.randrange(R) for _ in range(12)],
        ),
        (
            [pts[0], None, pts[5], pts[5], None, pts[7]],
            [rng.randrange(R) for _ in range(6)],
        ),
    ]
    for i, (p, s) in enumerate(cases):
        assert G1.eq(
            _g1_lincomb(p, s), _g1_lincomb_naive(p, s)
        ), f"case {i}"


def test_host_pippenger_window_heuristic_bounds():
    from lighthouse_tpu.kzg.api import _pippenger_window_bits

    widths = [_pippenger_window_bits(n) for n in (1, 8, 64, 4096, 10**6)]
    assert widths == sorted(widths), "window width must grow with n"
    assert all(2 <= c <= 15 for c in widths)


def test_signed_digits_reconstruct():
    """Device digit decomposition: sum d_w 2^(cw) == s for the edge
    scalars, digits within the signed bound, window count exact."""
    from lighthouse_tpu.ops import msm as msm_ops

    for c in (3, 4, 5):
        w = msm_ops.num_windows(c)
        half = 1 << (c - 1)
        for s in (0, 1, R - 1, R - 2, 2**254, (1 << 255) - 1, 0xDEADBEEF):
            d = msm_ops.signed_digits(s, c)
            assert len(d) == w
            assert all(-half < di <= half for di in d)
            assert sum(di << (c * i) for i, di in enumerate(d)) == s % R


@pytest.mark.slow
def test_device_msm_matches_host_oracle_on_vectors():
    """Variable-base Pippenger device graph vs the committed vectors —
    every adversarial edge case (zero scalars, infinity points, r-1,
    duplicates, single point). The 4096 shape is device-checked through
    the fixed-base commitment path below (the variable-base graph at
    4096 lanes is a hardware-scale program, not a CPU test)."""
    from lighthouse_tpu.bls.point_serde import g1_compress
    from lighthouse_tpu.kzg.tpu_backend import g1_msm_tpu

    cases = _load_cases()
    ran = 0
    for name, (pts, scalars, expect) in cases.items():
        if len(scalars) > 64:
            continue  # fixed-base covers the full shape
        got = g1_compress(g1_msm_tpu(pts, scalars))
        assert got == expect, name
        ran += 1
    assert ran >= 5


@pytest.mark.slow
def test_device_fixed_base_matches_host_oracle():
    """Fixed-base windowed device graph vs the host Pippenger oracle
    over the dev setup's powers, covering the same adversarial scalar
    edges on the producer (commitment/proof) path."""
    from lighthouse_tpu.bls.point_serde import g1_compress
    from lighthouse_tpu.kzg import dev_setup
    from lighthouse_tpu.kzg.tpu_backend import g1_msm_fixed_base_tpu

    s = dev_setup(8)
    scalar_sets = [
        [0, 0, 0, 0, 0, 0, 0, 0],
        [R - 1] * 8,
        [1, 0, R - 1, 2**254, 7, 7, 0xABCDEF, R - 2],
        [5],  # short MSM (proof path: quotient is one shorter)
    ]
    for i, scalars in enumerate(scalar_sets):
        got = g1_compress(g1_msm_fixed_base_tpu(scalars, s))
        want = g1_compress(_g1_lincomb(s.g1_powers[: len(scalars)], scalars))
        assert got == want, f"set {i}"


@pytest.mark.slow
def test_device_fixed_base_full_4096_shape():
    """The mainnet commitment shape end to end on the device graph.
    ~3 min of CPU-backend XLA even warm (the graph is hardware-scale:
    64 windows x 4096-lane tree folds), so it only runs when asked;
    the committed full_4096 vector is host-verified in tier-1 and the
    `BENCH_CONFIG=kzg` measures this shape on the chip."""
    if os.environ.get("LIGHTHOUSE_TPU_MSM_FULL") != "1":
        pytest.skip(
            "set LIGHTHOUSE_TPU_MSM_FULL=1 to run the 4096-lane device "
            "graph on CPU (verified on the PR-4 box: device == host)"
        )
    from lighthouse_tpu import kzg

    blob = b"".join(
        ((i * 2654435761 + 11) % (2**200)).to_bytes(32, "big")
        for i in range(4096)
    )
    setup = kzg.dev_setup(4096)
    assert kzg.blob_to_kzg_commitment(
        blob, setup, backend="tpu"
    ) == kzg.blob_to_kzg_commitment(blob, setup)


@pytest.mark.slow
def test_device_commitment_and_proof_dispatch():
    """End-to-end producer dispatch: blob_to_kzg_commitment and
    compute_kzg_proof produce identical bytes on ref and tpu backends,
    and the resulting sidecar proof verifies."""
    from lighthouse_tpu import kzg

    blob = b"".join(
        ((i * 7919 + 3) % (2**200)).to_bytes(32, "big") for i in range(8)
    )
    c_ref = kzg.blob_to_kzg_commitment(blob)
    c_tpu = kzg.blob_to_kzg_commitment(blob, backend="tpu")
    assert c_ref == c_tpu
    p_ref, y_ref = kzg.compute_kzg_proof(blob, 0xBEEF)
    p_tpu, y_tpu = kzg.compute_kzg_proof(blob, 0xBEEF, backend="tpu")
    assert (p_ref, y_ref) == (p_tpu, y_tpu)
    proof = kzg.compute_blob_kzg_proof(blob, c_tpu, backend="tpu")
    assert kzg.verify_blob_kzg_proof(blob, c_tpu, proof)
    # zero blob: the identity commitment flows through the device path
    zb = b"\x00" * (32 * 8)
    assert kzg.blob_to_kzg_commitment(
        zb, backend="tpu"
    ) == kzg.blob_to_kzg_commitment(zb)
