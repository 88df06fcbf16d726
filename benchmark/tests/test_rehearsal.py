"""Rehearsals that need no chip: every file the benchmark names loads by
name; the benchmark's own signatures verify under the program's `ref`
backend and its adversarial and torsion ones do not; without a TPU the
command exits non-zero and prints no result."""

import glob
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, registry, signing
from benchmark.crypto.curve import G1

ROOT = harness.ROOT


def test_every_named_file_loads():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        _, cfg, mix = harness.resolve(spec, cell["name"])
        gen = harness.generator(mix)
        for fn in ("warm_batches", "generate", "drive", "end_to_end",
                   "harness_readings", "counts", "check"):
            assert callable(getattr(gen, fn)), (mix["generator"], fn)
        assert gen.warm_batches(cfg, mix)
        for kind in ("end_to_end", "per_layer"):
            assert harness.cell_metrics(spec, cell, kind)
    for m in spec["per_layer"]:
        assert callable(harness.reader(m["name"]))
    # every traffic mix and reader on disk, with a cell or not yet
    for path in glob.glob(os.path.join(harness.BENCH_DIR, "traffic", "*.json")):
        mix = harness.load_mix(os.path.basename(path)[:-5])
        assert harness.generator(mix).warm_batches
    for path in glob.glob(os.path.join(harness.BENCH_DIR, "metrics", "*.py")):
        assert callable(harness.reader(os.path.basename(path)[:-3]))
    for conf in spec["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == conf["name"]
        assert cfg["source"].startswith(conf["source"])
        assert cfg["reduced"] == conf["reduced"]


def _program_set(bls, validators, message, sig):
    pks = []
    for v in validators:
        aff = G1.to_affine(G1.mul_scalar(G1.generator, registry.secret_key(v)))
        pks.append(bls.PublicKey((aff[0], aff[1], 1)))
    return bls.SignatureSet(bls.Signature.from_bytes(sig), pks, message)


def test_signatures_verify_under_the_program_reference():
    from lighthouse_tpu import bls

    delta = signing.adversary_delta(7)
    sets = [
        (b"\x01" * 32, (5,), 0),
        (b"\x02" * 32, (9, 11, 40), 0),
        (b"\x03" * 32, (2,), 1),
        (b"\x04" * 32, (3, 4), -1),
        (b"\x05" * 32, (6, 7), signing.TORSION),
    ]
    sigs = signing.sign_sets((sets, delta))
    verdicts = [
        bls.verify_signature_sets(
            [_program_set(bls, vs, m, sig)], backend="ref", consumer="bench"
        )
        for (m, vs, _), sig in zip(sets, sigs)
    ]
    assert verdicts == [True, True, False, False, False]
    # the pair's errors cancel in an unweighted sum: the reference of the
    # benchmark still refuses each
    expected = signing.expected_sigs(sets)
    assert [a == b for a, b in zip(sigs, expected)] == verdicts


def test_registry_round_trip(tmp_path):
    from multiprocessing.pool import ThreadPool

    with ThreadPool(2) as pool:
        path = registry.build(str(tmp_path), 40, pool, chunk=16)
    points, compressed = registry.load(path)
    assert len(points) == 40
    for v in (0, 17, 39):
        aff = G1.to_affine(G1.mul_scalar(G1.generator, registry.secret_key(v)))
        assert points[v] == aff
    from lighthouse_tpu.bls import api

    pk = api.PublicKey.from_bytes(compressed[17])
    assert G1.to_affine(pk.point) == points[17]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_command_without_a_tpu_prints_no_result(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["command"]
    proc = subprocess.run(
        [sys.executable] + cmd[1:] + [
            "--workload", "gnosis-300k.block-import", "--seed", "2147483659",
            "--seconds", "1", "--trace", trace,
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    assert "TPU" in proc.stderr
