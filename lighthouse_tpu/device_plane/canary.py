"""Known-answer sentinel material for canary-verified device batches.

The only way to catch a device that COMPLETES but LIES is to keep
asking it questions whose answers are known: one sentinel that must
verify and one that must not, committed as vectors
(``tests/vectors/sentinel/<plane>/{valid,invalid}.json``, written by
``scripts/gen_vectors.py`` from `build_sentinel_vectors` below so the
generator and the runtime share one source of truth). Two uses:

  * the verification bus splices the VALID bls sentinel into every
    canaried shared batch (attribution-free ``extra_sets`` — sentinels
    must appear in neither side of the attribution_complete equality)
    and checks the valid/invalid PAIR per-set inside the same guarded
    attempt (`check_pair`). A batch verdict can only be trusted if the
    pair comes back exactly (True, False): a flipped or stuck verdict
    plane fails that check, raises `CanaryViolation`, quarantines the
    plane, and the whole batch re-verifies on host — silent corruption
    becomes a detected, attributed, bounded event.
  * the startup self-test (`GUARD.self_test`) runs `self_test_plane`
    per plane (bls, kzg, merkle_proof) against the host oracles, so a
    node never goes live with corrupt sentinel material or a broken
    oracle path.

Sentinel generation is deterministic (interop keypair 0, fixed
messages, hash-derived blob/leaves) — regeneration is byte-identical,
which the vector round-trip test pins.
"""

import hashlib
import json
import threading
from pathlib import Path

from lighthouse_tpu.common.tracing import span
from lighthouse_tpu.device_plane.executor import (
    NULL_PLAN,
    CanaryViolation,
)

CANARY_MESSAGE = b"lighthouse-tpu device-plane canary"
TAMPERED_MESSAGE = b"lighthouse-tpu device-plane canary (tampered)"

# tiny deterministic kzg blob: 4 field elements keeps the sentinel MSM
# sub-millisecond on the host oracle
SENTINEL_BLOB_ELEMENTS = 4

# depth-3 merkle sentinel (gindex 11 -> branch length 3)
MERKLE_GINDEX = 11
MERKLE_DEPTH = 3

PLANES = ("bls", "kzg", "merkle_proof")

VECTOR_DIR = (
    Path(__file__).resolve().parents[2] / "tests" / "vectors" / "sentinel"
)

_lock = threading.Lock()
_built: dict | None = None
_bls_sets: tuple | None = None


# ---------------------------------------------------------------- building


def _sentinel_blob() -> bytes:
    from lighthouse_tpu.crypto.constants import R

    parts = []
    for i in range(SENTINEL_BLOB_ELEMENTS):
        v = (
            int.from_bytes(
                hashlib.sha256(
                    f"lighthouse-tpu kzg sentinel element {i}".encode()
                ).digest(),
                "big",
            )
            % R
        )
        parts.append(v.to_bytes(32, "big"))
    return b"".join(parts)


def _tamper_blob(blob: bytes) -> bytes:
    """Replace element 0 with a different canonical field element, so
    the blob stays well-formed but no longer matches the proof."""
    from lighthouse_tpu.crypto.constants import R

    v = (int.from_bytes(blob[:32], "big") + 1) % R
    return v.to_bytes(32, "big") + blob[32:]


def build_sentinel_vectors() -> dict:
    """{plane: {"valid": obj, "invalid": obj}} — the objects
    `scripts/gen_vectors.py` commits and the loaders below consume.
    Fully deterministic; no randomness, no wall clock."""
    from lighthouse_tpu import bls
    from lighthouse_tpu.kzg import api as kzg
    from lighthouse_tpu.ops.merkle_proof import fold_branches_host

    kp = bls.interop_keypairs(1)[0]
    sig = kp.sk.sign(CANARY_MESSAGE)
    bls_valid = {
        "pubkeys": [kp.pk.to_bytes().hex()],
        "message": CANARY_MESSAGE.hex(),
        "signature": sig.to_bytes().hex(),
    }
    # same signature, tampered message: structurally valid, must fail
    bls_invalid = dict(bls_valid, message=TAMPERED_MESSAGE.hex())

    blob = _sentinel_blob()
    commitment = kzg.blob_to_kzg_commitment(blob, consumer="bench")
    proof = kzg.compute_blob_kzg_proof(
        blob, commitment, consumer="bench"
    )
    kzg_valid = {
        "blob": blob.hex(),
        "commitment": commitment.hex(),
        "proof": proof.hex(),
    }
    kzg_invalid = dict(kzg_valid, blob=_tamper_blob(blob).hex())

    leaf = hashlib.sha256(b"lighthouse-tpu merkle sentinel leaf").digest()
    branch = [
        hashlib.sha256(
            f"lighthouse-tpu merkle sentinel sibling {d}".encode()
        ).digest()
        for d in range(MERKLE_DEPTH)
    ]
    root = fold_branches_host([(leaf, branch, MERKLE_GINDEX)])[0]
    merkle_valid = {
        "leaf": leaf.hex(),
        "branch": [b.hex() for b in branch],
        "gindex": MERKLE_GINDEX,
        "root": root.hex(),
    }
    merkle_invalid = dict(
        merkle_valid, root=(bytes([root[0] ^ 0xFF]) + root[1:]).hex()
    )

    return {
        "bls": {"valid": bls_valid, "invalid": bls_invalid},
        "kzg": {"valid": kzg_valid, "invalid": kzg_invalid},
        "merkle_proof": {
            "valid": merkle_valid,
            "invalid": merkle_invalid,
        },
    }


# ----------------------------------------------------------------- loading


def _vectors() -> dict:
    """Committed vectors when present, deterministic regeneration
    otherwise (a fresh checkout before gen_vectors ran must still
    self-test)."""
    global _built
    with _lock:
        if _built is not None:
            return _built
    out = {}
    complete = True
    for plane in PLANES:
        cases = {}
        for name in ("valid", "invalid"):
            path = VECTOR_DIR / plane / f"{name}.json"
            try:
                with open(path) as f:
                    cases[name] = json.load(f)
            except (OSError, json.JSONDecodeError):
                complete = False
                break
        if not complete:
            break
        out[plane] = cases
    if not complete:
        out = build_sentinel_vectors()
    with _lock:
        _built = out
    return out


def bls_sentinels() -> tuple:
    """(valid_set, invalid_set) as `SignatureSet`s — the valid one is
    spliced into canaried shared batches, the pair feeds
    `check_pair`."""
    global _bls_sets
    with _lock:
        if _bls_sets is not None:
            return _bls_sets
    from lighthouse_tpu import bls

    sets = []
    for name in ("valid", "invalid"):
        case = _vectors()["bls"][name]
        sets.append(
            bls.SignatureSet(
                bls.Signature.from_bytes(
                    bytes.fromhex(case["signature"])
                ),
                [
                    bls.PublicKey.from_bytes(bytes.fromhex(p))
                    for p in case["pubkeys"]
                ],
                bytes.fromhex(case["message"]),
            )
        )
    with _lock:
        _bls_sets = (sets[0], sets[1])
    return _bls_sets


# ---------------------------------------------------------------- checking


def check_pair(backend: str, plan=NULL_PLAN) -> None:
    """Verify the (valid, invalid) bls sentinel pair per-set on
    `backend`, verdicts routed through the dispatch's injection plan
    (so an injected flip flips the canary too — by construction every
    flip is caught). Anything but exactly (True, False) raises
    `CanaryViolation`.

    On the device backend this is one extra small-shape device call per
    canaried batch (`verify_signature_sets_tpu_individual`) — the price
    of catching FALSE-ACCEPTS, which the batch-riding valid sentinel
    cannot see. Sentinel sets stay out of device attribution on both
    sides (no note_sets, no journal n_sets). Its stages nest under one
    `verify/canary` span."""
    with span("verify/canary", backend=backend):
        valid, invalid = bls_sentinels()
        if backend == "tpu":
            from lighthouse_tpu.bls.tpu_backend import (
                verify_signature_sets_tpu_individual,
            )

            verdicts = [
                bool(v)
                for v in verify_signature_sets_tpu_individual(
                    [valid, invalid], consumer="bench"
                )
            ]
        else:
            from lighthouse_tpu.bls.api import _verify_one_ref

            verdicts = [_verify_one_ref(valid), _verify_one_ref(invalid)]
    verdicts = list(plan.verdict(verdicts))
    if verdicts != [True, False]:
        raise CanaryViolation(
            f"bls sentinel pair came back {verdicts} on backend "
            f"{backend!r} (expected [True, False]) — the device plane "
            "is producing wrong verdicts"
        )


def self_test_plane(plane: str) -> bool:
    """Host-oracle known-answer check for one plane: the committed
    valid sentinel must pass, the invalid one must fail."""
    cases = _vectors()
    if plane == "bls":
        from lighthouse_tpu.bls.api import _verify_one_ref

        valid, invalid = bls_sentinels()
        return _verify_one_ref(valid) and not _verify_one_ref(invalid)
    if plane == "kzg":
        from lighthouse_tpu.kzg.api import verify_blob_kzg_proof

        ok = True
        for name, want in (("valid", True), ("invalid", False)):
            case = cases["kzg"][name]
            got = verify_blob_kzg_proof(
                bytes.fromhex(case["blob"]),
                bytes.fromhex(case["commitment"]),
                bytes.fromhex(case["proof"]),
            )
            ok = ok and (got is want)
        return ok
    if plane == "merkle_proof":
        from lighthouse_tpu.ops.merkle_proof import fold_branches_host

        ok = True
        for name, want in (("valid", True), ("invalid", False)):
            case = cases["merkle_proof"][name]
            computed = fold_branches_host(
                [
                    (
                        bytes.fromhex(case["leaf"]),
                        [bytes.fromhex(b) for b in case["branch"]],
                        int(case["gindex"]),
                    )
                ]
            )[0]
            ok = ok and (
                (computed == bytes.fromhex(case["root"])) is want
            )
        return ok
    raise ValueError(f"unknown self-test plane {plane!r}")
