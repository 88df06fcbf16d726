"""Tier-1 tests for the device-plane fault domain.

Covers the four guard layers in isolation and composed:

  * `CircuitBreaker` — the full closed/open/half-open state machine
    including the single-probe discipline and the plane-wide quarantine
    key, driven by an injectable clock (no sleeping);
  * `FaultInjector` — the purity contract (every decision a pure
    function of (seed, kind, plane, bucket, ordinal)) and arm/disarm;
  * `GuardedExecutor` — failover order, fault-type narrowing for host
    backends, watchdog timeout + reaper, reentrancy passthrough,
    breaker-open fail-fast, and the startup known-answer self-test;
  * canary contract — committed sentinel vectors round-trip against
    regeneration, host-oracle self-tests, flip-catch through the
    verification bus end to end (an armed flip must produce ZERO wrong
    verdicts: the canary catches it and the batch re-verifies on host).

Plus the operational surface: `bn --device-breaker-*` knob application,
the `/lighthouse/health` stats block, scenario-schema validation for
the device_* fault kinds, and the guarded-dispatch lint pass.
"""

import copy
import json
import threading
import time
from pathlib import Path

import pytest

from lighthouse_tpu import bls
from lighthouse_tpu.common.events_journal import Journal
from lighthouse_tpu.device_plane import canary
from lighthouse_tpu.device_plane.breaker import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    QUARANTINE_BUCKET,
    CircuitBreaker,
)
from lighthouse_tpu.device_plane.executor import (
    GUARD,
    NULL_PLAN,
    CanaryViolation,
    DeviceFaultError,
    DeviceTimeout,
    GuardedExecutor,
    InjectionPlan,
    pow2_bucket,
)
from lighthouse_tpu.device_plane.faults import (
    INJECTOR,
    KINDS,
    FaultInjector,
    decide,
)

_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def clean_globals():
    """Tests that touch the process-global GUARD / INJECTOR must leave
    them at boot state for the rest of the suite."""
    GUARD.reset()
    INJECTOR.reset()
    yield
    GUARD.reset()
    INJECTOR.reset()


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


# ---------------------------------------------------------------- breaker


def test_breaker_closed_to_open_to_half_open_to_closed():
    clock = FakeClock()
    transitions = []
    br = CircuitBreaker(
        threshold=3,
        cooldown_s=10.0,
        clock=clock,
        on_transition=lambda p, b, to: transitions.append((p, b, to)),
    )
    # closed: dispatches flow; sub-threshold failures stay closed
    assert br.allow("bls", "64")
    br.record_failure("bls", "64")
    br.record_failure("bls", "64")
    assert br.state_of("bls", "64") == CLOSED
    # a success resets the consecutive-failure count
    br.record_success("bls", "64")
    br.record_failure("bls", "64")
    br.record_failure("bls", "64")
    assert br.state_of("bls", "64") == CLOSED
    # third consecutive failure trips it
    br.record_failure("bls", "64")
    assert br.state_of("bls", "64") == OPEN
    assert not br.allow("bls", "64")
    # other buckets and planes are unaffected
    assert br.allow("bls", "128")
    assert br.allow("kzg", "64")
    # cooldown elapses -> half-open, exactly ONE probe admitted
    clock.now += 10.0
    assert br.allow("bls", "64")
    assert br.state_of("bls", "64") == HALF_OPEN
    assert not br.allow("bls", "64")  # single-probe discipline
    assert not br.allow("bls", "64")
    # probe success closes the key and clears the failure count
    br.record_success("bls", "64")
    assert br.state_of("bls", "64") == CLOSED
    assert br.allow("bls", "64")
    assert transitions == [
        ("bls", "64", OPEN),
        ("bls", "64", HALF_OPEN),
        ("bls", "64", CLOSED),
    ]


def test_breaker_probe_failure_reopens_with_fresh_cooldown():
    clock = FakeClock()
    br = CircuitBreaker(threshold=1, cooldown_s=5.0, clock=clock)
    br.record_failure("bls", "4")
    assert br.state_of("bls", "4") == OPEN
    clock.now += 5.0
    assert br.allow("bls", "4")  # the probe
    br.record_failure("bls", "4")
    assert br.state_of("bls", "4") == OPEN
    # fresh cooldown: still open until ANOTHER full cooldown elapses
    clock.now += 4.9
    assert not br.allow("bls", "4")
    clock.now += 0.2
    assert br.allow("bls", "4")


def test_breaker_quarantine_rejects_every_bucket_and_recovers():
    clock = FakeClock()
    br = CircuitBreaker(threshold=3, cooldown_s=10.0, clock=clock)
    br.quarantine("bls")
    assert br.snapshot() == {f"bls/{QUARANTINE_BUCKET}": OPEN}
    # every bucket of the plane is rejected, other planes untouched
    assert not br.allow("bls", "4")
    assert not br.allow("bls", "4096")
    assert br.allow("kzg", "4")
    # recovery rides the quarantine key's own half-open probe,
    # whichever bucket carries it
    clock.now += 10.0
    assert br.allow("bls", "4096")
    assert not br.allow("bls", "4")  # probe already claimed
    br.record_success("bls", "4096")
    assert br.state_of("bls", "4") == CLOSED
    assert br.allow("bls", "4")


# --------------------------------------------------------------- injector


def test_decide_is_pure_and_respects_rate_bounds():
    args = (7, "stall", "bls", "64", 3)
    assert decide(*args, rate=1.0) is True
    assert decide(*args, rate=0.0) is False
    mid = [decide(7, "flip", "bls", "64", i, 0.5) for i in range(64)]
    # pure: byte-identical on recomputation, and actually mixed
    assert mid == [decide(7, "flip", "bls", "64", i, 0.5) for i in range(64)]
    assert True in mid and False in mid
    # the identity tuple matters: a different seed decides differently
    assert mid != [decide(8, "flip", "bls", "64", i, 0.5) for i in range(64)]


def test_injector_plans_are_deterministic_and_scoped():
    a, b = FaultInjector(), FaultInjector()
    for inj in (a, b):
        inj.arm("stall", "bls", rate=0.5, seed=42)
        inj.arm("flip", "bls", rate=0.25, seed=42)
    seq_a = [a.plan("bls", "64") for _ in range(32)]
    seq_b = [b.plan("bls", "64") for _ in range(32)]
    assert seq_a == seq_b  # same seed, same dispatch sequence
    assert any(p for p in seq_a)
    # other planes are untouched by bls specs
    assert a.plan("kzg", "64") == frozenset()
    # disarm by kind removes only that spec
    a.disarm(kind="stall", plane="bls")
    assert all("stall" not in a.plan("bls", "64") for _ in range(16))
    a.disarm()
    assert not a.armed()
    # a disarmed injector consumes no ordinals
    assert a.plan("bls", "64") == frozenset()
    with pytest.raises(ValueError):
        a.arm("segfault", "bls")


def test_injection_plan_flip_and_raise():
    plan = InjectionPlan({"flip"})
    assert plan.verdict(True) is False
    assert plan.verdict([True, False]) == [False, True]
    assert NULL_PLAN.verdict(True) is True
    with pytest.raises(DeviceFaultError):
        InjectionPlan({"stall"}).raise_if_faulted()
    with pytest.raises(DeviceFaultError):
        InjectionPlan({"error"}).raise_if_faulted()


def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (0, 1, 2, 3, 64, 65)] == [
        1, 1, 2, 4, 64, 128,
    ]


# --------------------------------------------------------------- executor


def _executor():
    g = GuardedExecutor()
    g.configure(watchdog=False)  # watchdog tested explicitly below
    return g


def test_dispatch_success_path_counts_and_stays_closed():
    g = _executor()
    out = g.dispatch("bls", 64, lambda plan: "verdict")
    assert out == "verdict"
    st = g.stats()
    assert st["dispatches"] == 1
    assert st["faults"] == {} and st["failovers"] == {}


def test_dispatch_failover_walks_tiers_in_order(clean_globals):
    g = _executor()
    j = Journal()

    def device_fn(plan):
        raise DeviceFaultError("wedged")

    calls = []

    def broken_tier():
        calls.append("xla-host")
        raise RuntimeError("tier down")

    def good_tier():
        calls.append("ref")
        return "host-verdict"

    out = g.dispatch(
        "bls", 64, device_fn,
        fallbacks=[("xla-host", broken_tier), ("ref", good_tier)],
        journal=j, slot=3,
    )
    assert out == "host-verdict"
    assert calls == ["xla-host", "ref"]
    st = g.stats()
    assert st["faults"] == {"bls:error": 1}
    assert st["failovers"] == {"bls:ref": 1}
    evs = j.query(kind="device_fault")
    assert [e["outcome"] for e in evs] == ["fault", "failover"]
    assert evs[1]["attrs"]["backend"] == "ref"
    assert evs[1]["attrs"]["fault"] == "error"
    assert evs[1]["slot"] == 3


def test_fault_type_narrowing_reraises_data_errors():
    """Host backends only guard the injected-fault taxonomy: a
    data-dependent exception keeps its semantics, does not poison the
    breaker, and never re-runs on a fallback tier."""
    g = _executor()

    def device_fn(plan):
        raise ValueError("malformed signature bytes")

    with pytest.raises(ValueError):
        g.dispatch(
            "bls", 64, device_fn,
            fallbacks=[("ref", lambda: "never")],
            fault_types=(DeviceFaultError,),
        )
    st = g.stats()
    assert st["faults"] == {} and st["failovers"] == {}
    assert g.breaker.state_of("bls", "64") == CLOSED


def test_breaker_open_fails_fast_and_recovers(clean_globals):
    g = _executor()
    g.configure(threshold=1, cooldown_s=0.0)

    def bad(plan):
        raise DeviceFaultError("wedged")

    # first failure: no fallback -> the device error propagates and
    # trips the threshold-1 breaker
    with pytest.raises(DeviceFaultError):
        g.dispatch("bls", 64, bad)
    assert g.stats()["transitions"] == {"bls:open": 1}
    # cooldown 0 -> next dispatch is the half-open probe; succeed it
    out = g.dispatch("bls", 64, lambda plan: "ok")
    assert out == "ok"
    assert g.breaker.state_of("bls", "64") == CLOSED
    tr = g.stats()["transitions"]
    assert tr == {"bls:open": 1, "bls:half_open": 1, "bls:closed": 1}


def test_breaker_open_without_fallback_raises_device_fault():
    g = _executor()
    g.configure(threshold=1, cooldown_s=3600.0)
    with pytest.raises(DeviceFaultError):
        g.dispatch("bls", 64, lambda plan: (_ for _ in ()).throw(
            DeviceFaultError("wedged")
        ))
    # breaker now open for a full hour: straight to failover, and with
    # no fallback that is a typed fail-fast, never a hang
    with pytest.raises(DeviceFaultError, match="breaker open"):
        g.dispatch("bls", 64, lambda plan: "unreachable")


def test_reentrant_dispatch_passes_through():
    """A guarded attempt reaching another guarded entry point (bus ->
    tpu backend) must not double-guard: only the outermost crossing
    injects and counts."""
    g = _executor()

    def inner(plan):
        return "inner"

    def outer(plan):
        return g.dispatch("bls", 32, inner)

    assert g.dispatch("bls", 64, outer) == "inner"
    assert g.stats()["dispatches"] == 1


def test_disabled_guard_is_passthrough():
    g = _executor()
    g.configure(enabled=False)
    assert g.dispatch("bls", 64, lambda plan: "raw") == "raw"
    assert g.stats()["dispatches"] == 0


def test_watchdog_timeout_abandons_reaps_and_fails_over():
    g = GuardedExecutor()  # watchdog ON
    release = threading.Event()

    def wedged(plan):
        release.wait(5.0)
        return "late"

    out = g.dispatch(
        "bls", 64, wedged,
        fallbacks=[("ref", lambda: "host-verdict")],
        timeout_s=0.05,
    )
    assert out == "host-verdict"
    st = g.stats()
    assert st["faults"].get("bls:timeout") == 1
    assert st["failovers"] == {"bls:ref": 1}
    assert st["abandoned"] == 1
    # let the wedge clear; the reaper joins it off the critical path
    # and records the late completion as its own fault kind
    release.set()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        st = g.stats()
        if st["reaped"] == 1 and st["abandoned"] == 0:
            break
        time.sleep(0.02)
    assert st["reaped"] == 1 and st["abandoned"] == 0
    assert st["faults"].get("bls:reaped") == 1


def test_compile_window_is_not_timed_by_the_watchdog():
    """Time inside `compile_window` (a cold bucket's trace+compile)
    extends the watchdog's deadline; execution outside it stays
    guarded."""
    g = GuardedExecutor()  # watchdog ON

    def compiles_then_runs(plan):
        with g.compile_window():
            time.sleep(0.3)
        return "verdict"

    out = g.dispatch(
        "bls", 64, compiles_then_runs,
        fallbacks=[("ref", lambda: "host-verdict")], timeout_s=0.1,
    )
    assert out == "verdict"
    assert g.stats()["faults"] == {}

    def compiles_then_wedges(plan):
        with g.compile_window():
            time.sleep(0.2)
        time.sleep(0.5)
        return "late"

    out = g.dispatch(
        "bls", 64, compiles_then_wedges,
        fallbacks=[("ref", lambda: "host-verdict")], timeout_s=0.1,
    )
    assert out == "host-verdict"
    assert g.stats()["faults"] == {"bls:timeout": 1}
    # outside a timed attempt the window is a no-op
    with g.compile_window():
        pass


def test_per_dispatch_watchdog_opt_out():
    """watchdog=False opts one dispatch out of the watchdog (the
    sharded mesh boundary: multi-minute legitimate cold compiles,
    async results) while keeping injection/breaker coverage."""
    g = GuardedExecutor()  # watchdog globally ON
    # would time out under the watchdog; runs on the caller thread
    out = g.dispatch(
        "sharded", 16,
        lambda plan: (time.sleep(0.15), "compiled")[1],
        timeout_s=0.05, watchdog=False,
    )
    assert out == "compiled"
    st = g.stats()
    assert st["faults"] == {} and st["abandoned"] == 0
    # the breaker still fronts opted-out dispatches
    g.configure(threshold=1, cooldown_s=3600.0)
    with pytest.raises(DeviceFaultError):
        g.dispatch(
            "sharded", 16,
            lambda plan: (_ for _ in ()).throw(DeviceFaultError("x")),
            watchdog=False,
        )
    with pytest.raises(DeviceFaultError, match="breaker open"):
        g.dispatch("sharded", 16, lambda plan: "skipped", watchdog=False)


def test_injected_stall_fails_over(clean_globals):
    g = _executor()
    INJECTOR.arm("stall", "bls", rate=1.0, seed=1)
    out = g.dispatch(
        "bls", 64, lambda plan: "device",
        fallbacks=[("ref", lambda: "host")],
    )
    assert out == "host"
    assert g.stats()["faults"] == {"bls:stall": 1}


def test_timeout_budget_composition():
    g = GuardedExecutor()
    g.configure(
        base_timeout_s=2.0, timeout_factor=4.0, min_timeout_s=1.0,
        cold_allowance_s=30.0,
    )
    # unknown shape: warm budget + cold allowance
    t = g.timeout_for("bls", "never-seen-shape", predicted_s=None)
    assert t == pytest.approx(4.0 * 2.0 + g.cold_allowance_s("x"))
    # a caller-predicted wall replaces the static base
    t = g.timeout_for("bls", "never-seen-shape", predicted_s=0.5)
    assert t == pytest.approx(
        max(1.0, 4.0 * 0.5) + g.cold_allowance_s("x")
    )


# ----------------------------------------------------------------- canary


def test_committed_sentinel_vectors_match_regeneration():
    """gen_vectors.py commits exactly what build_sentinel_vectors
    produces — the generator and the runtime share one source of
    truth, pinned here."""
    built = canary.build_sentinel_vectors()
    assert set(built) == set(canary.PLANES)
    for plane in canary.PLANES:
        for name in ("valid", "invalid"):
            path = canary.VECTOR_DIR / plane / f"{name}.json"
            assert path.exists(), f"missing committed vector {path}"
            with open(path) as f:
                assert json.load(f) == built[plane][name], (
                    f"committed sentinel vector {plane}/{name} drifted "
                    "from build_sentinel_vectors() — rerun "
                    "scripts/gen_vectors.py"
                )


def test_self_test_all_planes_pass_on_host_oracle():
    assert all(
        canary.self_test_plane(p) for p in canary.PLANES
    )


def test_check_pair_catches_flipped_verdicts():
    # clean pair on the host oracle: exactly (True, False)
    canary.check_pair("ref", NULL_PLAN)
    # a flip injection inverts BOTH sentinel verdicts -> violation
    with pytest.raises(CanaryViolation):
        canary.check_pair("ref", InjectionPlan({"flip"}))


def test_self_test_quarantines_failing_plane(monkeypatch, clean_globals):
    g = GuardedExecutor()
    j = Journal()
    monkeypatch.setattr(
        canary, "self_test_plane", lambda plane: plane != "kzg"
    )
    results = g.self_test(journal=j)
    assert results == {"bls": True, "kzg": False, "merkle_proof": True}
    assert g.breaker.state_of("kzg", "anything") == OPEN
    assert g.breaker.state_of("bls", "anything") == CLOSED
    outcomes = [
        e["outcome"] for e in j.query(kind="device_fault")
    ]
    assert "selftest_failed" in outcomes and "selftest_ok" in outcomes


def test_bus_flip_injection_yields_zero_wrong_verdicts(clean_globals):
    """The acceptance invariant, end to end on the real bus: with a
    verdict-flipping device armed, the canary pair catches the lie
    inside the guarded attempt and the whole batch re-verifies on the
    host tier — the caller sees only CORRECT verdicts."""
    from lighthouse_tpu.verification_bus import VerificationBus

    kps = bls.interop_keypairs(2)
    msg = b"device-plane-flip-test"
    good = bls.SignatureSet(kps[0].sk.sign(msg), [kps[0].pk], msg)
    bad = bls.SignatureSet(kps[1].sk.sign(b"wrong"), [kps[1].pk], msg)

    INJECTOR.arm("flip", "bls", rate=1.0, seed=9)
    GUARD.configure(watchdog=False)
    j = Journal()
    bus = VerificationBus(backend="ref", journal=j)
    assert bus.submit([good], consumer="gossip_single") is True
    assert bus.submit([bad], consumer="gossip_single") is False
    st = GUARD.stats()
    # first submit: canary catches the flip, quarantines the plane;
    # second submit: the open quarantine key skips the lying device
    # entirely — both still land on the host tier with true verdicts
    assert st["faults"].get("bls:canary") == 1
    assert st["failovers"].get("bls:ref") == 2
    assert st["breaker"]["state"].get("bls/*") in (OPEN, HALF_OPEN)
    evs = j.query(kind="device_fault")
    outcomes = [
        (e["outcome"], e["attrs"].get("fault")) for e in evs
    ]
    assert ("fault", "canary") in outcomes
    assert ("failover", "breaker_open") in outcomes


# ----------------------------------------------------- scenario + knobs


def _device_scenario_doc():
    with open(
        _ROOT / "lighthouse_tpu" / "sim" / "scenarios"
        / "device_faults.json"
    ) as f:
        return json.load(f)


def test_device_fault_scenario_schema():
    from lighthouse_tpu.sim.scenario import ScenarioError, validate

    doc = _device_scenario_doc()
    sc = validate(doc)
    kinds = sorted(f.kind for f in sc.faults)
    assert kinds == ["device_flip", "device_stall"]
    assert all(f.plane == "bls" for f in sc.faults)

    bad = copy.deepcopy(doc)
    bad["faults"][0]["rate"] = 0.5  # device faults are deterministic
    with pytest.raises(ScenarioError, match="rate"):
        validate(bad)

    bad = copy.deepcopy(doc)
    bad["faults"][0]["plane"] = "gpu"
    with pytest.raises(ScenarioError, match="plane"):
        validate(bad)

    bad = copy.deepcopy(doc)
    del bad["faults"][0]["until_slot"]
    with pytest.raises(ScenarioError, match="until_slot"):
        validate(bad)

    bad = copy.deepcopy(doc)
    bad["faults"][0]["kind"] = "offline"  # plane on a non-device kind
    with pytest.raises(ScenarioError, match="plane"):
        validate(bad)


def test_breaker_flags_apply_and_health_surface(clean_globals):
    import argparse

    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.cli import _apply_breaker_flags
    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.http_api import BeaconApiServer
    from lighthouse_tpu.types.spec import minimal_spec

    h = Harness(minimal_spec(name="breaker-health"), 4, backend="fake")
    chain = BeaconChain(h.state.copy(), h.spec, backend="fake")
    args = argparse.Namespace(
        device_breaker_threshold=5,
        device_breaker_cooldown_ms=250.0,
        device_breaker_canary="on",
        device_breaker_selftest="on",
    )
    _apply_breaker_flags(chain, args)
    assert GUARD.breaker.threshold == 5
    assert GUARD.breaker.cooldown_s == pytest.approx(0.25)
    assert GUARD.canary_mode == "on"
    # selftest=on ran the known-answer check at apply time
    assert GUARD.selftest is True
    assert GUARD.stats()["selftest"] == {
        "bls": True, "kzg": True, "merkle_proof": True,
    }
    doc = BeaconApiServer(chain).overload_state()
    dp = doc["device_plane"]
    assert dp["breaker"]["threshold"] == 5
    assert dp["breaker"]["cooldown_s"] == pytest.approx(0.25)
    assert dp["canary"] == "on"
    assert "dispatches" in dp and "faults" in dp


# ------------------------------------------------------------------- lint


def test_guarded_dispatch_lint_pass(tmp_path):
    from lighthouse_tpu.analysis.core import run_passes
    from lighthouse_tpu.analysis.passes.guarded_dispatch import (
        GuardedDispatchPass,
    )

    bad = (
        "from lighthouse_tpu.bls.tpu_backend import "
        "verify_signature_sets_tpu\n"
        "def f(sets):\n"
        "    return verify_signature_sets_tpu(sets)\n"
    )
    bad_attr = (
        "from lighthouse_tpu.kzg import tpu_backend\n"
        "def f(blobs, cs, ps):\n"
        "    return tpu_backend.verify_blob_kzg_proof_batch_tpu("
        "blobs, cs, ps)\n"
    )
    for rel, src in (
        ("beacon_chain/x.py", bad),
        ("network/y.py", bad_attr),
        ("bls/tpu_backend.py", bad),  # guarded boundary: exempt
        ("device_plane/executor.py", bad),  # the guard itself: exempt
    ):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    findings, _ = run_passes(tmp_path, [GuardedDispatchPass()])
    assert sorted(f.path for f in findings) == [
        "beacon_chain/x.py", "network/y.py",
    ]
    assert all(f.rule == "guarded-dispatch" for f in findings)
