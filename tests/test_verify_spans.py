"""The verify path's spans: the hash-to-G2 and pubkey-slot stages of the
marshal, the canary, one tree per bus batch across the guard's watchdog
thread, and the same spans on the profiler's clock. Host-only: marshals
are called directly and guarded attempts are fakes, so no device program
compiles."""

import subprocess
import sys
import threading
import time
from types import SimpleNamespace as NS

import pytest

from lighthouse_tpu import bls
from lighthouse_tpu.bls import tpu_backend as tb
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.events_journal import Journal
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.common.tracing import TRACER
from lighthouse_tpu.device_plane import GUARD, canary
from lighthouse_tpu.device_plane.executor import GuardedExecutor
from lighthouse_tpu.device_plane.faults import INJECTOR
from lighthouse_tpu.state_processing.pubkey_cache import PubkeyCache


def _newest(name):
    """The newest root span tree named `name`."""
    return next(r for r in reversed(TRACER.recent()) if r["name"] == name)


def _misses():
    return REGISTRY.get("lighthouse_tpu_msg_cache_events_total").labels(
        "miss"
    ).value


@pytest.fixture
def clean_guard():
    GUARD.reset()
    INJECTOR.reset()
    yield
    GUARD.reset()
    INJECTOR.reset()


def test_hash_to_g2_spans_are_the_memo_misses():
    kps = bls.interop_keypairs(3)
    msgs = [b"verify-spans fresh a", b"verify-spans fresh b"]
    msgs.append(msgs[0])  # a memo hit
    sets = [
        bls.SignatureSet(kp.sk.sign(m), [kp.pk], m)
        for kp, m in zip(kps, msgs)
    ]
    before = _misses()
    tb._marshal(sets, allow_grouped=False)
    points = _newest("verify/marshal/points")
    hashed = tracing.find(points, "verify/marshal/hash_to_g2")
    assert len(hashed) == _misses() - before == 2
    # a second marshal of the same messages hashes nothing
    tb._marshal(sets, allow_grouped=False)
    assert not tracing.find(
        _newest("verify/marshal/points"), "verify/marshal/hash_to_g2"
    )


def test_pubkeys_span_names_the_path_and_slots():
    kps = bls.interop_keypairs(3)
    cache = PubkeyCache()
    cache.import_new(
        NS(validators=[NS(pubkey=kp.pk.to_bytes()) for kp in kps])
    )
    cache.device_table()
    msg = b"verify-spans pubkeys"
    agg = bls.aggregate_signatures([kp.sk.sign(msg) for kp in kps[:2]])
    tagged = [
        bls.SignatureSet(agg, [cache.get(0), cache.get(1)], msg),
        bls.SignatureSet(kps[2].sk.sign(msg), [cache.get(2)], msg),
    ]
    tb._marshal(tagged, allow_grouped=False)
    (pubkeys,) = tracing.find(
        _newest("verify/marshal/pack"), "verify/marshal/pubkeys"
    )
    assert pubkeys["attrs"] == {
        "slots": 3, "path": "table", "overflow": 0, "lanes": 2,
        "wide_sets": 0,
    }

    untagged = bls.PublicKey.from_bytes(kps[2].pk.to_bytes())
    mixed = tagged[:1] + [bls.SignatureSet(tagged[1].signature,
                                           [untagged], msg)]
    tb._marshal(mixed, allow_grouped=False)
    (pubkeys,) = tracing.find(
        _newest("verify/marshal/pack"), "verify/marshal/pubkeys"
    )
    assert pubkeys["attrs"] == {
        "slots": 3, "path": "table", "overflow": 1, "lanes": 2,
        "wide_sets": 0,
    }

    bare = [bls.SignatureSet(tagged[1].signature, [untagged], msg)]
    tb._marshal(bare, allow_grouped=False)
    (pubkeys,) = tracing.find(
        _newest("verify/marshal/pack"), "verify/marshal/pubkeys"
    )
    assert pubkeys["attrs"] == {
        "slots": 1, "path": "packed", "overflow": 0, "lanes": 1,
        "wide_sets": 0,
    }


def _slots():
    fam = REGISTRY.get("lighthouse_tpu_pubkey_slots_total")
    return {
        path: fam.labels(path).value
        for path in ("table", "overflow", "packed", "padding")
    }


@pytest.mark.parametrize(
    "width, lanes, wide_sets, shape, padding",
    [
        # every set fits a lane: one lane a set, the s4k4 grid of today
        (4, 3, 0, "s4k4e8", 4 * 4 - 8),
        # 2-key lanes: the 3- and 4-key sets take two each; 8 lanes
        (2, 5, 2, "s4l8k2e8", 8 * 2 - 8),
    ],
)
def test_pubkeys_span_and_counter_name_the_lanes(
    width, lanes, wide_sets, shape, padding, monkeypatch
):
    monkeypatch.setattr(tb, "KEY_LANE_WIDTH", width)
    kps = bls.interop_keypairs(4)
    cache = PubkeyCache()
    cache.import_new(
        NS(validators=[NS(pubkey=kp.pk.to_bytes()) for kp in kps])
    )
    cache.device_table()
    msg = b"verify-spans lanes"
    sets = [
        bls.SignatureSet(
            bls.aggregate_signatures([kp.sk.sign(msg) for kp in kps[:k]]),
            [cache.get(v) for v in range(k)], msg,
        )
        for k in (1, 3, 4)
    ]
    before = _slots()
    m = tb._marshal(sets, allow_grouped=False)
    after = _slots()
    (pubkeys,) = tracing.find(
        _newest("verify/marshal/pack"), "verify/marshal/pubkeys"
    )
    assert pubkeys["attrs"] == {
        "slots": 8, "path": "table", "overflow": 0, "lanes": lanes,
        "wide_sets": wide_sets,
    }
    assert tb._marshal_attrs(m)["shape"] == shape
    assert {k: after[k] - before[k] for k in after} == {
        "table": 8, "overflow": 0, "packed": 0, "padding": padding,
    }


def test_guarded_attempt_nests_under_the_callers_span():
    g = GuardedExecutor()  # watchdog on: the attempt runs on its own thread
    seen = {}

    def attempt(plan):
        seen["thread"] = threading.current_thread().name
        with tracing.span("verify/device"):
            return True

    with tracing.span("bus/batch", batch=-7):
        assert g.dispatch("bls", 1, attempt) is True
    root = _newest("bus/batch")
    assert root["attrs"]["batch"] == -7
    assert [c["name"] for c in root["children"]] == ["verify/device"]
    assert seen["thread"].startswith("device-dispatch-")


def test_late_child_of_a_closed_parent_is_dropped():
    tr = tracing.Tracer(capacity=4)
    opened, parent_closed = threading.Event(), threading.Event()

    with tr.span("bus/batch") as parent:

        def worker():
            with tr.adopt(parent), tr.span("verify/device"):
                opened.set()
                parent_closed.wait(5)

        th = threading.Thread(target=worker)
        th.start()
        assert opened.wait(5)
    parent_closed.set()
    th.join(5)
    assert not th.is_alive()
    (root,) = tr.recent()
    assert root["children"] == []
    assert tr.completed_roots == 1


def test_canary_span_wraps_check_pair():
    canary.check_pair("ref")
    root = _newest("verify/canary")
    assert root["attrs"] == {"backend": "ref"}
    names = {c["name"] for c in root["children"]}
    assert "verify/subgroup_check" in names


def test_one_bus_batch_is_one_tree(clean_guard):
    from lighthouse_tpu.verification_bus import VerificationBus

    GUARD.configure(canary="on")  # watchdog stays on: a second thread
    kp = bls.interop_keypairs(1)[0]
    msg = b"verify-spans bus batch"
    journal = Journal()
    bus = VerificationBus(backend="ref", journal=journal)
    before = TRACER.completed_roots
    assert bus.submit(
        [bls.SignatureSet(kp.sk.sign(msg), [kp.pk], msg)],
        consumer="gossip_single",
    ) is True
    roots = TRACER.recent(TRACER.completed_roots - before)
    assert [r["name"] for r in roots] == ["bus/batch"]
    (root,) = roots
    (event,) = journal.query(kind="signature_batch")
    assert root["attrs"] == {
        "batch": event["attrs"]["bus_batch"],
        "trigger": event["attrs"]["trigger"],
        "live": 1,
        "submissions": 1,
    }
    assert [c["name"] for c in root["children"]] == [
        "verify/canary", "verify",
    ]


def test_tracing_never_imports_jax():
    code = (
        "import sys\n"
        "from lighthouse_tpu.common import tracing\n"
        "with tracing.span('verify/probe'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_spans_are_on_the_profiler_clock(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            time.sleep(0.002)
            with tracing.span("verify/marshal"):
                time.sleep(0.005)
            with tracing.Tracer(enabled=False).span("verify/canary"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                events[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    w0, w1 = events["bench/window"]
    m0, m1 = events["verify/marshal"]
    c0, c1 = events["verify/canary"]
    assert w0 < m0 < m1 <= c0 < c1 < w1
    assert m1 - m0 >= 4e6
