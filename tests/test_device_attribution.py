"""Device-plane flight recorder: per-consumer batch attribution,
padding-waste & amortization accounting, the compile ledger (+ its
/lighthouse/compiles endpoint and JSONL round trip), the consumer-label
lint pass, obs_report's cross-node timeline mode, and the notifier's
per-consumer throughput line.

Device dispatch is STUBBED throughout (the marshal layer runs for real;
the jitted call is replaced) so the flat / grouped / sharded / N=1
fallback paths all exercise their attribution without paying a single
XLA compile — tier-1 budget discipline."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from lighthouse_tpu import bls
from lighthouse_tpu.bls import tpu_backend
from lighthouse_tpu.common import device_attribution as attribution
from lighthouse_tpu.common.compile_ledger import (
    CompileLedger,
    LEDGER,
    load_jsonl,
)
from lighthouse_tpu.common.events_journal import Journal
from lighthouse_tpu.common.metrics import REGISTRY


def _val(name, labels=None):
    return REGISTRY.get_value(name, labels)


def _mk_sets(n, shared_message=False, seed=0):
    kps = bls.interop_keypairs(n + seed)[seed:]
    out = []
    for i, kp in enumerate(kps):
        msg = b"shared-msg" if shared_message else b"msg-%d" % i
        out.append(bls.SignatureSet(kp.sk.sign(msg), [kp.pk], msg))
    return out


@pytest.fixture
def stub_dispatch(monkeypatch):
    """Replace the device compile + dispatch with an always-true stub;
    marshal (bucketing, masks, waste accounting inputs) still runs for
    real."""
    monkeypatch.setattr(tpu_backend, "_compile_ahead", lambda *a: 0.0)
    monkeypatch.setattr(
        tpu_backend, "_dispatch", lambda m, program: np.True_
    )


# ------------------------------------------------- flat marshal path


def test_flat_batch_attribution_and_waste(stub_dispatch):
    sets = _mk_sets(3)  # distinct messages -> flat marshal, s_bucket=4
    j = Journal()
    before_sets = _val(
        "lighthouse_tpu_device_sets_total", ("bench",)
    )
    before_batches = _val(
        "lighthouse_tpu_device_batches_total", ("bench", "bls", "4")
    )
    before_waste = _val(
        "lighthouse_tpu_device_waste_lanes_total", ("bench", "bls")
    )
    before_live = _val(
        "lighthouse_tpu_device_live_lanes_total", ("bench", "bls")
    )
    assert bls.verify_signature_sets(
        sets, backend="tpu", consumer="bench", journal=j, slot=9
    )
    assert (
        _val("lighthouse_tpu_device_sets_total", ("bench",))
        == before_sets + 3
    )
    assert (
        _val(
            "lighthouse_tpu_device_batches_total", ("bench", "bls", "4")
        )
        == before_batches + 1
    )
    # padding-waste accounting: 4 bucket lanes - 3 live sets = 1
    assert (
        _val(
            "lighthouse_tpu_device_waste_lanes_total", ("bench", "bls")
        )
        == before_waste + 1
    )
    assert (
        _val("lighthouse_tpu_device_live_lanes_total", ("bench", "bls"))
        == before_live + 3
    )
    assert (
        _val(
            "lighthouse_tpu_device_padding_waste_lanes",
            ("bench", "bls"),
        )
        == 1
    )
    # fixed-cost amortization: 90 ms / 3 live sets
    assert _val(
        "lighthouse_tpu_device_amortized_fixed_ms", ("bench", "bls")
    ) == pytest.approx(30.0)
    # the journal event carries the exact economics
    (ev,) = j.query(kind="signature_batch")
    assert ev["slot"] == 9 and ev["outcome"] == "ok"
    attrs = ev["attrs"]
    assert attrs["consumer"] == "bench"
    assert attrs["n_sets"] == 3
    assert attrs["lanes"] == 4 and attrs["waste"] == 1
    assert attrs["amortized_fixed_ms"] == pytest.approx(30.0)


def test_grouped_marshal_attribution(stub_dispatch):
    # one shared message across 3 sets -> grouped grid (1 group x 4
    # lanes): same lane count, marshalled through the grouped path
    sets = _mk_sets(3, shared_message=True)
    m = tpu_backend._marshal(sets)
    assert m.grouped and m.s_bucket == 4
    j = Journal()
    assert bls.verify_signature_sets(
        sets, backend="tpu", consumer="oppool", journal=j
    )
    (ev,) = j.query(kind="signature_batch")
    assert ev["attrs"]["lanes"] == 4
    assert ev["attrs"]["waste"] == 1
    assert ev["attrs"]["consumer"] == "oppool"


def test_individual_fallback_attribution(monkeypatch):
    sets = _mk_sets(3)
    stub = lambda *a: np.ones(4, dtype=bool)  # noqa: E731
    monkeypatch.setattr(
        tpu_backend, "_get_individual_fns", lambda: (stub, stub)
    )
    monkeypatch.setattr(tpu_backend, "_compile_ahead", lambda *a: 0.0)
    j = Journal()
    before = _val(
        "lighthouse_tpu_device_batches_total",
        ("slasher", "bls", "4"),
    )
    out = bls.verify_signature_sets_individually(
        sets, backend="tpu", consumer="slasher", journal=j
    )
    assert out == [True, True, True]
    assert (
        _val(
            "lighthouse_tpu_device_batches_total",
            ("slasher", "bls", "4"),
        )
        == before + 1
    )
    (ev,) = j.query(kind="signature_batch")
    assert ev["attrs"]["individual"] is True
    assert ev["attrs"]["lanes"] == 4 and ev["attrs"]["waste"] == 1


def test_streamed_batches_attribution(stub_dispatch):
    batches = [_mk_sets(2), [], _mk_sets(1, seed=4)]
    j = Journal()
    before = _val("lighthouse_tpu_device_sets_total", ("oppool",))
    out = bls.verify_signature_set_batches(
        batches, backend="tpu", consumer="oppool", journal=j
    )
    assert out == [True, False, True]
    # per-batch journal events for the non-empty batches only
    evs = j.query(kind="signature_batch")
    assert [e["attrs"]["n_sets"] for e in evs] == [2, 1]
    assert all(e["attrs"]["streamed"] for e in evs)
    assert (
        _val("lighthouse_tpu_device_sets_total", ("oppool",))
        == before + 3
    )


def test_sharded_wrapper_attribution():
    from lighthouse_tpu.parallel.sharded_verify import _wrap_attributed

    calls = []

    class _Inner(_FakeJit):  # a jitted verify, as the ledger reads it
        def __call__(self, *a):
            calls.append(a)
            return np.True_

    fn = _wrap_attributed(_Inner(), "sharded_verify", "flat", "bench")
    set_mask = np.array([True, True, False, False])
    before = _val(
        "lighthouse_tpu_device_batches_total", ("bench", "sharded", "4")
    )
    out = fn(1, 2, 3, 4, 5, set_mask)
    assert bool(np.asarray(out)) and len(calls) == 1
    assert (
        _val(
            "lighthouse_tpu_device_batches_total",
            ("bench", "sharded", "4"),
        )
        == before + 1
    )
    # 4 lanes - 2 live = 2 wasted
    assert (
        _val(
            "lighthouse_tpu_device_padding_waste_lanes",
            ("bench", "sharded"),
        )
        == 2
    )
    # the dispatch landed in the compile ledger
    assert any(
        e["fn"] == "sharded_verify" and e["shape"] == "lanes4"
        for e in LEDGER.entries()
    )


def test_host_backends_count_without_lanes():
    sets = _mk_sets(2)
    before = _val(
        "lighthouse_tpu_device_batches_total",
        ("gossip_single", "bls", "host"),
    )
    assert bls.verify_signature_sets(
        sets, backend="fake", consumer="gossip_single"
    )
    assert bls.verify_signature_sets(
        sets, backend="ref", consumer="gossip_single"
    )
    assert (
        _val(
            "lighthouse_tpu_device_batches_total",
            ("gossip_single", "bls", "host"),
        )
        == before + 2
    )


def test_unknown_consumer_fails_loud():
    sets = _mk_sets(1)
    with pytest.raises(ValueError, match="unknown device-plane"):
        bls.verify_signature_sets(sets, backend="fake", consumer="oops")
    with pytest.raises(ValueError):
        attribution.note_batch("nope", "bls", lanes=4, live=1)


# ---------------------------------------------------- compile ledger


class _FakeJit:
    def __init__(self):
        self._size = 0

    def _cache_size(self):
        return self._size


def test_compile_ledger_cold_warm_and_round_trip(tmp_path):
    path = tmp_path / "ledger.jsonl"
    ledger = CompileLedger(capacity=16, path=str(path))
    jit = _FakeJit()
    jit._size = 1  # the first dispatch traced+compiled a shape class
    grew = ledger.note_dispatch(
        "verify", jit, ("xla",), "s4k1", duration_s=1.25
    )
    assert grew == 1
    assert ledger.note_dispatch("verify", jit, ("xla",), "s4k1", 0.001) == 0
    jit._size = 2  # new shape bucket -> retrace
    assert ledger.note_dispatch("verify", jit, ("xla",), "s8k1", 2.5) == 1
    entries = ledger.entries()
    assert [e["event"] for e in entries] == ["cold", "warm", "cold"]
    assert entries[0]["impl_key"] == "('xla',)"
    assert entries[0]["duration_s"] == pytest.approx(1.25)
    stats = ledger.stats()
    assert stats["recorded"] == 3 and stats["cold"] == 2
    # persistent JSONL round trip: COLD entries only (warm dispatches
    # are the timed hot path and never pay file I/O)
    persisted = load_jsonl(str(path))
    assert persisted == [e for e in entries if e["event"] == "cold"]
    # an ahead-of-dispatch compile is the bucket's cold entry; the
    # dispatch that follows adds the jit-cache entry and records warm
    # (two compiled before either dispatches: both dispatches are warm)
    ledger.note_compile("verify", jit, ("xla",), "s16k1", 4.0)
    ledger.note_compile("verify", jit, ("xla",), "s32k1", 5.0)
    jit._size = 3
    assert ledger.note_dispatch("verify", jit, ("xla",), "s16k1", 0.01) == 0
    jit._size = 4
    assert ledger.note_dispatch("verify", jit, ("xla",), "s32k1", 0.01) == 0
    assert [e["event"] for e in ledger.entries()[-4:]] == [
        "cold", "cold", "warm", "warm"
    ]


def test_compile_ledger_http_endpoint():
    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.http_api.server import BeaconApiServer
    from lighthouse_tpu.types.spec import minimal_spec

    LEDGER.record("verify", ("xla",), "s4k1", "cold", 3.25)
    spec = minimal_spec()
    h = Harness(spec, 8)
    chain = BeaconChain(h.state.copy(), spec, backend="fake")
    srv = BeaconApiServer(chain)
    doc = srv.handle_get("/lighthouse/compiles")
    assert doc["meta"]["recorded"] >= 1
    assert any(
        e["fn"] == "verify" and e["event"] == "cold"
        for e in doc["data"]
    )
    limited = srv.handle_get("/lighthouse/compiles?limit=1")
    assert len(limited["data"]) == 1


# ------------------------------------------------ consumer-label lint


def _lint(src: str):
    from lighthouse_tpu.analysis.core import Module
    from lighthouse_tpu.analysis.passes.consumer_label import (
        ConsumerLabelPass,
    )

    mod = Module(Path("x.py"), "x.py", src)
    return list(ConsumerLabelPass().run([mod]))


def test_consumer_label_pass_fires_on_missing_keyword():
    findings = _lint(
        "from lighthouse_tpu import bls\n"
        "def f(sets):\n"
        "    return bls.verify_signature_sets(sets, backend='tpu')\n"
    )
    assert len(findings) == 1
    assert "consumer=" in findings[0].msg


def test_consumer_label_pass_accepts_explicit_keyword():
    assert not _lint(
        "from lighthouse_tpu import bls, kzg\n"
        "def f(sets, blobs):\n"
        "    bls.verify_signature_sets(sets, consumer='oppool')\n"
        "    bls.verify_signature_sets_individually(\n"
        "        sets, consumer=None)\n"
        "    kzg.verify_blob_kzg_proof_batch(\n"
        "        blobs, blobs, blobs, consumer='kzg')\n"
    )


def test_consumer_label_pass_exempts_raw_graph_namespace():
    assert not _lint(
        "from lighthouse_tpu.ops import batch_verify\n"
        "def f(*args):\n"
        "    return batch_verify.verify_signature_sets(*args)\n"
    )


def test_consumer_label_pass_rejects_kwargs_splat():
    findings = _lint(
        "from lighthouse_tpu import bls\n"
        "def f(sets, **kw):\n"
        "    return bls.verify_signature_sets(sets, **kw)\n"
    )
    assert len(findings) == 1


def test_package_is_consumer_label_clean():
    """The production package carries zero consumer-label findings —
    attribution cannot silently regress (the full lint gate re-checks
    this with the baseline; this is the targeted fast check)."""
    from lighthouse_tpu.analysis.core import iter_modules
    from lighthouse_tpu.analysis.passes.consumer_label import (
        ConsumerLabelPass,
    )

    root = Path(__file__).resolve().parents[1] / "lighthouse_tpu"
    modules, parse_findings = iter_modules(root)
    assert not parse_findings
    findings = list(ConsumerLabelPass().run(modules))
    assert findings == []


# ----------------------------------------------- obs_report timelines


def _obs_report():
    import importlib.util

    path = (
        Path(__file__).resolve().parents[1] / "scripts" / "obs_report.py"
    )
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_timeline_merge_lag_and_amplification(tmp_path):
    obs = _obs_report()
    root = "0x" + "ab" * 32
    node0 = [
        {
            "seq": 2, "t": 50.0, "kind": "signature_batch", "slot": 7,
            "outcome": "ok",
            "attrs": {
                "consumer": "gossip_single", "n_sets": 5, "lanes": 8,
                "waste": 3,
            },
        },
        {
            "seq": 3, "t": 50.01, "kind": "block_import", "slot": 7,
            "root": root, "outcome": "imported", "duration_s": 0.02,
        },
    ]
    node1 = [
        {
            "seq": 1, "t": 50.25, "kind": "block_import", "slot": 7,
            "root": root, "outcome": "imported", "duration_s": 0.03,
        },
        {
            "seq": 2, "t": 50.30, "kind": "block_import", "slot": 7,
            "root": root, "outcome": "duplicate",
        },
    ]
    timelines = obs.build_timelines({"n0": node0, "n1": node1})
    tl = timelines[root]
    assert tl["producer"] == "n0" and tl["slot"] == 7
    assert tl["nodes"]["n1"]["lag_s"] == pytest.approx(0.24)
    assert tl["nodes"]["n1"]["deliveries"] == 2
    # the producer's verify batch is correlated by slot, with lanes/waste
    (batch,) = tl["nodes"]["n0"]["verify_batches"]
    assert batch["consumer"] == "gossip_single"
    assert batch["lanes"] == 8 and batch["waste"] == 3
    stats = obs.timeline_population_stats(timelines)
    assert stats["blocks"] == 1
    assert stats["lag_p50_s"] == pytest.approx(0.24)
    assert stats["amplification_mean"] == pytest.approx(1.5)
    report = obs.render_timeline_report({"n0": node0, "n1": node1})
    assert "population:" in report and "gossip_single" in report
    # the JSONL loader round-trips a raw journal export
    p = tmp_path / "journal_n0.jsonl"
    p.write_text("\n".join(json.dumps(e) for e in node0) + "\n")
    assert obs.load_journal_jsonl(str(p)) == node0


# -------------------------------------------- attribution invariant


def test_attribution_complete_invariant_unit(monkeypatch):
    from lighthouse_tpu.sim import invariants as inv

    class _SN:
        def __init__(self):
            self.index = 0
            self.online = True
            self.journal_archives = [
                [
                    {
                        "kind": "signature_batch",
                        "attrs": {"consumer": "sync_segment", "n_sets": 4},
                    }
                ]
            ]

    events = [
        {
            "kind": "signature_batch",
            "attrs": {"consumer": "gossip_single", "n_sets": 6},
        }
    ]
    key_g = 'lighthouse_tpu_device_sets_total{consumer="gossip_single"}'
    key_s = 'lighthouse_tpu_device_sets_total{consumer="sync_segment"}'
    ctx = inv.SimContext(
        scenario=None,
        nodes={"n0": _SN()},
        snapshot_before={},
        snapshot_after={key_g: 6.0, key_s: 4.0},
        blob_blocks={},
        eclipse_windows={},
    )
    ctx.events = lambda name, **q: list(events)
    ctx.health = lambda name: {"journal": {"dropped": 0}}
    assert inv.attribution_complete(ctx) == []
    # a registry/journal mismatch is a violation
    ctx.snapshot_after = {key_g: 9.0, key_s: 4.0}
    assert any(
        "gossip_single" in v for v in inv.attribution_complete(ctx)
    )
    # an unlabeled batch is a violation
    ctx.snapshot_after = {key_g: 6.0, key_s: 4.0}
    events.append({"kind": "signature_batch", "attrs": {"n_sets": 1}})
    assert any(
        "lack a consumer label" in v
        for v in inv.attribution_complete(ctx)
    )
    events.pop()
    # TWO-sided: a consumer present ONLY in the registry (its call
    # sites lost journal threading entirely) must still be caught
    key_sl = 'lighthouse_tpu_device_sets_total{consumer="slasher"}'
    ctx.snapshot_after = {key_g: 6.0, key_s: 4.0, key_sl: 3.0}
    assert any(
        "journal threading lost" in v
        for v in inv.attribution_complete(ctx)
    )


# ------------------------------------------------------- notifier


def test_notifier_per_consumer_throughput():
    from lighthouse_tpu.notifier import Notifier

    n = Notifier(chain=None)
    assert n.consumer_throughput() == []  # first tick: no baseline
    attribution.note_sets("sidecar_header", 50)
    time.sleep(0.02)
    top = n.consumer_throughput()
    assert top and top[0][0] == "sidecar_header" and top[0][1] > 0
