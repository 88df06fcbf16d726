#!/usr/bin/env python
"""Render p50/p99 stage reports from the Prometheus histogram families,
and merge multi-node journals into per-object causal timelines.

The bench/chaos assertion tool: takes a `/metrics` text exposition —
from a live node (``--url http://127.0.0.1:5052/metrics``), a dump file
(``--file metrics.txt``), or stdin — parses every histogram family, and
reports count / mean / p50 / p99 per labeled series, Prometheus
`histogram_quantile`-style (linear interpolation inside the owning
cumulative bucket). This is how a load test or chaos run turns the
registry's `*_stage_seconds` / `*_request_seconds` histograms into the
"p50/p99 from the existing histograms" number the ROADMAP's serving
plane asks for, with no Prometheus server in the loop.

Multi-node mode (``--timeline``): merge per-node lifecycle journals —
live nodes' ``GET /lighthouse/events`` (``--node-url``, repeatable)
and/or raw ``--journal-jsonl`` exports (``--journal``, repeatable) —
into per-block-root causal timelines: which node produced root X (first
import), the gossip receipt lag on every other node, the redelivery
(duplicate) count, the consumer-attributed verify batch (journal seq =
batch id, lanes, padding waste), and the import latency — plus the
POPULATION metrics the 100+-node simulator item needs: gossip
propagation-lag p50/p99 and the mean gossip amplification factor
(deliveries per importing node). Timelines need wall-clock timestamps,
so the inputs are RAW journals (the sim's canonical replay journals
strip `t` by design — export raw ones with `bn --journal-jsonl` or
read live nodes).

Counter mode (``--counters``): the non-histogram families — every
plain counter/gauge series, labels expanded — rendered as a sorted
value table. This is how the DA sampling plane's `da_*` families
(samples by outcome, withholding flags, column/cell batch counts,
custody gauges) read out of a scrape: ``--counters --family
lighthouse_tpu_da`` is the post-run DAS audit view.

Importable pieces (used by tests and bench tooling):
  parse_histograms(text)   -> {(name, labels): {"buckets", "sum", "count"}}
  parse_counters(text)     -> {(name, labels): value}
  bucket_quantile(buckets, count, q) -> float | None
  render_report(text, family_filter=None) -> str
  render_counter_report(text, family_filter=None) -> str
  render_slot_budget(doc, waterfalls=6) -> str   (--slot-budget mode)
  build_timelines({node: [event, ...]}) -> {root: timeline}
  timeline_population_stats(timelines) -> dict
  render_timeline_report({node: [event, ...]}) -> str
"""

import argparse
import json
import math
import re
import sys

_SERIES_RE = re.compile(
    r"^(?P<name>[A-Za-z_:][A-Za-z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?\s+(?P<value>[^\s]+)$"
)
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')
_ESCAPE_RE = re.compile(r"\\(.)")


def _unescape(v: str) -> str:
    # single pass, so '\\n' (escaped backslash + n) stays backslash+n
    # instead of being mangled by sequential replaces
    return _ESCAPE_RE.sub(
        lambda m: "\n" if m.group(1) == "n" else m.group(1), v
    )


def _parse_labels(raw: str) -> dict:
    if not raw:
        return {}
    return {k: _unescape(v) for k, v in _LABEL_RE.findall(raw)}


def parse_histograms(text: str) -> dict:
    """Prometheus text exposition -> histogram series.

    Returns {(family, labels_tuple): {"buckets": [(le, cum_count)...],
    "sum": float, "count": int}} where labels_tuple excludes `le` and is
    a sorted (key, value) tuple."""
    out: dict = {}

    def entry(family, labels: dict):
        key = (family, tuple(sorted(labels.items())))
        return out.setdefault(
            key, {"buckets": [], "sum": 0.0, "count": 0}
        )

    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SERIES_RE.match(line)
        if m is None:
            continue
        name = m.group("name")
        labels = _parse_labels(m.group("labels") or "")
        try:
            value = float(m.group("value"))
        except ValueError:
            continue
        if name.endswith("_bucket") and "le" in labels:
            le_raw = labels.pop("le")
            le = math.inf if le_raw == "+Inf" else float(le_raw)
            entry(name[: -len("_bucket")], labels)["buckets"].append(
                (le, value)
            )
        elif name.endswith("_sum"):
            entry(name[: -len("_sum")], labels)["sum"] = value
        elif name.endswith("_count"):
            entry(name[: -len("_count")], labels)["count"] = int(value)
    # only keep series that actually look like histograms
    return {
        k: v for k, v in out.items() if v["buckets"] and v["count"]
    }


def parse_counters(text: str) -> dict:
    """Prometheus text exposition -> plain (counter/gauge) series:
    {(family, labels_tuple): value}. Histogram components are excluded
    — `_bucket` series always, and `_sum`/`_count` series whose base
    family actually exposes buckets (a counter legitimately named
    `*_total_count` without buckets still renders)."""
    hist_families = {
        m.group("name")[: -len("_bucket")]
        for m in (
            _SERIES_RE.match(line.strip()) for line in text.splitlines()
        )
        if m
        and m.group("name").endswith("_bucket")
        and "le" in _parse_labels(m.group("labels") or "")
    }
    out: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SERIES_RE.match(line)
        if m is None:
            continue
        name = m.group("name")
        if name.endswith("_bucket"):
            continue
        for suffix in ("_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in hist_families:
                break
        else:
            try:
                value = float(m.group("value"))
            except ValueError:
                continue
            labels = _parse_labels(m.group("labels") or "")
            out[(name, tuple(sorted(labels.items())))] = value
    return out


def counter_rows(text: str, family_filter: str | None = None) -> list:
    """[(series_label, value)] sorted by family then descending value."""
    rows = []
    for (family, labels), value in parse_counters(text).items():
        if family_filter and family_filter not in family:
            continue
        label_str = ",".join(f"{k}={v}" for k, v in labels)
        series = family + (f"{{{label_str}}}" if label_str else "")
        rows.append((series, value))
    rows.sort(key=lambda r: (r[0].split("{")[0], -r[1]))
    return rows


def render_counter_report(
    text: str, family_filter: str | None = None
) -> str:
    rows = counter_rows(text, family_filter)
    if not rows:
        return "no counter/gauge series matched\n"
    width = max(len(r[0]) for r in rows)
    lines = [f"{'series':<{width}}  {'value':>12}"]
    for series, value in rows:
        v = f"{int(value)}" if value == int(value) else f"{value:.6g}"
        lines.append(f"{series:<{width}}  {v:>12}")
    return "\n".join(lines) + "\n"


def bucket_quantile(buckets, count: int, q: float):
    """Quantile from cumulative le-buckets, histogram_quantile-style:
    find the owning bucket and interpolate linearly inside it. Returns
    None for an empty series; a quantile landing in the +Inf bucket
    reports the highest finite bound (the histogram cannot resolve
    beyond its buckets)."""
    if count <= 0 or not buckets:
        return None
    buckets = sorted(buckets)
    target = q * count
    prev_le, prev_cum = 0.0, 0.0
    highest_finite = 0.0
    for le, cum in buckets:
        if not math.isinf(le):
            highest_finite = le
        if cum >= target:
            if math.isinf(le):
                return highest_finite
            span = cum - prev_cum
            if span <= 0:
                return le
            frac = (target - prev_cum) / span
            return prev_le + (le - prev_le) * frac
        if not math.isinf(le):
            prev_le, prev_cum = le, cum
    return highest_finite


def report_rows(text: str, family_filter: str | None = None) -> list:
    """[(series_label, count, mean, p50, p99)] sorted by family then
    descending count."""
    rows = []
    for (family, labels), h in parse_histograms(text).items():
        if family_filter and family_filter not in family:
            continue
        label_str = ",".join(f"{k}={v}" for k, v in labels)
        series = family + (f"{{{label_str}}}" if label_str else "")
        count = h["count"]
        rows.append(
            (
                series,
                count,
                h["sum"] / count if count else 0.0,
                bucket_quantile(h["buckets"], count, 0.50),
                bucket_quantile(h["buckets"], count, 0.99),
            )
        )
    rows.sort(key=lambda r: (r[0].split("{")[0], -r[1]))
    return rows


def _fmt(v) -> str:
    if v is None:
        return "-"
    if v >= 1:
        return f"{v:.3f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.2f}ms"
    return f"{v * 1e6:.1f}us"


def render_report(text: str, family_filter: str | None = None) -> str:
    rows = report_rows(text, family_filter)
    if not rows:
        return "no histogram series matched\n"
    width = max(len(r[0]) for r in rows)
    lines = [
        f"{'series':<{width}}  {'count':>8}  {'mean':>9}  "
        f"{'p50':>9}  {'p99':>9}"
    ]
    for series, count, mean, p50, p99 in rows:
        lines.append(
            f"{series:<{width}}  {count:>8}  {_fmt(mean):>9}  "
            f"{_fmt(p50):>9}  {_fmt(p99):>9}"
        )
    return "\n".join(lines) + "\n"


# --------------------------------------------------- slot-budget waterfalls


def fetch_slot_budget(base_url: str) -> dict:
    """The slot-budget document from a live node. Accepts the node base
    URL, its /metrics scrape URL, or the endpoint itself."""
    from urllib.request import urlopen

    url = base_url.rstrip("/")
    if url.endswith("/metrics"):
        url = url[: -len("/metrics")]
    if not url.endswith("/lighthouse/slot_budget"):
        url += "/lighthouse/slot_budget"
    with urlopen(url, timeout=10) as r:
        doc = json.loads(r.read())
    return doc.get("data", doc)


def _bar(start_s, end_s, wall_s, width, ch="#") -> str:
    """One proportional interval bar on a `width`-char canvas."""
    if wall_s <= 0:
        return " " * width
    a = int(round(start_s / wall_s * width))
    b = int(round(end_s / wall_s * width))
    a = max(0, min(width - 1, a))
    b = max(a + 1, min(width, b))
    return " " * a + ch * (b - a) + " " * (width - b)


def render_slot_budget(doc: dict, waterfalls: int = 6,
                       width: int = 48) -> str:
    """The /lighthouse/slot_budget document as text: the per-stage
    quantile table, then proportional per-import waterfalls — stage
    bars (#) over the import wall with the device round trips (=) and
    the accounting line beneath each."""
    lines = []
    lines.append(
        "slot budget: {n} recent imports (of {total} recorded), "
        "budget {budget:g}ms, wall p50={p50} p99={p99}, "
        "fusable gap p50={gap}, serial dispatches p50={sd} "
        "max={sdmax}".format(
            n=doc.get("imports", 0),
            total=doc.get("recorded_total", 0),
            budget=doc.get("budget_ms", 0.0),
            p50=_fmt(doc.get("wall_p50_s")),
            p99=_fmt(doc.get("wall_p99_s")),
            gap=_fmt(doc.get("fusable_gap_p50_s")),
            sd=doc.get("serial_dispatches_p50"),
            sdmax=doc.get("serial_dispatches_max"),
        )
    )
    if "fused_imports" in doc:
        # one-dispatch-slot ledger: chained slot-program imports vs
        # imports that paid separate serial round trips
        lines.append(
            "dispatch mode: {f} fused (chained slot-program), "
            "{s} serial".format(
                f=doc.get("fused_imports", 0),
                s=doc.get("serial_dispatch_imports", 0),
            )
        )
    stages = doc.get("stages") or {}
    if stages:
        name_w = max(len(n) for n in stages)
        lines.append("")
        lines.append(
            f"{'stage':<{name_w}}  {'count':>6}  {'p50':>9}  {'p99':>9}"
        )
        for name, s in stages.items():
            lines.append(
                f"{name:<{name_w}}  {s['count']:>6}  "
                f"{_fmt(s['p50_s']):>9}  {_fmt(s['p99_s']):>9}"
            )
    recent = (doc.get("recent") or [])[-waterfalls:]
    for r in recent:
        wall = r.get("wall_s") or 0.0
        lines.append("")
        lines.append(
            "import {root}… slot={slot} path={path} {outcome} "
            "wall={wall} serial={sd} gap={gap}".format(
                root=(r.get("root") or "?")[:18],
                slot=r.get("slot"),
                path=r.get("path"),
                outcome=r.get("outcome"),
                wall=_fmt(wall),
                sd=r.get("serial_dispatches"),
                gap=_fmt(r.get("fusable_gap_s")),
            )
        )
        rows = [
            (name, s, e, "#")
            for name, s, e in (r.get("stages") or [])
        ] + [
            (
                # fused dispatches (the chained slot-program) are the
                # one-dispatch slot's signature — make them readable
                # at a glance in the waterfall
                f"dev:{d.get('label')}"
                + ("[fused]" if d.get("kind") == "fused" else ""),
                d.get("start_s", 0.0),
                d.get("end_s", 0.0),
                "=",
            )
            for d in (r.get("dispatches") or [])
        ]
        if rows:
            name_w = max(len(n) for n, *_ in rows)
            for name, s, e, ch in rows:
                lines.append(
                    f"  {name:<{name_w}} |{_bar(s, e, wall, width, ch)}|"
                    f" {_fmt(max(0.0, e - s)):>9}"
                )
        lines.append(
            "  accounted: stages(union)={u} overlap={o} "
            "unattributed={ua} bus_wait={bw} device={dv}".format(
                u=_fmt(r.get("union_s")),
                o=_fmt(r.get("overlap_s")),
                ua=_fmt(r.get("unattributed_s")),
                bw=_fmt(r.get("bus_wait_s")),
                dv=_fmt(r.get("device_s")),
            )
        )
    return "\n".join(lines) + "\n"


# --------------------------------------------------- cross-node timelines


def load_journal_jsonl(path) -> list:
    """Raw journal export (Journal.export_jsonl / to_jsonl lines) ->
    event dicts; malformed lines are skipped so a torn tail can't kill
    the report. (Near-twin of compile_ledger.load_jsonl, duplicated on
    purpose: this script stays importable standalone against any dump,
    and a user-passed --journal path that does not exist should raise,
    where a maybe-absent ledger file should not.)"""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def fetch_node_events(base_url: str) -> list:
    """Every journaled event from a live node's observability plane."""
    from urllib.request import urlopen

    url = base_url.rstrip("/") + "/lighthouse/events"
    with urlopen(url, timeout=10) as r:
        return json.loads(r.read())["data"]


def _percentile(values, q: float):
    if not values:
        return None
    values = sorted(values)
    idx = min(len(values) - 1, int(q * (len(values) - 1) + 0.5))
    return values[idx]


def build_timelines(events_by_node: dict) -> dict:
    """Merge per-node journals into per-block-root causal timelines.

    Returns {root_hex: {"slot", "producer", "produced_t", "nodes":
    {node: {"import_t", "lag_s", "deliveries", "outcome",
    "import_duration_s", "verify_batches": [...]}}}}.

    The producing node is the one with the EARLIEST successful import
    (a producer imports its own block before gossip fans out); every
    other node's receipt lag is measured against that. `deliveries`
    counts every journaled arrival (import + duplicate outcomes) — the
    per-node amplification numerator. `verify_batches` are the node's
    consumer-attributed `signature_batch` events at the block's slot
    (the journal seq is the batch id; tpu batches carry lanes/waste)."""
    timelines: dict = {}
    for node, events in sorted(events_by_node.items()):
        # slot -> verify batches on this node (batch events are
        # slot-correlated, not root-correlated: one bulk batch can span
        # many blocks)
        batches_by_slot: dict = {}
        for ev in events:
            if ev.get("kind") != "signature_batch":
                continue
            attrs = ev.get("attrs") or {}
            doc = {
                "batch_id": ev.get("seq"),
                "consumer": attrs.get("consumer"),
                "n_sets": attrs.get("n_sets"),
            }
            for k in ("lanes", "waste", "amortized_fixed_ms"):
                if attrs.get(k) is not None:
                    doc[k] = attrs[k]
            batches_by_slot.setdefault(ev.get("slot"), []).append(doc)
        for ev in events:
            if ev.get("kind") != "block_import":
                continue
            root = ev.get("root")
            if root is None:
                continue
            tl = timelines.setdefault(
                root, {"slot": ev.get("slot"), "nodes": {}}
            )
            doc = tl["nodes"].setdefault(
                node, {"deliveries": 0, "verify_batches": []}
            )
            doc["deliveries"] += 1
            if ev.get("outcome") == "imported":
                doc["import_t"] = ev.get("t")
                doc["outcome"] = "imported"
                if ev.get("duration_s") is not None:
                    doc["import_duration_s"] = ev["duration_s"]
                if ev.get("slot") is not None:
                    tl["slot"] = ev["slot"]
                doc["verify_batches"] = batches_by_slot.get(
                    ev.get("slot"), []
                )
            elif "outcome" not in doc:
                doc["outcome"] = ev.get("outcome")
    for root, tl in timelines.items():
        imported = {
            n: d["import_t"]
            for n, d in tl["nodes"].items()
            if d.get("import_t") is not None
        }
        if not imported:
            tl["producer"] = None
            continue
        producer = min(imported, key=imported.get)
        tl["producer"] = producer
        tl["produced_t"] = imported[producer]
        for n, d in tl["nodes"].items():
            if d.get("import_t") is not None:
                d["lag_s"] = d["import_t"] - tl["produced_t"]
    return timelines


def timeline_population_stats(timelines: dict) -> dict:
    """Population metrics over every root: gossip propagation-lag
    distribution (non-producer receipt lags), import latency
    distribution, and the mean amplification factor (journaled
    deliveries per importing node — 1.0 == each block arrived exactly
    once everywhere)."""
    lags, durations, amps = [], [], []
    for tl in timelines.values():
        producer = tl.get("producer")
        importing = 0
        deliveries = 0
        for node, d in tl["nodes"].items():
            if d.get("import_t") is not None:
                importing += 1
                deliveries += d["deliveries"]
                if node != producer and d.get("lag_s") is not None:
                    lags.append(d["lag_s"])
            if d.get("import_duration_s") is not None:
                durations.append(d["import_duration_s"])
        if importing:
            amps.append(deliveries / importing)
    return {
        "blocks": len(timelines),
        "lag_samples": len(lags),
        "lag_p50_s": _percentile(lags, 0.50),
        "lag_p99_s": _percentile(lags, 0.99),
        "lag_max_s": _percentile(lags, 1.0),
        "import_p50_s": _percentile(durations, 0.50),
        "import_p99_s": _percentile(durations, 0.99),
        "amplification_mean": (
            round(sum(amps) / len(amps), 3) if amps else None
        ),
    }


def render_timeline_report(events_by_node: dict) -> str:
    timelines = build_timelines(events_by_node)
    if not timelines:
        return "no block_import events in the merged journals\n"
    lines = []
    ordered = sorted(
        timelines.items(), key=lambda kv: (kv[1].get("slot") or 0, kv[0])
    )
    for root, tl in ordered:
        lines.append(
            f"block {root[:18]}… slot={tl.get('slot')} "
            f"producer={tl.get('producer')}"
        )
        for node, d in sorted(tl["nodes"].items()):
            lag = d.get("lag_s")
            lag_s = "-" if lag is None else f"{lag * 1e3:8.1f}ms"
            batches = ", ".join(
                "#{batch_id} {consumer} n={n_sets}".format(**b)
                + (
                    f" lanes={b['lanes']} waste={b['waste']}"
                    if b.get("lanes") is not None
                    else ""
                )
                for b in d.get("verify_batches", [])
            )
            lines.append(
                f"  {node:<12} {d.get('outcome', '-'):<10} "
                f"lag={lag_s} deliveries={d['deliveries']}"
                + (f"  verify[{batches}]" if batches else "")
            )
    stats = timeline_population_stats(timelines)
    lines.append("")
    lines.append(
        "population: blocks={blocks} lag_p50={p50} lag_p99={p99} "
        "import_p50={ip50} amplification={amp}".format(
            blocks=stats["blocks"],
            p50=_fmt(stats["lag_p50_s"]),
            p99=_fmt(stats["lag_p99_s"]),
            ip50=_fmt(stats["import_p50_s"]),
            amp=stats["amplification_mean"],
        )
    )
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="p50/p99 stage report from a /metrics exposition"
    )
    src = ap.add_mutually_exclusive_group()
    src.add_argument(
        "--url", help="scrape a live node (e.g. http://127.0.0.1:5052/metrics)"
    )
    src.add_argument("--file", help="read a saved exposition dump")
    ap.add_argument(
        "--family",
        default=None,
        help="substring filter on the family name "
        "(e.g. stage_seconds, http_request)",
    )
    ap.add_argument(
        "--counters",
        action="store_true",
        help="render plain counter/gauge families instead of "
        "histograms (e.g. --counters --family lighthouse_tpu_da "
        "for the DAS audit view)",
    )
    ap.add_argument(
        "--slot-budget",
        action="store_true",
        help="render per-import critical-path waterfalls + stage "
        "quantiles from /lighthouse/slot_budget (--url = node base "
        "URL; --file = a saved response document)",
    )
    ap.add_argument(
        "--timeline",
        action="store_true",
        help="multi-node mode: merge per-node journals into per-block "
        "causal timelines + population stats",
    )
    ap.add_argument(
        "--node-url",
        action="append",
        default=None,
        help="timeline source: a live node's base URL (repeatable; "
        "events read from <url>/lighthouse/events)",
    )
    ap.add_argument(
        "--journal",
        action="append",
        default=None,
        help="timeline source: a raw journal JSONL export "
        "(repeatable; node name taken from the file name)",
    )
    args = ap.parse_args(argv)
    if args.slot_budget:
        if args.url:
            doc = fetch_slot_budget(args.url)
        elif args.file:
            with open(args.file) as f:
                doc = json.load(f)
            doc = doc.get("data", doc)
        else:
            doc = json.loads(sys.stdin.read())
            doc = doc.get("data", doc)
        sys.stdout.write(render_slot_budget(doc))
        return 0
    if args.timeline:
        import os

        events_by_node = {}
        for url in args.node_url or ():
            events_by_node[url] = fetch_node_events(url)
        for path in args.journal or ():
            name = os.path.splitext(os.path.basename(path))[0]
            if name in events_by_node:
                # per-node-directory layouts share a basename
                # (node0/events.jsonl, node1/events.jsonl) — keep both
                name = os.path.normpath(path)
            events_by_node[name] = load_journal_jsonl(path)
        if not events_by_node:
            print("--timeline needs --node-url and/or --journal sources")
            return 2
        sys.stdout.write(render_timeline_report(events_by_node))
        return 0
    if args.url:
        from urllib.request import urlopen

        with urlopen(args.url, timeout=10) as r:
            text = r.read().decode()
    elif args.file:
        with open(args.file) as f:
            text = f.read()
    else:
        text = sys.stdin.read()
    if args.counters:
        sys.stdout.write(render_counter_report(text, args.family))
    else:
        sys.stdout.write(render_report(text, args.family))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
