"""Runs one cell of BENCHMARK.json once: set-up, the measured window, the
reference check, and the result line.

Everything particular to a cell is found by name: the configuration in
`benchmark/configs/<config>.json`, the traffic mix in
`benchmark/traffic/<traffic>.json` (naming its generator module in
`benchmark/traffic/`), and each per-layer metric's reader in
`benchmark/metrics/<metric up to its first dot>.py`.

The node is wired as `node.py` and `beacon_chain/chain.py` wire it: a
PubkeyCache holding the registry with its device table uploaded, a
`BeaconProcessor` with the program's default workers and bounds, a
`VerificationBus(backend="tpu")` whose gossip budget is the slot clock's
1/3-slot attestation deadline and whose pressure signal is the
processor's.
"""

import gc
import importlib
import importlib.util
import json
import multiprocessing
import os
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, "cache")
TRACE_DIR = os.path.join(CACHE_DIR, "trace")

FLAT_PROGRAMS = ("verify", "verify_indexed", "verify_grouped",
                 "verify_grouped_indexed")


class Fail(Exception):
    """A run that cannot produce a result (no chip, bad spec)."""


# ------------------------------------------------------------------ spec


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec():
    return _load_json(ROOT, "BENCHMARK.json")


def resolve(spec, workload):
    """-> (cell, configuration, traffic mix) for a workload name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Fail(f"unknown workload {workload!r}")
    cell = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = _load_json(ROOT, conf["file"])
    return cell, cfg, load_mix(cell["traffic"])


def load_config(name):
    return _load_json(BENCH_DIR, "configs", name + ".json")


def load_mix(name):
    return _load_json(BENCH_DIR, "traffic", name + ".json")


def generator(mix):
    return importlib.import_module(f"benchmark.traffic.{mix['generator']}")


def reader(metric_name):
    """The per-layer reader of a metric: the file named by the metric's
    name up to its first dot, so one reader serves every suffix."""
    base = metric_name.split(".", 1)[0]
    path = os.path.join(BENCH_DIR, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{base}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec, cell, kind):
    """The cell's metrics of `kind` ("end_to_end" | "per_layer")."""
    e2e = {
        m["name"] for m in spec["end_to_end"]
        if cell["name"] in m.get("workloads", [cell["name"]])
    }
    out = []
    for m in spec[kind]:
        listed = m.get("workloads")
        if listed is not None:
            if cell["name"] in listed:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def make_pool(processes=None):
    """Worker processes for signing and the reference: started with
    `spawn`, they import neither JAX nor the program."""
    if processes is None:
        processes = max(1, min(8, (os.cpu_count() or 2) - 2))
    return multiprocessing.get_context("spawn").Pool(processes)


# ------------------------------------------------------------------ node


class Node:
    """The program's verification path, wired as the beacon node wires it."""

    def __init__(self, cfg, points, compressed):
        from lighthouse_tpu.bls.api import PublicKey
        from lighthouse_tpu.common.events_journal import Journal
        from lighthouse_tpu.common.slot_clock import SystemTimeSlotClock
        from lighthouse_tpu.network.beacon_processor import BeaconProcessor
        from lighthouse_tpu.state_processing.pubkey_cache import PubkeyCache
        from lighthouse_tpu.verification_bus import VerificationBus

        self.cfg = cfg
        # the registry as PubkeyCache.import_new leaves it: decompressed,
        # validated keys tagged with their index and cache (the keys were
        # validated when the registry was built; a node restores them from
        # its own store the same way)
        self.cache = PubkeyCache()
        by_index = []
        for v, ((x, y), b) in enumerate(zip(points, compressed)):
            pk = PublicKey((x, y, 1), b)
            pk.validator_index = v
            pk.cache = self.cache
            by_index.append(pk)
        self.cache._by_index = by_index
        self.cache._by_bytes = {b: v for v, b in enumerate(compressed)}
        # the HBM pubkey table a deployment holds, built and uploaded in
        # set-up so that no later path pays for it inside the window
        tx, _ = self.cache.device_table().rows()
        tx.block_until_ready()

        self.journal = Journal()
        self.bus = VerificationBus(backend="tpu", journal=self.journal)
        S = cfg["SECONDS_PER_SLOT"]
        self.clock = SystemTimeSlotClock(time.time(), S)

        def gossip_budget():
            # chain.py's gossip budget: time to the next 1/3-slot
            # attestation deadline, floored, capped at one slot
            clock = self.clock
            rem = (
                clock.attestation_deadline(clock.current_slot())
                - clock.now()
            )
            if rem <= 0:
                rem += S
            return max(0.25, min(rem, float(S)))

        self.bus.budget_fns["gossip_single"] = gossip_budget
        self.bus.budget_fns["sidecar_header"] = gossip_budget
        self.processor = BeaconProcessor(handlers={}, journal=self.journal)
        self.bus.pressure_fn = self.processor.pressure_high
        self._window = None

    def open_window(self, annotate=None):
        """Start the slot clock at a slot boundary now; returns the
        window's start on the perf counter."""
        if annotate is not None:
            self._window = annotate("bench/window")
            self._window.__enter__()
        t0 = time.perf_counter()
        self.clock.genesis_time = time.time()
        return t0

    def close_window(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None


def _warm_sets(pool, batches, n_validators, seed):
    """Valid signature sets for each warm batch (fresh messages, keys
    drawn from the registry): [(kind, [(message, validators, 0)], sigs)]."""
    import numpy as np

    from benchmark.signing import sign_sets

    rng = np.random.default_rng((int(seed) + 0x5EED) % 2**64)
    plans = []
    for kind, keys in batches:
        sets = [
            (rng.bytes(32),
             tuple(rng.choice(n_validators, k, replace=False).tolist()), 0)
            for k in keys
        ]
        plans.append((kind, sets))
    sigs = pool.map(sign_sets, [(sets, None) for _, sets in plans])
    return [(k, sets, s) for (k, sets), s in zip(plans, sigs)]


def _to_api_sets(node, sets, sigs):
    from lighthouse_tpu import bls

    return [
        bls.SignatureSet(
            bls.Signature.from_bytes(sig),
            [node.cache.get(v) for v in vs], m,
        )
        for (m, vs, _), sig in zip(sets, sigs)
    ]


def warm_device(node, warm, log=None):
    """Compile (or load from the persistent cache) every program the
    traffic can reach, in parallel threads, then dispatch each once
    through the node's own path, so nothing compiles in the window."""
    from lighthouse_tpu.bls import tpu_backend
    from lighthouse_tpu.device_plane import canary

    sentinel = canary.bls_sentinels()[0]
    # create the individual-path jit objects on this thread, so that the
    # threads below all warm the same ones
    tpu_backend._get_individual_fns()
    batches = [(k, _to_api_sets(node, sets, sigs)) for k, sets, sigs in warm]
    # the canary pair's own individual bucket
    batches.append(("individual", list(canary.bls_sentinels())))
    log = log or (lambda msg: None)
    t0 = time.perf_counter()

    def timed(fn, *args, **kw):
        t = time.perf_counter()
        fn(*args, **kw)
        return t - t0, time.perf_counter() - t0

    with ThreadPoolExecutor(len(batches)) as ex:
        futs = [
            ex.submit(timed, tpu_backend.compile_ahead, sets + [sentinel])
            if kind == "bus"
            else ex.submit(
                timed, tpu_backend.verify_signature_sets_tpu_individual,
                sets, consumer="bench",
            )
            for kind, sets in batches
        ]
        for (kind, sets), f in zip(batches, futs):
            start, end = f.result()
            log(f"bench: {kind} {len(sets)} sets ready {start:.1f}-{end:.1f} s")
    for kind, sets in batches[:-1]:
        if kind == "bus":
            ok = node.bus.submit(
                sets, consumer="gossip_single", backend="tpu",
                journal=node.journal,
            )
        else:
            ok = all(node.bus.submit_individual(
                sets, consumer="gossip_single", backend="tpu",
                journal=node.journal,
            ))
        if not ok:
            raise Fail(f"a valid warm-up {kind} batch was refused")


# ------------------------------------------------------------ readings


def snapshot(node):
    """The program's counters and span sums, to diff across the window."""
    from lighthouse_tpu.common.metrics import REGISTRY
    from lighthouse_tpu.device_plane import GUARD

    stages = REGISTRY.get("lighthouse_tpu_verify_stage_seconds")
    entries = REGISTRY.get("lighthouse_tpu_compile_ledger_entries_total")
    memo = REGISTRY.get("lighthouse_tpu_msg_cache_events_total")
    memo = {
        lbl[0]: c.value for lbl, c in (memo.children() if memo else {}).items()
    }
    bus = node.bus.stats()
    return {
        "stages": {
            lbl[0]: (h.total, h.n)
            for lbl, h in (stages.children() if stages else {}).items()
        },
        "dispatches": sum(
            c.value
            for (fn, event), c in (
                entries.children() if entries else {}
            ).items()
            if fn in FLAT_PROGRAMS and event == "warm"
        ),
        "memo_hits": memo.get("hit", 0),
        "memo_misses": memo.get("miss", 0),
        "live": bus["live_dispatched"],
        "batches": bus["batches_formed"],
        "failovers": sum(GUARD.stats()["failovers"].values()),
        "t": time.time(),
    }


def diff(a, b):
    stages = {
        k: (v[0] - a["stages"].get(k, (0.0, 0))[0],
            v[1] - a["stages"].get(k, (0.0, 0))[1])
        for k, v in b["stages"].items()
    }
    out = {k: b[k] - a[k] for k in b if k != "stages"}
    out["stages"] = stages
    return out


def cold_entries_since(t):
    """[fn, shape] of every compile the ledger recorded after `t`."""
    from lighthouse_tpu.common.compile_ledger import LEDGER

    return [
        [e["fn"], e["shape"]] for e in LEDGER.entries()
        if e["event"] == "cold" and e["t"] >= t
    ]


def _peak_rss():
    """This process's peak resident memory, in bytes."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class HostProbe:
    """What the host did while the window ran: CPU time of the whole
    process (the block's host work runs on the guard's dispatch thread,
    not the driving one), and garbage collections by generation with the
    time they took."""

    def __init__(self):
        self.gc_s = [0.0, 0.0, 0.0]
        self._t = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_s[info["generation"]] += time.perf_counter() - self._t
            self._t = None

    @staticmethod
    def _read():
        t = os.times()
        return t.user + t.system, [g["collections"] for g in gc.get_stats()]

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._a = self._read()
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        self._b = self._read()

    def reading(self):
        (cpu_a, gc_a), (cpu_b, gc_b) = self._a, self._b
        return {
            "process_cpu_s": cpu_b - cpu_a,
            "gc_collections": [y - x for x, y in zip(gc_a, gc_b)],
            "gc_s": self.gc_s,
        }


def device_info(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def start_traffic(gen, cfg, mix, seed, seconds, pool):
    """Generate the window's traffic on a thread (the signing runs in the
    worker pool, beside the device set-up); returns a call that waits for
    it."""
    from benchmark.signing import adversary_delta

    box = {}

    def make():
        try:
            box["value"] = gen.generate(
                cfg, mix, seed, seconds, pool, adversary_delta(seed)
            )
        except BaseException as e:  # re-raised on the caller's thread
            box["error"] = e

    th = threading.Thread(target=make, name="traffic")
    th.start()

    def wait():
        th.join()
        if "error" in box:
            raise box["error"]
        return box["value"]

    return wait


def prepare(gen, cfg, mix, seed, pool, reg_path, log=None):
    """The node with its registry loaded and every program warm."""
    from benchmark import registry

    log = log or (lambda msg: None)
    t = time.perf_counter()
    warm = _warm_sets(
        pool, gen.warm_batches(cfg, mix), cfg["validators"], seed
    )
    log(f"bench: warm-up sets signed {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    points, compressed = registry.load(reg_path)
    node = Node(cfg, points, compressed)
    del points, compressed
    log(f"bench: registry and pubkey table {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    warm_device(node, warm, log)
    log(f"bench: programs warm {time.perf_counter() - t:.1f} s")
    return node


# ------------------------------------------------------------------- run


def run_cell(
    spec, cell, cfg, mix, seed, seconds, trace, t_start, devices,
    pool_processes=None, log=None,
):
    """One run of one cell; returns the result dict (without printing)."""
    import jax

    from benchmark import registry
    from lighthouse_tpu.backend import enable_compile_cache

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    gen = generator(mix)
    enable_compile_cache()
    pool = make_pool(pool_processes)
    try:
        reg_path = registry.build(CACHE_DIR, cfg["validators"], pool)
        log(f"bench: registry file {time.perf_counter() - t_start:.1f} s")
        pending = start_traffic(gen, cfg, mix, seed, seconds, pool)
        node = prepare(gen, cfg, mix, seed, pool, reg_path, log)
        traffic = pending()
        before = snapshot(node)
        setup_s = time.perf_counter() - t_start
        log(f"bench: set-up {setup_s:.3f} s")

        annotate = None
        trace_dir = None
        if trace:
            trace_dir = os.path.join(TRACE_DIR, f"{cell['name']}-{seed}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            annotate = jax.profiler.TraceAnnotation
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with HostProbe() as host:
                out = gen.drive(node, traffic, seconds, annotate)
        finally:
            node.close_window()
            if trace:
                jax.profiler.stop_trace()
        after = snapshot(node)
        window_compiles = cold_entries_since(before["t"])
        info = device_info(devices)
        delta_counts = diff(before, after)
        e2e = gen.end_to_end(traffic, out, seconds)
        readings = gen.harness_readings(traffic, out)
        attempted, failed = gen.counts(traffic, out)
        reduced = None
        if trace:
            from benchmark import trace_reduce

            reduced = trace_reduce.reduce_dir(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            info["busy_s"] = reduced["busy_s"]
            info["window_s"] = reduced["window_s"]
            log("bench: trace planes " + json.dumps(reduced["planes"]))
        node.processor.stop()
        del node
        checks = gen.check(traffic, out, pool)
    finally:
        pool.terminate()
        pool.join()

    if trace:
        ctx = {
            "stages": delta_counts["stages"],
            "bus": {"live": delta_counts["live"],
                    "batches": delta_counts["batches"]},
            "live_sets": delta_counts["live"],
            "verify_dispatches": delta_counts["dispatches"],
            "harness": readings,
            "trace": reduced,
        }
        metrics = {}
        for m in cell_metrics(spec, cell, "per_layer"):
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(spec, cell, "end_to_end")
        }
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": info,
    }
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    result["info"] = dict(
        readings,
        host_peak_rss_bytes=_peak_rss(),
        window_compiles=window_compiles,
        failovers=delta_counts["failovers"],
        bus_batches=delta_counts["batches"],
        live_sets=delta_counts["live"],
        hash_to_g2_memo={"hits": delta_counts["memo_hits"],
                         "misses": delta_counts["memo_misses"]},
        host=host.reading(),
    )
    result["checks"] = {
        k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()
    }
    for k, (v, lim) in checks.items():
        log(f"check {k} {v} limit {lim}")
    return result
