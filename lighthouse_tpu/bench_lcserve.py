"""BENCH_CONFIG=lcserve / lcproof: the light-client serving plane.

Two configs ride this module:

  * ``lcserve`` — read-flood phase against ONE live node: drive the
    chain to finality with full-participation sync aggregates, then
    flood the hot light-client reads (bootstrap by trusted root +
    per-period update ranges + finality/optimistic documents, SSZ
    streaming responses) with concurrent clients. Reports p50/p99 per
    admission class from the existing `http_class_seconds` histogram
    (phase-diffed), asserts cache misses <= TTL windows (the per-import
    invalidated TTL cache converting the flood into one producer
    lookup per window), and carries the streamed-bytes/chunks totals.
  * ``lcproof`` — the batched device Merkle-proof kernel
    (ops/merkle_proof) at BENCH_NSETS query shapes (the chip runs
    1k/16k): deterministic (leaf, branch, gindex) queries at the
    light-client finality depth, device results cross-checked
    byte-identical against the hashlib host oracle every iteration.

Crypto runs on the fake backend in lcserve (it measures the SERVING
edge); lcproof measures a real device kernel and is the entry the
hardware sweep replays. Neither line is ever `valid_for_headline`.
"""

import hashlib
import json
import os
import threading
import time
import urllib.error
import urllib.request

N_VALIDATORS = 8
# enough slots past the third epoch boundary that the chain finalizes
# and the producer holds bootstrap + finality/optimistic documents
CHAIN_SLOTS = 33

_FLOOD_PATHS = (
    "/eth/v1/beacon/light_client/finality_update",
    "/eth/v1/beacon/light_client/optimistic_update",
    "/eth/v1/beacon/light_client/updates?start_period=0&count=4",
)


def _build_node():
    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.node import BeaconNode
    from lighthouse_tpu.types.spec import minimal_spec

    spec = minimal_spec(ALTAIR_FORK_EPOCH=0)
    h = Harness(spec, N_VALIDATORS, backend="fake")
    node = BeaconNode("lcbench0", h.state, spec, backend="fake")
    for slot in range(1, CHAIN_SLOTS + 1):
        block = h.advance_slot_with_block(slot, consumer="bench")
        node.on_slot(slot)
        node.chain.process_block(block)
    return h, node


def _request(base: str, path: str, ssz: bool) -> int:
    req = urllib.request.Request(
        base + path,
        headers=(
            {"Accept": "application/octet-stream"} if ssz else {}
        ),
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            r.read()
        return 200
    except urllib.error.HTTPError as e:
        return e.code
    except OSError:
        return -1


def measure(jax, platform):
    """The lcserve read-flood line."""
    from lighthouse_tpu.bench_serve import _histogram_quantiles, _parse_family
    from lighthouse_tpu.common.metrics import REGISTRY

    if platform == "cpu":
        n_threads, reads_per_thread = 4, 60
    else:
        n_threads, reads_per_thread = 8, 120

    h, node = _build_node()
    api = node.start_http_api()
    base = f"http://127.0.0.1:{api.port}"
    producer = node.chain.light_client_producer
    bootstrap_roots = ["0x" + r.hex() for r in producer.bootstraps]
    if not bootstrap_roots:
        raise RuntimeError(
            "lcserve: chain never finalized — no bootstrap to flood"
        )

    def _served_bytes_total():
        fam = REGISTRY.get("lighthouse_tpu_lc_served_bytes_total")
        if fam is None:
            return 0.0
        return sum(c.value for c in fam.children().values())

    class_before = _parse_family(
        "lighthouse_tpu_http_class_seconds", "cls"
    )
    bytes_before = _served_bytes_total()
    cache = api._hot_caches["light_client"]
    cache.invalidate()
    misses_before = cache.misses
    statuses = []
    t0 = time.perf_counter()

    def flood(seed: int):
        paths = list(_FLOOD_PATHS) + [
            "/eth/v1/beacon/light_client/bootstrap/"
            + bootstrap_roots[seed % len(bootstrap_roots)]
        ]
        for i in range(reads_per_thread):
            # alternate SSZ streaming and JSON renderings of the same
            # hot documents — both ride the TTL cache
            statuses.append(
                _request(
                    base, paths[i % len(paths)], ssz=(i % 2 == 0)
                )
            )

    threads = [
        threading.Thread(target=flood, args=(i,), daemon=True)
        for i in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    wall_s = time.perf_counter() - t0

    cache_misses = cache.misses - misses_before
    # distinct hot keys: each (path, rendering) pair occupies one slot
    hot_keys = (len(_FLOOD_PATHS) + len(bootstrap_roots)) * 2
    cache_windows = (int(wall_s / cache.ttl_s) + 1) * hot_keys
    served_bytes = _served_bytes_total() - bytes_before
    classes = _histogram_quantiles(
        "lighthouse_tpu_http_class_seconds",
        "cls",
        before=class_before,
    )
    api.stop()

    ok = sum(1 for s in statuses if s == 200)
    total = len(statuses)
    return {
        "metric": "lc_serve_read_throughput",
        "value": round(total / wall_s, 2),
        "unit": "requests/sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "impl": "lc_ttl_stream",
        "n_sets": total,
        "flood_ok": ok,
        "flood_shed": sum(1 for s in statuses if s in (429, 503)),
        "classes": classes,
        "cache_misses": cache_misses,
        "cache_windows": cache_windows,
        "cache_ok": bool(cache_misses <= cache_windows),
        "served_bytes": int(served_bytes),
        "producer": producer.stats(),
        "valid_for_headline": False,
    }


# ----------------------------------------------------------- proof kernel


def _proof_queries(n: int, depth: int):
    """Deterministic (leaf, branch, gindex) fixtures at `depth`."""
    queries = []
    for i in range(n):
        leaf = hashlib.sha256(b"lcproof-leaf-%d" % i).digest()
        branch = [
            hashlib.sha256(b"lcproof-sib-%d-%d" % (i, d)).digest()
            for d in range(depth)
        ]
        gindex = (1 << depth) + (i * 2654435761 % (1 << depth))
        queries.append((leaf, branch, gindex))
    return queries


def measure_proofs(jax, platform):
    """The lcproof line: batched branch folds at BENCH_NSETS lanes,
    device byte-identical to the host oracle each iteration."""
    from lighthouse_tpu.ops import merkle_proof as mp

    n = int(os.environ.get("BENCH_NSETS", "1024"))
    depth = 6  # the light-client finality-branch depth
    queries = _proof_queries(n, depth)
    expected = mp.fold_branches_host(queries)

    t0 = time.perf_counter()
    got = mp.batch_merkle_roots(queries, consumer="bench")
    compile_s = time.perf_counter() - t0
    if got != expected:
        raise RuntimeError("device fold diverged from the host oracle")

    iters = 5
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        got = mp.batch_merkle_roots(queries, consumer="bench")
        times.append(time.perf_counter() - t0)
        if got != expected:
            raise RuntimeError(
                "device fold diverged from the host oracle"
            )
    times.sort()
    p50 = times[len(times) // 2]
    return {
        "metric": "lc_proof_batch_throughput",
        "value": round(n / p50, 1),
        "unit": "proofs/sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "impl": "merkle_fold",
        "n_sets": n,
        "depth": depth,
        "p50_s": round(p50, 5),
        "compile_s": round(compile_s, 3),
        "byte_identical": True,
        "valid_for_headline": False,
    }


if __name__ == "__main__":
    print(json.dumps(measure(None, "cpu"), indent=2))
