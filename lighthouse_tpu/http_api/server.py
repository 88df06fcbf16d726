"""REST beacon API server (standard endpoints + metrics scrape).

Role of the reference's warp-based http_api (beacon_node/http_api/src/
lib.rs, 3,119 LoC: beacon, node, validator, debug namespaces) and
http_metrics (Prometheus scrape). Implemented over stdlib http.server
(threaded) so the surface carries no extra dependencies; the validator
client's HTTP transport (`BeaconNodeHttpClient` analog) talks to exactly
these routes.

Endpoints (the operative subset):
  GET  /eth/v1/node/version | health | syncing
  GET  /eth/v1/beacon/genesis
  GET  /eth/v1/beacon/states/{state_id}/finality_checkpoints | root
  GET  /eth/v1/beacon/states/{state_id}/validators[?id=...]
  GET  /eth/v1/beacon/headers/{block_id}
  GET  /eth/v2/beacon/blocks/{block_id}
  POST /eth/v1/beacon/blocks
  POST /eth/v1/beacon/pool/attestations
  POST /eth/v1/beacon/pool/sync_committees
  GET  /eth/v1/validator/duties/proposer/{epoch}
  POST /eth/v1/validator/duties/attester/{epoch}
  POST /eth/v1/validator/duties/sync/{epoch}
  GET  /eth/v2/validator/blocks/{slot}?randao_reveal=...&graffiti=...
  GET  /eth/v1/validator/blinded_blocks/{slot}?randao_reveal=...
  POST /eth/v1/beacon/blinded_blocks
  POST /eth/v1/validator/register_validator
  GET  /eth/v1/beacon/states/{id}/fork | committees | validator_balances
       | sync_committees
  GET  /eth/v1/beacon/blocks/{id}/root | attestations
  GET  /eth/v1/config/spec | fork_schedule | deposit_contract
  GET  /eth/v1/node/identity | peers | peer_count
  GET  /lighthouse/health  (per-node health document: head/finality,
       queues, peer scores, DA occupancy, journal, validator monitor)
  GET  /lighthouse/events?root=...&slot=...&kind=...&peer=...&outcome=...
       (object-lifecycle journal forensics)
  GET  /lighthouse/metrics/snapshot  (flat registry snapshot for diffs)
  GET  /lighthouse/compiles  (process compile ledger: jit (re)compiles
       with impl key, shape bucket, cold/warm, wall duration)
  GET  /lighthouse/tpu/stats  (chain internals namespace)
  GET  /eth/v1/validator/attestation_data?slot=...&committee_index=...
  GET  /eth/v1/validator/aggregate_attestation?slot=...&attestation_data_root=...
  POST /eth/v1/validator/aggregate_and_proofs
  GET  /eth/v1/validator/sync_committee_contribution?slot=...&subcommittee_index=...&beacon_block_root=...
  POST /eth/v1/validator/contribution_and_proofs
  POST /eth/v1/validator/liveness/{epoch}
  GET  /metrics
"""

import json
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

from lighthouse_tpu.common.logging import get_logger
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.common.tracing import TRACER
from lighthouse_tpu.http_api.admission import (
    AdmissionController,
    AdmissionError,
    TTLCache,
    check_deadline,
    classify,
    count_shed,
)
from lighthouse_tpu.http_api.json_codec import from_json, to_json

_LOG = get_logger("http_api")

VERSION = "lighthouse-tpu/0.1.0"

# serving-plane shape (ROADMAP "high-traffic serving plane"): a bounded
# worker pool fed by a bounded accept queue replaces the unbounded
# thread-per-request model — overload sheds at the edge (503 +
# Retry-After) instead of growing a thread per attacker
DEFAULT_POOL_WORKERS = 8
DEFAULT_ACCEPT_QUEUE = 64
MAX_STREAM_DETACH = 8  # concurrent SSE streams allowed off-pool

_HTTP_SECONDS = REGISTRY.histogram_vec(
    "lighthouse_tpu_http_request_seconds",
    "REST API request latency by method and endpoint template",
    ("method", "endpoint"),
)
_HTTP_CLASS_SECONDS = REGISTRY.histogram_vec(
    "lighthouse_tpu_http_class_seconds",
    "REST API request latency by admission class "
    "(cheap_read|expensive_read|write)",
    ("cls",),
)
_CACHE_STATS = REGISTRY.gauge_vec(
    "lighthouse_tpu_attestation_cache_stat",
    "attestation-production cache statistics",
    ("cache", "stat"),
)


# the route vocabulary: any path segment outside it becomes {id}, so
# the latency family's cardinality is bounded by real routes no matter
# what a scanner throws at the port
_ROUTE_SEGMENTS = frozenset(
    """
    eth lighthouse v1 v2 metrics spans health tpu stats node beacon
    snapshot compiles
    config validator debug events genesis states headers blocks blinded
    blob_sidecars pool duties liveness register_validator blinded_blocks
    light_client bootstrap updates finality_update optimistic_update
    aggregate_and_proofs contribution_and_proofs aggregate_attestation
    attestation_data sync_committee_contribution
    beacon_committee_subscriptions attestations sync_committees
    voluntary_exits proposer_slashings attester_slashings committees
    validators validator_balances finality_checkpoints fork
    fork_schedule spec deposit_contract root attester proposer sync
    identity peers peer_count syncing version heads fork_choice
    head finalized justified genesis_state
    """.split()
)


def _endpoint_label(path: str) -> str:
    """Collapse everything outside the route vocabulary (slots, roots,
    hex blobs, scanner garbage) to {id} so the latency family stays
    low-cardinality; named route words (head, finalized, ...) stay
    literal."""
    parts = [p for p in path.split("?")[0].split("/") if p]
    out = [
        p if p in _ROUTE_SEGMENTS else "{id}"
        for p in parts[:6]
    ]
    return "/" + "/".join(out)


class ApiError(Exception):
    def __init__(self, code, message):
        self.code = code
        self.message = message


def _validator_status(v, balance: int, epoch: int) -> str:
    """Standard validator status algorithm (the beacon-API state
    machine): pending_initialized only while the deposit has no
    eligibility epoch; withdrawal_done once the balance is gone."""
    from lighthouse_tpu.types.spec import FAR_FUTURE_EPOCH as FAR

    if epoch < v.activation_epoch:
        return (
            "pending_initialized"
            if v.activation_eligibility_epoch == FAR
            else "pending_queued"
        )
    if epoch < v.exit_epoch:
        if v.slashed:
            return "active_slashed"
        return (
            "active_exiting" if v.exit_epoch < FAR else "active_ongoing"
        )
    if epoch < v.withdrawable_epoch:
        return "exited_slashed" if v.slashed else "exited_unslashed"
    return "withdrawal_done" if balance == 0 else "withdrawal_possible"


class PooledHTTPServer(HTTPServer):
    """Bounded worker pool + bounded accept queue over the stdlib
    server. `process_request` enqueues the accepted socket; N pool
    workers drain it. A full accept queue is the outermost shed point:
    the client gets a raw 503 + Retry-After and the socket closes —
    overload costs one queue probe, never a thread.

    SSE streams (`/eth/v1/events`) hold a connection for minutes; a
    handler entering a stream calls `detach_current_worker()`, which
    spawns a replacement pool worker (bounded by MAX_STREAM_DETACH) so
    streaming never starves request serving.
    """

    daemon_threads = True
    allow_reuse_address = True

    _RAW_503 = (
        b"HTTP/1.1 503 Service Unavailable\r\n"
        b"Content-Type: application/json\r\n"
        b"Retry-After: 1\r\n"
        b"Content-Length: 45\r\n\r\n"
        b'{"code": 503, "message": "accept queue full"}'
    )

    def __init__(
        self,
        addr,
        handler_cls,
        workers: int = DEFAULT_POOL_WORKERS,
        accept_queue: int = DEFAULT_ACCEPT_QUEUE,
    ):
        super().__init__(addr, handler_cls)
        self._accept_q: queue.Queue = queue.Queue(maxsize=accept_queue)
        self._pool_lock = threading.Lock()
        self._detached_streams = 0
        self._retire_pending = 0
        self._workers: list[threading.Thread] = []
        self._pool_size = workers
        self.accept_shed = 0

    def start_pool(self):
        """Spawn the workers — called from BeaconApiServer.start(), so
        CONSTRUCTION stays side-effect-free beyond the socket bind
        (tests that only call handle_get directly never pay 8 threads).
        No request can arrive earlier: serve_forever starts alongside."""
        for _ in range(self._pool_size):
            self._spawn_worker()

    def _spawn_worker(self):
        th = threading.Thread(target=self._worker_loop, daemon=True)
        th.start()
        # prune retired workers so the list tracks LIVE threads only
        # (every SSE detach spawns one; a long-lived node must not
        # accumulate dead Thread objects). Under the pool lock: two
        # concurrent SSE detaches must not lose each other's append.
        with self._pool_lock:
            self._workers = [
                t for t in self._workers if t.is_alive()
            ] + [th]

    def process_request(self, request, client_address):
        try:
            self._accept_q.put_nowait((request, client_address))
        except queue.Full:
            self.accept_shed += 1
            count_shed("(accept)", "accept_queue")
            try:
                request.sendall(self._RAW_503)
            except OSError as e:
                _LOG.debug("accept-shed response failed: %s", e)
            self.shutdown_request(request)

    def _worker_loop(self):
        while True:
            item = self._accept_q.get()
            if item is None:
                return
            request, client_address = item
            try:
                self.finish_request(request, client_address)
            except Exception as e:
                # one broken connection must not kill a pool worker
                _LOG.debug("request handling failed: %s", e)
            finally:
                self.shutdown_request(request)
            if self._maybe_retire():
                return

    def _maybe_retire(self) -> bool:
        """Shrink the pool back after a detached SSE stream ended."""
        with self._pool_lock:
            if self._retire_pending > 0:
                self._retire_pending -= 1
                return True
        return False

    def detach_current_worker(self) -> bool:
        """Called by a handler about to block on a long-lived stream:
        spawns a replacement worker so the pool's serving capacity is
        unchanged. Returns False (stream must be refused) once
        MAX_STREAM_DETACH streams are already detached."""
        with self._pool_lock:
            if self._detached_streams >= MAX_STREAM_DETACH:
                return False
            self._detached_streams += 1
        self._spawn_worker()
        return True

    def reattach_worker(self):
        """Stream ended: the streaming worker resumes its pool loop, so
        one worker (whichever finishes a request next) retires and the
        pool shrinks back to its configured size."""
        with self._pool_lock:
            self._detached_streams -= 1
            self._retire_pending += 1

    def stop_pool(self):
        # drain pending requests first (closing them) so one exit
        # sentinel per LIVE worker always fits in the queue
        try:
            while True:
                item = self._accept_q.get_nowait()
                if item is not None:
                    self.shutdown_request(item[0])
        except queue.Empty:
            pass
        with self._pool_lock:
            self._workers = [
                t for t in self._workers if t.is_alive()
            ]
            live = len(self._workers)
        for _ in range(live):
            try:
                self._accept_q.put_nowait(None)
            except queue.Full:
                break


class BeaconApiServer:
    def __init__(self, chain, host: str = "127.0.0.1", port: int = 0,
                 net=None, sync=None, node=None):
        self.chain = chain
        self.net = net  # optional SocketNet for node/identity + peers
        self.sync = sync  # optional SyncManager for node/syncing
        self.node = node  # optional BeaconNode for subnet subscriptions
        # admission control: per-class concurrency limits + deadlines;
        # hot immutable reads answered from TTL caches invalidated on
        # every block import (a read flood against a hot key costs one
        # store hit per TTL window)
        self.admission = AdmissionController()
        self._hot_caches = {
            "state_reads": TTLCache("state_reads", ttl_s=1.0),
            "blob_sidecars": TTLCache("blob_sidecars", ttl_s=2.0),
            # light-client read documents change only on import (the
            # same hook invalidates), so a million-user read flood
            # costs one producer lookup per TTL window per period
            "light_client": TTLCache("light_client", ttl_s=1.0),
        }
        api = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _send(
                self,
                code,
                payload,
                content_type="application/json",
                headers=None,
            ):
                body = (
                    payload
                    if isinstance(payload, bytes)
                    else json.dumps(payload).encode()
                )
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _send_stream(self, stream):
                """Stream an SszStream response: Content-Length known
                up front (pure arithmetic), body written chunk by
                chunk — the handler never held the full encoding."""
                self.send_response(200)
                self.send_header("Content-Type", stream.content_type)
                self.send_header("Content-Length", str(stream.length))
                self.end_headers()
                for chunk in stream.chunks():
                    self.wfile.write(chunk)

            def _send_shed(self, e: AdmissionError):
                """503/429 + Retry-After: the refuse-loud contract."""
                self._send(
                    e.code,
                    {"code": e.code, "message": e.message},
                    headers={
                        "Retry-After": str(
                            max(1, int(e.retry_after + 0.999))
                        )
                    },
                )

            def do_GET(self):
                if self.path.split("?")[0] == "/eth/v1/events":
                    # SSE streams stay open for minutes — detach from
                    # the worker pool (bounded) so streaming cannot
                    # starve request serving; excluded from the
                    # request-latency histogram by design
                    if not api._httpd.detach_current_worker():
                        return self._send(
                            503,
                            {
                                "code": 503,
                                "message": "stream limit reached",
                            },
                            headers={"Retry-After": "30"},
                        )
                    try:
                        return self._serve_events()
                    finally:
                        api._httpd.reattach_worker()
                cls_ = classify("GET", self.path)
                endpoint = _endpoint_label(self.path)
                try:
                    slot = api.admission.acquire(cls_, endpoint)
                except AdmissionError as e:
                    return self._send_shed(e)
                t0 = time.perf_counter()
                try:
                    with slot:
                        # self.headers is an HTTPMessage: case-
                        # insensitive get(), as header lookup must be
                        out = api._cached_get(self.path, self.headers)
                    from lighthouse_tpu.http_api.streaming import (
                        SszStream,
                    )

                    if isinstance(out, SszStream):
                        self._send_stream(out)
                    elif isinstance(out, tuple):
                        self._send(200, out[0], content_type=out[1])
                    else:
                        self._send(200, out)
                except AdmissionError as e:
                    # deadline exceeded mid-handler: abort loudly
                    self._send_shed(e)
                except ApiError as e:
                    self._send(
                        e.code, {"code": e.code, "message": e.message}
                    )
                except Exception as e:  # pragma: no cover
                    self._send(500, {"code": 500, "message": str(e)})
                finally:
                    dt = time.perf_counter() - t0
                    _HTTP_SECONDS.labels("GET", endpoint).observe(dt)
                    _HTTP_CLASS_SECONDS.labels(cls_).observe(dt)

            def _serve_events(self):
                """Server-sent events stream (/eth/v1/events?topics=…,
                beacon_chain/src/events.rs + the http_api SSE route).
                Streams until the client disconnects or the idle window
                passes with no events. Unknown topics are a 400, per the
                standard beacon API."""
                import queue as _queue
                from urllib.parse import parse_qs, urlparse

                from lighthouse_tpu.beacon_chain.events import TOPICS

                try:
                    q = urlparse(self.path)
                    requested = [
                        t
                        for part in parse_qs(q.query).get("topics", [])
                        for t in part.split(",")
                        if t
                    ]
                    bad = [t for t in requested if t not in TOPICS]
                    if bad:
                        return self._send(
                            400,
                            {
                                "code": 400,
                                "message": f"unknown topics {bad}",
                            },
                        )
                    # dedupe: duplicate topics would double-register
                    # the queue (and leak one copy on unsubscribe)
                    wanted = list(dict.fromkeys(requested)) or list(TOPICS)
                    sub = api.chain.events.subscribe(wanted)
                except Exception as e:
                    return self._send(500, {"code": 500, "message": str(e)})
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.end_headers()
                # idle window must exceed the slot interval or steady-state
                # consumers get disconnected between block events
                idle_limit = getattr(
                    api,
                    "sse_idle_seconds",
                    4.0 * api.chain.spec.SECONDS_PER_SLOT,
                )
                try:
                    while True:
                        try:
                            ev = sub.get(timeout=idle_limit)
                        except _queue.Empty:
                            break
                        frame = (
                            f"event: {ev['event']}\n"
                            f"data: {json.dumps(ev['data'])}\n\n"
                        )
                        self.wfile.write(frame.encode())
                        self.wfile.flush()
                except OSError:
                    pass  # client went away mid-stream
                finally:
                    api.chain.events.unsubscribe(sub)

            def do_POST(self):
                # classify() routes read-shaped POSTs (duties) to the
                # expensive_read class — block publishes must never
                # queue behind a committee-walk stampede
                cls_ = classify("POST", self.path)
                endpoint = _endpoint_label(self.path)
                try:
                    slot = api.admission.acquire(cls_, endpoint)
                except AdmissionError as e:
                    return self._send_shed(e)
                t0 = time.perf_counter()
                try:
                    with slot:
                        length = int(
                            self.headers.get("Content-Length", 0)
                        )
                        body = self.rfile.read(length)
                        out = api.handle_post(self.path, body)
                    self._send(200, out)
                except AdmissionError as e:
                    self._send_shed(e)
                except ApiError as e:
                    self._send(
                        e.code, {"code": e.code, "message": e.message}
                    )
                except Exception as e:
                    self._send(400, {"code": 400, "message": str(e)})
                finally:
                    dt = time.perf_counter() - t0
                    _HTTP_SECONDS.labels("POST", endpoint).observe(dt)
                    _HTTP_CLASS_SECONDS.labels(cls_).observe(dt)

        self._httpd = PooledHTTPServer((host, port), Handler)
        self.port = self._httpd.server_port
        self._thread = None

    # --------------------------------------------------- admission plane

    # paths whose responses are immutable within a TTL window AND
    # invalidated on import: finalized/head/justified state reads and
    # blob sidecars by block id
    _CACHEABLE_STATE_IDS = frozenset({"head", "finalized", "justified"})

    def _cache_for(self, path: str):
        parts = [p for p in path.split("?")[0].split("/") if p]
        if parts[:4] == ["eth", "v1", "beacon", "blob_sidecars"]:
            return self._hot_caches["blob_sidecars"]
        if parts[:4] == ["eth", "v1", "beacon", "light_client"]:
            return self._hot_caches["light_client"]
        if (
            parts[:4] == ["eth", "v1", "beacon", "states"]
            and len(parts) >= 5
            and parts[4] in self._CACHEABLE_STATE_IDS
        ):
            return self._hot_caches["state_reads"]
        return None

    def _cached_get(self, path: str, headers=None):
        """handle_get through the hot-read TTL caches: a repeated read
        of a hot immutable key costs ONE store/state hit per TTL
        window. Only 200s are cached; errors always re-resolve."""
        cache = self._cache_for(path)
        if cache is None:
            return self.handle_get(path, headers)
        key = path
        is_lc = cache is self._hot_caches["light_client"]
        if is_lc and headers is not None and (
            "application/octet-stream" in headers.get("Accept", "")
        ):
            # light-client endpoints negotiate JSON vs SSZ — the two
            # renderings must never share a cache slot
            key = path + "#ssz"
        hit, value = cache.get(key)
        if hit:
            out = value
        else:
            # capture the generation BEFORE resolving: if an import
            # invalidates while we compute, put() discards our
            # (old-head) response instead of caching it past the
            # invalidation
            gen = cache.generation
            out = self.handle_get(path, headers)
            cache.put(key, out, generation=gen)
        if is_lc:
            self._account_lc_serve(path, out)
        return out

    def _account_lc_serve(self, path: str, out):
        """Per-request light-client serving record: one `lc_served`
        journal event (cache hits included — the count is a function of
        the request stream, never of TTL timing) plus byte accounting.
        JSON responses are PRE-RENDERED bytes tuples (the resolver
        encodes once; cache hits re-serve the same bytes), so counting
        is a len() — streams count their own bytes at write time."""
        from lighthouse_tpu.http_api.streaming import (
            SszStream,
            count_served_bytes,
        )

        endpoint = _endpoint_label(path)
        if isinstance(out, tuple):
            count_served_bytes(endpoint, len(out[0]))
        elif not isinstance(out, SszStream):  # pragma: no cover
            count_served_bytes(endpoint, len(json.dumps(out)))
        self.chain.journal.emit("lc_served", endpoint=endpoint)

    def _invalidate_hot_caches(self, block_root=None):
        """Chain import hook: a new block moves the head and lands new
        sidecars, so every cached hot read is stale NOW, not at TTL."""
        for cache in self._hot_caches.values():
            cache.invalidate()

    # REST endpoints whose POST enqueues beacon-processor work, mapped
    # to the queue kind whose shed window gates them with a 429
    _SATURATION_GATED = {
        "/eth/v1/beacon/pool/attestations": "gossip_attestation",
        "/eth/v1/validator/aggregate_and_proofs": "gossip_aggregate",
        "/eth/v1/beacon/pool/sync_committees": "sync_message",
        "/eth/v1/validator/contribution_and_proofs": "sync_message",
    }

    def _check_processor_saturation(self, path: str):
        """429 + Retry-After on enqueue endpoints while the matching
        work kind's shed window is open — the REST edge refuses the
        same work the gossip edge is already shedding. Block publishes
        are forensic work and are never gated."""
        kind = self._SATURATION_GATED.get(path.split("?")[0])
        if kind is None:
            return
        processor = getattr(
            getattr(self, "node", None), "processor", None
        )
        if processor is None:
            return
        if processor.shedder.is_shedding(kind):
            count_shed(
                _endpoint_label(path), "processor_saturated"
            )
            raise AdmissionError(
                429,
                f"processor saturated ({kind} shed window open)",
                retry_after=2.0,
            )

    def overload_state(self) -> dict:
        """The health-plane overload document: HTTP admission state,
        hot-cache occupancy, accept-queue sheds, and the beacon
        processor's shed windows."""
        doc = {
            "http": self.admission.state(),
            "caches": {
                name: c.stats()
                for name, c in self._hot_caches.items()
            },
            "accept_shed": getattr(self._httpd, "accept_shed", 0),
        }
        processor = getattr(
            getattr(self, "node", None), "processor", None
        )
        if processor is not None:
            doc["processor"] = processor.shed_state()
        # verification-bus control surface: knobs (max hold, fill
        # target, per-class deadlines) + live batch-formation counters,
        # so the self-tuning loop can read what it would adjust
        bus = getattr(self.chain, "verification_bus", None)
        if bus is not None:
            doc["verification_bus"] = bus.stats()
        # device-plane fault domain: breaker states per (plane, bucket),
        # fault/failover/transition counters — what an operator checks
        # when the node silently degrades to host tiers
        from lighthouse_tpu.device_plane import GUARD

        doc["device_plane"] = GUARD.stats()
        return doc

    # ------------------------------------------------------------ routing

    def handle_get(self, path: str, headers: dict | None = None):
        chain = self.chain
        parts = [p for p in path.split("?")[0].split("/") if p]
        if path == "/metrics":
            # refresh the attestation-cache gauges at scrape time
            for cache, stat, value in (
                ("attester", "hits", chain.attester_cache.hits),
                ("attester", "misses", chain.attester_cache.misses),
                ("early_attester", "hits",
                 chain.early_attester_cache.hits),
                ("proposer", "hits", chain.proposer_cache.hits),
                ("proposer", "misses", chain.proposer_cache.misses),
            ):
                _CACHE_STATS.labels(cache, stat).set(value)
            return (REGISTRY.render().encode(), "text/plain; version=0.0.4")
        if parts[:3] == ["eth", "v1", "node"] and len(parts) >= 4:
            if parts[3] == "version":
                return {"data": {"version": VERSION}}
            if parts[3] == "health":
                # standard semantics: 200 synced, 206 syncing — external
                # tooling health-checks read the status code only
                if self._sync_distance() > 1:
                    raise ApiError(206, "syncing")
                return {}
            if parts[3] == "identity":
                net = getattr(self, "net", None)
                return {
                    "data": {
                        "peer_id": getattr(net, "node_id", "in-process"),
                        "enr": "",
                        "p2p_addresses": [
                            f"/ip4/{net.host}/tcp/{net.tcp_port}"
                        ]
                        if net is not None
                        else [],
                        "discovery_addresses": [
                            f"/ip4/{net.host}/udp/{net.udp_port}"
                        ]
                        if net is not None
                        else [],
                    }
                }
            if parts[3] == "peers" and len(parts) == 4:
                net = getattr(self, "net", None)
                peers = (
                    [
                        self._peer_json(pid)
                        # snapshot: network threads mutate peers
                        for pid in list(getattr(net, "peers", {}))
                    ]
                    if net is not None
                    else []
                )
                return {
                    "data": peers,
                    "meta": {"count": len(peers)},
                }
            if parts[3] == "peers" and len(parts) == 5:
                net = getattr(self, "net", None)
                if net is None or parts[4] not in getattr(
                    net, "peers", {}
                ):
                    raise ApiError(404, "peer not found")
                return {"data": self._peer_json(parts[4])}
            if parts[3] == "peer_count":
                net = getattr(self, "net", None)
                n = len(getattr(net, "peers", {})) if net else 0
                return {
                    "data": {
                        "connected": str(n),
                        "connecting": "0",
                        "disconnected": "0",
                        "disconnecting": "0",
                    }
                }
            if parts[3] == "syncing":
                distance = self._sync_distance()
                return {
                    "data": {
                        "head_slot": str(chain.head_state.slot),
                        "sync_distance": str(distance),
                        # >1: the clock running one slot ahead of the
                        # head is steady-state, not syncing
                        "is_syncing": distance > 1,
                        "is_optimistic": chain.fork_choice.is_optimistic(
                            chain.head_root
                        ),
                        "el_offline": False,
                    }
                }
        # ---- debug namespace (http_api/src/lib.rs debug routes) ----
        if (
            len(parts) >= 4
            and parts[0] == "eth"
            and parts[2] == "debug"
        ):
            if parts[3:5] == ["beacon", "heads"]:
                # ONE snapshot for both walks: the import thread appends
                # to proto.nodes, and parent indices must agree with the
                # enumeration they were computed against
                nodes = list(chain.fork_choice.proto.nodes)
                is_parent = {
                    n.parent for n in nodes if n.parent is not None
                }
                heads = [
                    {
                        "root": "0x" + n.root.hex(),
                        "slot": str(n.slot),
                        "execution_optimistic":
                            chain.fork_choice.is_optimistic(n.root),
                    }
                    for i, n in enumerate(nodes)
                    if i not in is_parent
                ]
                return {"data": heads}
            if parts[3:5] == ["beacon", "states"] and len(parts) == 6:
                # full state as SSZ (the v2 octet-stream form — the JSON
                # rendering of a whole BeaconState is not served),
                # STREAMED: the handler never materializes the encoded
                # state, its peak allocation is one chunk (PR 10's
                # remaining idea, landed with the light-client plane)
                from lighthouse_tpu.http_api.streaming import SszStream

                state = self._resolve_state(parts[5])
                return SszStream.for_value(
                    type(state), state, endpoint="debug_state"
                )
            if parts[3] == "fork_choice":
                # snapshot before iterating AND before parent-index
                # lookups — the import thread appends concurrently
                proto_nodes = list(chain.fork_choice.proto.nodes)
                nodes = []
                for node in proto_nodes:
                    parent_root = (
                        proto_nodes[node.parent].root
                        if node.parent is not None
                        else b""
                    )
                    nodes.append(
                        {
                            "slot": str(node.slot),
                            "block_root": "0x" + node.root.hex(),
                            "parent_root": "0x" + parent_root.hex(),
                            "justified_epoch": str(node.justified_epoch),
                            "finalized_epoch": str(node.finalized_epoch),
                            "weight": str(node.weight),
                            "validity": node.execution_status,
                        }
                    )
                jc_epoch, jc_root = chain.fork_choice.justified_checkpoint
                fc_epoch, fc_root = chain.fork_choice.finalized_checkpoint
                return {
                    "justified_checkpoint": {
                        "epoch": str(jc_epoch),
                        "root": "0x" + jc_root.hex(),
                    },
                    "finalized_checkpoint": {
                        "epoch": str(fc_epoch),
                        "root": "0x" + fc_root.hex(),
                    },
                    "fork_choice_nodes": nodes,
                }
        if parts[:3] == ["eth", "v1", "beacon"]:
            if parts[3] == "light_client" and len(parts) >= 5:
                return self._light_client(parts, path, headers)
            if parts[3] == "genesis":
                st = chain.head_state
                return {
                    "data": {
                        "genesis_time": str(st.genesis_time),
                        "genesis_validators_root": "0x"
                        + bytes(st.genesis_validators_root).hex(),
                        "genesis_fork_version": "0x"
                        + bytes(chain.spec.GENESIS_FORK_VERSION).hex(),
                    }
                }
            if parts[3] == "states" and len(parts) >= 6:
                state = self._resolve_state(parts[4])
                if parts[5] == "fork":
                    f = state.fork
                    return {
                        "data": {
                            "previous_version": "0x"
                            + bytes(f.previous_version).hex(),
                            "current_version": "0x"
                            + bytes(f.current_version).hex(),
                            "epoch": str(f.epoch),
                        }
                    }
                if parts[5] == "committees":
                    return self._committees(state, self._query(path))
                if parts[5] == "validator_balances":
                    q = self._query(path)
                    wanted = self._parse_validator_ids(q.get("id"))
                    return {
                        "data": [
                            {"index": str(i), "balance": str(b)}
                            for i, b in enumerate(state.balances)
                            if wanted is None or i in wanted
                        ]
                    }
                if parts[5] == "sync_committees":
                    if not hasattr(state, "current_sync_committee"):
                        raise ApiError(400, "pre-altair state")
                    q = self._query(path)
                    spec = chain.spec
                    period = lambda e: (  # noqa: E731
                        e // spec.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
                    )
                    cur_epoch = spec.slot_to_epoch(state.slot)
                    qe = self._int_q(q, "epoch")
                    epoch = qe if qe is not None else cur_epoch
                    if period(epoch) == period(cur_epoch):
                        committee = state.current_sync_committee
                    elif period(epoch) == period(cur_epoch) + 1:
                        committee = state.next_sync_committee
                    else:
                        raise ApiError(
                            400, f"epoch {epoch} outside known periods"
                        )
                    indices = [
                        str(chain.pubkey_cache.index_of(bytes(pk)))
                        for pk in committee.pubkeys
                    ]
                    # validator_aggregates: members grouped per
                    # subcommittee (required by the API schema)
                    sub = max(
                        spec.SYNC_COMMITTEE_SIZE
                        // spec.SYNC_COMMITTEE_SUBNET_COUNT,
                        1,
                    )
                    aggregates = [
                        indices[i : i + sub]
                        for i in range(0, len(indices), sub)
                    ]
                    return {
                        "data": {
                            "validators": indices,
                            "validator_aggregates": aggregates,
                        }
                    }
                if parts[5] == "finality_checkpoints":
                    def cp(c):
                        return {
                            "epoch": str(c.epoch),
                            "root": "0x" + bytes(c.root).hex(),
                        }

                    return {
                        "data": {
                            "previous_justified": cp(
                                state.previous_justified_checkpoint
                            ),
                            "current_justified": cp(
                                state.current_justified_checkpoint
                            ),
                            "finalized": cp(state.finalized_checkpoint),
                        }
                    }
                if parts[5] == "root":
                    return {
                        "data": {
                            "root": "0x"
                            + type(state).hash_tree_root(state).hex()
                        }
                    }
                if parts[5] == "validators":
                    q = self._query(path)
                    wanted = self._parse_validator_ids(q.get("id"))
                    epoch = chain.spec.slot_to_epoch(state.slot)
                    out = []
                    for i, v in enumerate(state.validators):
                        if i % 512 == 0:
                            check_deadline("validator walk")
                        if wanted is not None and i not in wanted:
                            continue
                        out.append(
                            {
                                "index": str(i),
                                "balance": str(state.balances[i]),
                                "status": _validator_status(
                                    v, state.balances[i], epoch
                                ),
                                "validator": {
                                    "pubkey": "0x"
                                    + bytes(v.pubkey).hex(),
                                    "effective_balance": str(
                                        v.effective_balance
                                    ),
                                    "slashed": bool(v.slashed),
                                    "activation_epoch": str(
                                        v.activation_epoch
                                    ),
                                    "exit_epoch": str(v.exit_epoch),
                                },
                            }
                        )
                    return {"data": out}
            if parts[3] == "blob_sidecars" and len(parts) >= 5:
                # GET /eth/v1/beacon/blob_sidecars/{block_id}[?indices=..]
                # (deneb beacon API): sidecars are served from the store
                # within the retention window; an importable block with
                # no blobs returns an empty list, not a 404
                block = self._resolve_block(parts[4])
                root = type(block.message).hash_tree_root(block.message)
                sidecars = chain.store.get_blob_sidecars(root)
                q = self._query(path)
                if "indices" in q:
                    try:
                        wanted = {
                            int(i) for i in q["indices"].split(",") if i
                        }
                    except ValueError:
                        raise ApiError(400, "invalid indices") from None
                    sidecars = [
                        sc for sc in sidecars if int(sc.index) in wanted
                    ]
                return {
                    "data": [
                        to_json(type(sc), sc) for sc in sidecars
                    ]
                }
            if parts[3] == "headers" and len(parts) >= 5:
                block = self._resolve_block(parts[4])
                header = self._header_json(block)
                return {"data": header}
            if (
                parts[3] == "blocks"
                and len(parts) == 6
                and parts[5] == "root"
            ):
                block = self._resolve_block(parts[4])
                return {
                    "data": {
                        "root": "0x"
                        + type(block.message)
                        .hash_tree_root(block.message)
                        .hex()
                    }
                }
            if (
                parts[3] == "blocks"
                and len(parts) == 6
                and parts[5] == "attestations"
            ):
                block = self._resolve_block(parts[4])
                return {
                    "data": [
                        to_json(type(a), a)
                        for a in block.message.body.attestations
                    ]
                }
        if parts[:3] == ["eth", "v1", "config"] and len(parts) >= 4:
            if parts[3] == "spec":
                return {"data": self._spec_json()}
            if parts[3] == "fork_schedule":
                return {"data": self._fork_schedule()}
            if parts[3] == "deposit_contract":
                return {
                    "data": {
                        "chain_id": str(
                            getattr(chain.spec, "DEPOSIT_CHAIN_ID", 1)
                        ),
                        "address": "0x" + "00" * 20,
                    }
                }
        if parts[:2] == ["lighthouse", "spans"]:
            # recent span trees from the data-plane tracer (JSON sibling
            # of the /metrics scrape; ?limit=N bounds the response)
            q = self._query(path)
            limit = self._int_q(q, "limit")
            return {
                "data": TRACER.recent(limit),
                "meta": {
                    "enabled": TRACER.enabled,
                    "capacity": TRACER.capacity,
                    "completed_roots": TRACER.completed_roots,
                },
            }
        if parts[:2] == ["lighthouse", "slot_budget"]:
            # per-import critical-path waterfalls + stage quantiles from
            # the slot-budget recorder; ?limit=N bounds the waterfall list
            q = self._query(path)
            limit = self._int_q(q, "limit")
            recorder = chain.slot_budget
            return {
                "data": {
                    **recorder.summary(),
                    "recent": recorder.recent(limit),
                }
            }
        if parts[:3] == ["lighthouse", "da", "columns"] and len(
            parts
        ) >= 4:
            # GET /lighthouse/da/columns/{block_id}[?indices=..]: the
            # verified column sidecars a column-mode node currently
            # SERVES (held in the column checker until finality
            # pruning), scoped to the node's custody assignment when a
            # node handle is wired — the surface DAS samplers poll. A
            # root nobody imported resolves to an empty list (that
            # absence IS the withholding signal), never a 404.
            ident = parts[3]
            if ident.startswith("0x"):
                try:
                    root = bytes.fromhex(ident[2:])
                except ValueError:
                    raise ApiError(400, "invalid block root") from None
            else:
                block = self._resolve_block(ident)
                root = type(block.message).hash_tree_root(
                    block.message
                )
            cols_fn = getattr(chain.da_checker, "columns_for", None)
            cols = cols_fn(root) if cols_fn is not None else []
            node = getattr(self, "node", None)
            if node is not None and getattr(node, "column_mode", False):
                custody = set(node.custody_columns)
                cols = [
                    sc for sc in cols if int(sc.index) in custody
                ]
            q = self._query(path)
            if "indices" in q:
                try:
                    wanted = {
                        int(i) for i in q["indices"].split(",") if i
                    }
                except ValueError:
                    raise ApiError(400, "invalid indices") from None
                cols = [sc for sc in cols if int(sc.index) in wanted]
            return {"data": [to_json(type(sc), sc) for sc in cols]}
        if parts[:3] == ["lighthouse", "tpu", "stats"]:
            # lighthouse namespace analog: process + chain internals
            return {
                "data": {
                    "metrics": dict(chain.metrics),
                    "attester_cache": {
                        "hits": chain.attester_cache.hits,
                        "misses": chain.attester_cache.misses,
                    },
                    "proposer_cache": {
                        "hits": chain.proposer_cache.hits,
                        "misses": chain.proposer_cache.misses,
                    },
                    "snapshots": len(chain._snapshots),
                }
            }
        if parts[:2] == ["lighthouse", "health"]:
            return {"data": self._health_doc()}
        if parts[:2] == ["lighthouse", "events"]:
            # per-object forensic queries over the node's lifecycle
            # journal: ?root=0x…&slot=…&kind=…&peer=…&outcome=…&limit=…
            q = self._query(path)
            kind = q.get("kind")
            from lighthouse_tpu.common.events_journal import KINDS

            if kind is not None and kind not in KINDS:
                raise ApiError(400, f"unknown event kind {kind!r}")
            root = q.get("root")
            if root is not None:
                try:
                    bytes.fromhex(root[2:] if root.startswith("0x") else root)
                except ValueError:
                    raise ApiError(400, "invalid root") from None
            events = chain.journal.query(
                root=root,
                slot=self._int_q(q, "slot"),
                kind=kind,
                peer=q.get("peer"),
                outcome=q.get("outcome"),
                limit=self._int_q(q, "limit"),
            )
            return {
                "data": events,
                "meta": chain.journal.stats(),
            }
        if parts[:2] == ["lighthouse", "compiles"]:
            # the process compile ledger: every jit dispatch with its
            # impl key, shape bucket, cold/warm status and wall
            # duration — tier-1's cold-compile dominance and chip
            # runs as structured data instead of log archaeology.
            # PROCESS-global (jit caches are process state, not chain
            # state), unlike /lighthouse/events.
            from lighthouse_tpu.common.compile_ledger import LEDGER

            q = self._query(path)
            return {
                "data": LEDGER.entries(self._int_q(q, "limit")),
                "meta": LEDGER.stats(),
            }
        if parts[:3] == ["lighthouse", "metrics", "snapshot"]:
            # flat registry snapshot (series key -> value): the remote
            # half of the snapshot/diff API multi-node tests assert
            # convergence and bounded scores from
            return {"data": REGISTRY.snapshot()}
        if parts[:3] == ["eth", "v2", "beacon"]:
            if parts[3] == "blocks" and len(parts) >= 5:
                block = self._resolve_block(parts[4])
                accept = (
                    headers.get("Accept", "") if headers is not None
                    else ""
                )
                if "application/octet-stream" in accept:
                    # standard SSZ content negotiation — the checkpoint
                    # sync client pulls the anchor block this way
                    return (
                        block.to_bytes(),
                        "application/octet-stream",
                    )
                return {
                    "version": chain.spec.fork_name_at_epoch(
                        chain.spec.slot_to_epoch(block.message.slot)
                    ),
                    "data": to_json(type(block), block),
                }
        if parts[:3] == ["eth", "v1", "validator"]:
            if parts[3] == "duties" and parts[4] == "proposer":
                epoch = int(parts[5])
                return self._proposer_duties(epoch)
            if parts[3] == "attestation_data":
                q = self._query(path)
                data = chain.produce_attestation_data(
                    int(q["slot"]), int(q["committee_index"])
                )
                return {"data": to_json(type(data), data)}
            if parts[3] == "aggregate_attestation":
                q = self._query(path)
                root = bytes.fromhex(q["attestation_data_root"][2:])
                agg = None
                for a in chain.naive_pool.aggregates_at_slot(
                    int(q["slot"])
                ):
                    if type(a.data).hash_tree_root(a.data) == root:
                        agg = a
                        break
                if agg is None:
                    raise ApiError(404, "no aggregate for data root")
                return {"data": to_json(type(agg), agg)}
            if parts[3] == "sync_committee_contribution":
                q = self._query(path)
                c = chain.sync_message_pool.get_contribution(
                    int(q["slot"]),
                    bytes.fromhex(q["beacon_block_root"][2:]),
                    int(q["subcommittee_index"]),
                )
                if c is None:
                    raise ApiError(404, "no contribution known")
                return {"data": to_json(type(c), c)}
        if (
            parts[:3] == ["eth", "v1", "validator"]
            and len(parts) >= 5
            and parts[3] == "blinded_blocks"
        ):
            # builder flow (http_api/src/lib.rs blinded-block production)
            q = self._query(path)
            block = chain.produce_blinded_block_unsigned(
                int(parts[4]),
                bytes.fromhex(q["randao_reveal"][2:]),
                bytes.fromhex(q["graffiti"][2:])
                if "graffiti" in q
                else b"\x00" * 32,
            )
            return {
                "version": chain.spec.fork_name_at_epoch(
                    chain.spec.slot_to_epoch(block.slot)
                ),
                "data": to_json(type(block), block),
            }
        if parts[:3] == ["eth", "v2", "validator"]:
            if parts[3] == "blocks" and len(parts) >= 5:
                q = self._query(path)
                block = chain.produce_block_unsigned(
                    int(parts[4]),
                    bytes.fromhex(q["randao_reveal"][2:]),
                    bytes.fromhex(q["graffiti"][2:])
                    if "graffiti" in q
                    else b"\x00" * 32,
                )
                return {
                    "version": chain.spec.fork_name_at_epoch(
                        chain.spec.slot_to_epoch(block.slot)
                    ),
                    "data": to_json(type(block), block),
                }
        raise ApiError(404, f"unknown route {path}")

    def handle_post(self, path: str, body: bytes):
        chain = self.chain
        # backpressure surfaces on the REST edge too: enqueue endpoints
        # answer 429 while the matching processor kind is shedding
        self._check_processor_saturation(path)
        parts = [p for p in path.split("?")[0].split("/") if p]
        if (
            parts[:4] == ["eth", "v1", "validator", "liveness"]
            and len(parts) == 5
        ):
            # standard liveness endpoint backing doppelganger detection:
            # a validator is "live" in an epoch if the chain has seen an
            # attestation from it (observed_attesters first-seen cache)
            epoch = int(parts[4])
            indices = [int(i) for i in json.loads(body)]
            return {
                "data": [
                    {
                        "index": str(i),
                        "is_live": chain.observed_attesters.is_known(
                            epoch, i
                        ),
                    }
                    for i in indices
                ]
            }
        if path == "/eth/v1/beacon/blocks":
            # decode happens on the SAME thread that imports: stash it
            # as a slot-budget pre-stage so the import's waterfall
            # starts at the bytes, not at the decoded object
            from lighthouse_tpu.common import slot_budget

            with slot_budget.pre_stage("decode"):
                doc = json.loads(body)
                slot = int(doc["message"]["slot"])
                fork = chain.spec.fork_name_at_epoch(
                    chain.spec.slot_to_epoch(slot)
                )
                cls = chain.t.signed_block_classes[fork]
                block = from_json(cls, doc)
            chain.process_block(block)
            return {}
        if path == "/eth/v1/beacon/blinded_blocks":
            # unblind via the payload cache / builder reveal, then import
            doc = json.loads(body)
            slot = int(doc["message"]["slot"])
            fork = chain.spec.fork_name_at_epoch(
                chain.spec.slot_to_epoch(slot)
            )
            cls = chain.t.signed_blinded_block_classes[fork]
            chain.import_blinded_block(from_json(cls, doc))
            return {}
        if path == "/eth/v1/validator/beacon_committee_subscriptions":
            # duty-driven subnet subscriptions (attestation_subnets.rs
            # validator_subscriptions): the VC announces upcoming duties
            # so the BN joins the right beacon_attestation_{id} topics
            node = getattr(self, "node", None)
            if node is None:
                raise ApiError(400, "no network service attached")
            for s in json.loads(body):
                node.subscribe_for_attestation_duty(
                    int(s["slot"]), int(s["committee_index"])
                )
            return {}
        if path == "/eth/v1/validator/register_validator":
            regs = [
                from_json(chain.t.SignedValidatorRegistrationData, d)
                for d in json.loads(body)
            ]
            for r in regs:
                chain.validator_registrations[bytes(r.message.pubkey)] = r
            if chain.builder is not None:
                chain.builder.register_validators(regs)
            return {}
        if path == "/eth/v1/beacon/pool/attestations":
            docs = json.loads(body)
            atts = [from_json(self.chain.t.Attestation, d) for d in docs]
            results = chain.process_unaggregated_attestations(atts)
            return self._pool_response(results)
        if path == "/eth/v1/beacon/pool/sync_committees":
            docs = json.loads(body)
            msgs = [
                from_json(chain.t.SyncCommitteeMessage, d) for d in docs
            ]
            return self._pool_response(chain.process_sync_messages(msgs))
        if path == "/eth/v1/validator/aggregate_and_proofs":
            docs = json.loads(body)
            saps = [
                from_json(chain.t.SignedAggregateAndProof, d)
                for d in docs
            ]
            return self._pool_response(
                chain.process_aggregated_attestations(saps)
            )
        if path == "/eth/v1/validator/contribution_and_proofs":
            docs = json.loads(body)
            caps = [
                from_json(chain.t.SignedContributionAndProof, d)
                for d in docs
            ]
            return self._pool_response(
                chain.process_signed_contributions(caps)
            )
        if (
            parts[:4] == ["eth", "v1", "validator", "duties"]
            and len(parts) == 6
        ):
            indices = [int(i) for i in json.loads(body)]
            if parts[4] == "attester":
                return self._attester_duties(int(parts[5]), indices)
            if parts[4] == "sync":
                return self._sync_duties(int(parts[5]), indices)
        raise ApiError(404, f"unknown route {path}")

    # ------------------------------------------------- light-client plane

    # standard beacon-API cap on updates-by-range responses
    MAX_LC_UPDATES = 16

    def _light_client(self, parts, path: str, headers):
        """GET /eth/v1/beacon/light_client/{bootstrap/{root} | updates
        ?start_period=&count= | finality_update | optimistic_update}.

        Served entirely from the producer's retained documents — no
        state walk, no store replay — behind the cheap_read admission
        class with a per-import-invalidated TTL cache in front. SSZ
        responses (Accept: application/octet-stream) STREAM; the
        updates range streams as length-prefixed frames."""
        from lighthouse_tpu.http_api.streaming import SszStream

        chain = self.chain
        producer = getattr(chain, "light_client_producer", None)
        if producer is None:
            raise ApiError(404, "light-client serving not enabled")
        t = chain.t
        which = parts[4]
        want_ssz = headers is not None and (
            "application/octet-stream" in headers.get("Accept", "")
        )
        fork = chain.spec.fork_name_at_epoch(
            chain.spec.slot_to_epoch(chain.head_state.slot)
        )

        def render_json(payload):
            # encode ONCE at resolve time: the TTL cache holds rendered
            # bytes, so a cache hit re-serves without re-serializing
            # (the byte accounting is then a len(), never a dumps)
            return (json.dumps(payload).encode(), "application/json")

        def one(doc, cls, endpoint):
            if doc is None:
                raise ApiError(404, f"no {endpoint} available")
            if want_ssz:
                return SszStream.for_value(cls, doc, endpoint=endpoint)
            return render_json(
                {"version": fork, "data": to_json(cls, doc)}
            )

        if which == "bootstrap" and len(parts) == 6:
            root = parts[5]
            try:
                root_bytes = bytes.fromhex(
                    root[2:] if root.startswith("0x") else root
                )
            except ValueError:
                raise ApiError(400, "invalid block root") from None
            doc = producer.bootstrap_for(root_bytes)
            if doc is None:
                raise ApiError(
                    404, "no bootstrap for that block root"
                )
            return one(doc, t.LightClientBootstrap, "lc_bootstrap")
        if which == "updates":
            q = self._query(path)
            start = self._int_q(q, "start_period")
            count = self._int_q(q, "count")
            if start is None or count is None:
                raise ApiError(400, "start_period and count required")
            count = min(count, self.MAX_LC_UPDATES)
            updates = producer.updates_range(start, count)
            if want_ssz:
                return SszStream.framed(
                    [(t.LightClientUpdate, u) for u in updates],
                    endpoint="lc_updates",
                )
            return render_json(
                {
                    "data": [
                        {
                            "version": fork,
                            "data": to_json(t.LightClientUpdate, u),
                        }
                        for u in updates
                    ]
                }
            )
        if which == "finality_update":
            return one(
                producer.finality_update,
                t.LightClientFinalityUpdate,
                "lc_finality_update",
            )
        if which == "optimistic_update":
            return one(
                producer.optimistic_update,
                t.LightClientOptimisticUpdate,
                "lc_optimistic_update",
            )
        raise ApiError(404, f"unknown light_client route {path}")

    # ------------------------------------------------------------ helpers

    @staticmethod
    def _int_q(q: dict, name: str):
        """Integer query param or a 400 (the API's invalid-param code,
        never a 500); None when absent."""
        if name not in q:
            return None
        try:
            v = int(q[name])
        except ValueError:
            raise ApiError(400, f"invalid {name} {q[name]!r}") from None
        if v < 0:
            raise ApiError(400, f"negative {name}")
        return v

    @staticmethod
    def _query(path: str) -> dict:
        from urllib.parse import parse_qs, urlparse

        return {
            k: v[0] for k, v in parse_qs(urlparse(path).query).items()
        }

    @staticmethod
    def _pool_response(results):
        failures = [
            {"index": i, "message": str(r)}
            for i, r in enumerate(results)
            if isinstance(r, Exception)
        ]
        if failures:
            raise ApiError(400, json.dumps(failures))
        return {}

    def _attester_duties(self, epoch: int, indices):
        """POST /eth/v1/validator/duties/attester/{epoch}
        (http_api/src/lib.rs attester-duties route): committee assignment
        per requested validator."""
        from lighthouse_tpu.state_processing.helpers import CommitteeCache

        chain = self.chain
        state = chain.state_for_epoch(epoch)
        cache = CommitteeCache(state, epoch, chain.spec)
        wanted = set(indices)
        duties = []
        for slot in range(
            chain.spec.epoch_start_slot(epoch),
            chain.spec.epoch_start_slot(epoch + 1),
        ):
            check_deadline("attester duties")
            for index in range(cache.committees_per_slot):
                committee = cache.get_beacon_committee(slot, index)
                for pos, v in enumerate(committee):
                    if v in wanted:
                        duties.append(
                            {
                                "pubkey": "0x"
                                + bytes(
                                    state.validators[v].pubkey
                                ).hex(),
                                "validator_index": str(v),
                                "committee_index": str(index),
                                "committee_length": str(len(committee)),
                                "committees_at_slot": str(
                                    cache.committees_per_slot
                                ),
                                "validator_committee_index": str(pos),
                                "slot": str(slot),
                            }
                        )
        return {"data": duties}

    def _sync_duties(self, epoch: int, indices):
        """POST /eth/v1/validator/duties/sync/{epoch}: membership +
        positions in the sync committee serving `epoch` — the head
        state's current committee for the current period, its next
        committee for the next period (the reference resolves duties by
        the period containing the requested epoch); anything beyond the
        next period is not derivable from the head state."""
        from lighthouse_tpu.beacon_chain.sync_committee_verification import (
            committee_positions,
        )

        chain = self.chain
        state = chain.head_state
        spec = chain.spec
        period = epoch // spec.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
        head_period = spec.slot_to_epoch(
            state.slot
        ) // spec.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
        if period == head_period:
            committee = state.current_sync_committee
        elif period == head_period + 1:
            committee = state.next_sync_committee
        else:
            raise ApiError(
                400,
                f"epoch {epoch} is outside the current and next "
                f"sync-committee periods",
            )
        duties = []
        for v in indices:
            positions = committee_positions(state, v, chain, committee)
            if positions:
                duties.append(
                    {
                        "pubkey": "0x"
                        + bytes(state.validators[v].pubkey).hex(),
                        "validator_index": str(v),
                        "validator_sync_committee_indices": [
                            str(p) for p in positions
                        ],
                    }
                )
        return {"data": duties}

    def _health_doc(self) -> dict:
        """GET /lighthouse/health: one per-node health document — head
        and finality distance, queue depths, peer-score summary, DA
        cache occupancy, journal stats, validator-monitor report — so
        multi-node tests and operators assert node state from data, not
        internals."""
        chain = self.chain
        spec = chain.spec
        fin = chain.finalized_checkpoint
        current_epoch = spec.slot_to_epoch(chain.current_slot())
        doc = {
            "head": {
                "slot": int(chain.head_state.slot),
                "root": "0x" + chain.head_root.hex(),
                "justified_epoch": int(
                    chain.head_state.current_justified_checkpoint.epoch
                ),
                "finalized_epoch": int(fin.epoch),
                "finality_distance_epochs": max(
                    0, int(current_epoch) - int(fin.epoch)
                ),
                "sync_distance": self._sync_distance(),
                "execution_optimistic": chain.fork_choice.is_optimistic(
                    chain.head_root
                ),
            },
            "da": chain.da_checker.stats(),
            "journal": chain.journal.stats(),
            # overload plane: admission state, hot caches, shed windows
            "overload": self.overload_state(),
            "validator_monitor": (
                chain.validator_monitor.health_summary()
            ),
            "metrics": chain.metrics.snapshot(),
        }
        node = getattr(self, "node", None)
        if getattr(node, "column_mode", False):
            # DAS view: deterministic custody assignment plus the
            # sampler's issued/satisfied/flagged counters when a
            # sampler is attached (the sim's DasSampler registers
            # itself on the node)
            doc["da"]["custody"] = {
                "subnets": list(node.custody_subnets),
                "columns": list(node.custody_columns),
            }
            sampler = getattr(node, "das_sampler", None)
            if sampler is not None:
                doc["da"]["sampling"] = sampler.stats()
        processor = getattr(node, "processor", None)
        if processor is not None:
            doc["queues"] = processor.queue_depths()
        # peer summary: scores from the gossip hub (shared scoring
        # plane), quarantine view from the sync manager. dict() takes
        # an atomic snapshot — network threads mutate peers.
        self_id = getattr(node, "node_id", None)
        scores = {}
        hub = getattr(node, "hub", None)
        for pid, peer in dict(getattr(hub, "peers", {})).items():
            score = getattr(peer, "score", None)
            if score is not None and pid != self_id:
                scores[pid] = score
        sync = getattr(self, "sync", None)
        doc["peers"] = {
            "count": len(scores) if scores else (
                len(getattr(sync, "peers", {})) if sync else 0
            ),
            "quarantined": (
                sorted(sync.quarantined.copy())
                if sync is not None
                else []
            ),
            "scores": {
                "min": min(scores.values()),
                "max": max(scores.values()),
                "mean": sum(scores.values()) / len(scores),
                "by_peer": scores,
            }
            if scores
            else None,
        }
        return doc

    def _sync_distance(self) -> int:
        """Slots between the wall clock and the head — the standard
        node/syncing + health signal. 0/1 = synced (the clock leads the
        head by one slot between block arrival and the tick)."""
        chain = self.chain
        return max(0, chain.current_slot() - chain.head_state.slot)

    def _peer_json(self, pid: str) -> dict:
        net = getattr(self, "net", None)
        conn = getattr(net, "peers", {}).get(pid)
        port = getattr(conn, "listen_port", None)
        host = getattr(net, "host", "127.0.0.1")
        return {
            "peer_id": pid,
            "enr": "",
            "last_seen_p2p_address": (
                f"/ip4/{host}/tcp/{port}" if port else ""
            ),
            "state": "connected" if getattr(conn, "alive", True)
            else "disconnected",
            "direction": "outbound",
        }

    def _checkpoint_root(self, which: str) -> tuple:
        """(root, epoch) for finalized|justified; epoch 0 maps the zero
        root onto the chain's genesis/anchor root."""
        chain = self.chain
        cp = (
            chain.finalized_checkpoint
            if which == "finalized"
            else chain.head_state.current_justified_checkpoint
        )
        root = bytes(cp.root) if cp.epoch else chain.genesis_root
        return root, cp.epoch

    def _resolve_state(self, state_id: str):
        """head | finalized | justified | slot — finalized/justified
        resolve to the CHECKPOINT block's post-state (what a
        checkpoint-sync client must receive). Before the first
        finalization the checkpoint IS genesis, so the GENESIS state is
        served (the live head would hand checkpoint clients a
        reorgable anchor); checkpoint-sync clients detect the slot-0
        state and report that the provider has not finalized."""
        # deadline propagation into store/state lookups: a state
        # resolve can replay slots — abort before starting work the
        # request's class budget cannot fund
        check_deadline("state lookup")
        chain = self.chain
        if state_id == "head":
            return chain.head_state
        if state_id in ("justified", "finalized"):
            root, epoch = self._checkpoint_root(state_id)
            if epoch == 0:
                # pre-finalization the checkpoint IS genesis; serving
                # the live head here would hand checkpoint-sync clients
                # a reorgable anchor
                state = chain.store.state_at_slot(0)
                if state is None:
                    raise ApiError(404, "genesis state not found")
                return state
            block = chain.store.get_block(root)
            if block is None:
                raise ApiError(404, f"{state_id} block not found")
            state = chain.store.state_at_slot(block.message.slot)
            if state is None:
                raise ApiError(404, f"{state_id} state not found")
            return state
        if state_id.startswith("0x"):
            raise ApiError(404, "state lookup by root unsupported")
        state = chain.store.state_at_slot(int(state_id))
        if state is None:
            raise ApiError(404, "state not found")
        return state

    def _resolve_block(self, block_id: str):
        check_deadline("block lookup")
        chain = self.chain
        if block_id == "head":
            root = chain.head_root
        elif block_id in ("justified", "finalized"):
            root, _ = self._checkpoint_root(block_id)
        elif block_id.startswith("0x"):
            root = bytes.fromhex(block_id[2:])
        else:
            root = chain.store.get_canonical_block_root(int(block_id))
            if root is None:
                raise ApiError(404, "no canonical block at slot")
        block = chain.store.get_block(root)
        if block is None:
            raise ApiError(404, "block not found")
        return block

    def _header_json(self, block):
        msg = block.message
        body_root = type(msg.body).hash_tree_root(msg.body)
        root = type(msg).hash_tree_root(msg)
        return {
            "root": "0x" + root.hex(),
            "canonical": True,
            "header": {
                "message": {
                    "slot": str(msg.slot),
                    "proposer_index": str(msg.proposer_index),
                    "parent_root": "0x" + bytes(msg.parent_root).hex(),
                    "state_root": "0x" + bytes(msg.state_root).hex(),
                    "body_root": "0x" + body_root.hex(),
                },
                "signature": "0x" + bytes(block.signature).hex(),
            },
        }

    def _parse_validator_ids(self, raw):
        """?id= parsing: indices and 0x pubkeys -> set of indices (the
        standard API accepts both forms)."""
        if raw is None:
            return None
        wanted = set()
        for part in raw.split(","):
            if part.startswith("0x"):
                try:
                    pk = bytes.fromhex(part[2:])
                except ValueError:
                    continue  # malformed id: matches nothing, not a 500
                idx = self.chain.pubkey_cache.index_of(pk)
                if idx is not None:
                    wanted.add(idx)
            else:
                try:
                    wanted.add(int(part))
                except ValueError:
                    continue
        return wanted

    def _committees(self, state, q):
        """GET /eth/v1/beacon/states/{id}/committees — committee member
        lists per (slot, index), filterable by epoch/index/slot
        (http_api/src/lib.rs:920 region)."""
        from lighthouse_tpu.state_processing.helpers import CommitteeCache

        chain = self.chain
        spec = chain.spec
        current = spec.slot_to_epoch(state.slot)
        qe = self._int_q(q, "epoch")
        epoch = qe if qe is not None else current
        # the shuffling window: seeds beyond next epoch don't exist yet,
        # and randao mixes wrap after EPOCHS_PER_HISTORICAL_VECTOR (the
        # reference 400s outside the window rather than serving
        # committees shuffled from a wrapped mix)
        lookback = spec.EPOCHS_PER_HISTORICAL_VECTOR - 2
        if epoch > current + 1 or (
            current > lookback and epoch < current - lookback
        ):
            raise ApiError(400, f"epoch {epoch} outside shuffling window")
        cache = CommitteeCache(state, epoch, spec)
        want_index = self._int_q(q, "index")
        want_slot = self._int_q(q, "slot")
        if want_slot is not None and spec.slot_to_epoch(
            want_slot
        ) != epoch:
            raise ApiError(
                400, f"slot {want_slot} not in epoch {epoch}"
            )
        out = []
        for slot in range(
            spec.epoch_start_slot(epoch), spec.epoch_start_slot(epoch + 1)
        ):
            check_deadline("committee walk")
            if want_slot is not None and slot != want_slot:
                continue
            for index in range(cache.committees_per_slot):
                if want_index is not None and index != want_index:
                    continue
                committee = cache.get_beacon_committee(slot, index)
                out.append(
                    {
                        "index": str(index),
                        "slot": str(slot),
                        "validators": [str(m) for m in committee],
                    }
                )
        return {"data": out}

    def _spec_json(self):
        """GET /eth/v1/config/spec: the full two-tier config as decimal
        strings / 0x-hex (config_and_preset in the reference)."""
        import dataclasses

        out = {}
        for f in dataclasses.fields(self.chain.spec):
            v = getattr(self.chain.spec, f.name)
            if isinstance(v, bytes):
                out[f.name] = "0x" + v.hex()
            elif isinstance(v, int):
                out[f.name] = str(v)
            elif isinstance(v, str):
                out[f.name] = v
        return out

    def _fork_schedule(self):
        spec = self.chain.spec
        sched = [
            {
                "previous_version": "0x"
                + spec.GENESIS_FORK_VERSION.hex(),
                "current_version": "0x" + spec.GENESIS_FORK_VERSION.hex(),
                "epoch": "0",
            }
        ]
        prev = spec.GENESIS_FORK_VERSION
        for name, epoch_attr, ver_attr in (
            ("altair", "ALTAIR_FORK_EPOCH", "ALTAIR_FORK_VERSION"),
            (
                "bellatrix",
                "BELLATRIX_FORK_EPOCH",
                "BELLATRIX_FORK_VERSION",
            ),
        ):
            epoch = getattr(spec, epoch_attr, None)
            ver = getattr(spec, ver_attr, None)
            if epoch is None or ver is None or epoch >= 2**63:
                continue
            sched.append(
                {
                    "previous_version": "0x" + prev.hex(),
                    "current_version": "0x" + ver.hex(),
                    "epoch": str(epoch),
                }
            )
            prev = ver
        return sched

    def _proposer_duties(self, epoch: int):
        """Served from the chain's proposer cache — one whole-epoch
        computation per (epoch, decision root), never a per-slot state
        advance (beacon_proposer_cache.rs)."""
        chain = self.chain
        proposers = chain.proposers_for_epoch(epoch)
        validators = chain.head_state.validators
        start = chain.spec.epoch_start_slot(epoch)
        return {
            "data": [
                {
                    "pubkey": "0x"
                    + bytes(validators[idx].pubkey).hex(),
                    "validator_index": str(idx),
                    "slot": str(start + i),
                }
                for i, idx in enumerate(proposers)
            ]
        }

    # ----------------------------------------------------------- lifecycle

    def start(self):
        # serving side effects live HERE, not in construction: the
        # worker pool and the chain's cache-invalidation hook only
        # exist while the server actually serves
        hooks = getattr(self.chain, "import_hooks", None)
        if hooks is not None and self._invalidate_hot_caches not in hooks:
            hooks.append(self._invalidate_hot_caches)
        self._httpd.start_pool()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self):
        # shutdown() FIRST: once the accept loop is dead no new
        # connection can be enqueued after the workers have taken
        # their exit sentinels (it would hang unserved forever)
        self._httpd.shutdown()
        self._httpd.stop_pool()
        if self._thread:
            self._thread.join(timeout=5)
        # a stopped server must not keep invalidation hooks alive on
        # the chain (tests build many servers per chain)
        hooks = getattr(self.chain, "import_hooks", None)
        if hooks is not None and self._invalidate_hot_caches in hooks:
            hooks.remove(self._invalidate_hot_caches)
