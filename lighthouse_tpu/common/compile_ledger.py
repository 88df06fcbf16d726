"""Compile ledger: every jit (re)compile event as structured data.

Tier-1's wall clock is DOMINATED by cold XLA compiles (ROADMAP:
cold-compile cost decides which tests fit the 870 s window; PR 8's
headline was a 598.5 s -> 6.9 s cold-compile drop), and every chip run
pays a fresh compile per shape — but until now the only record was log
archaeology over bench stdout. This module is the structured
replacement: every device dispatch through the bls/kzg/sharded backends
records one ledger entry

    {t, fn, impl_key, shape, event: cold|warm, duration_s}

where `event` is derived from the jitted object's trace-cache size
(growth == this dispatch traced+compiled a new shape class — the same
detection the `lighthouse_tpu_jit_cache_events_total` xla layer uses)
and `duration_s` is the dispatch-call wall time: JAX dispatch is
asynchronous, so a WARM entry's duration is microseconds of dispatch
overhead while a COLD entry's duration is dominated by trace+compile —
which is exactly the number the ledger exists to capture.

The ledger is PROCESS-GLOBAL (compiles are a property of the process's
jit caches, not of any one chain) and served at ``GET
/lighthouse/compiles``. Set ``LIGHTHOUSE_TPU_COMPILE_LEDGER=/path`` (or
call `LEDGER.configure(path=...)`; `bn --compile-ledger` wires the
flag) to ALSO append every COLD entry to a persistent JSONL file.
Warm dispatches stay in the ring and the counters only: a bench loop
dispatches thousands of warm reps inside its timed region, and a
per-dispatch open/append would inflate exactly the p50/p99 the sweep
exists to measure.
"""

import json
import os
import threading
import time
from collections import deque

from lighthouse_tpu.common.metrics import REGISTRY

_ENTRIES_TOTAL = REGISTRY.counter_vec(
    "lighthouse_tpu_compile_ledger_entries_total",
    "device dispatches recorded in the compile ledger, by entry point "
    "and cold/warm status",
    ("fn", "event"),
)
_COMPILE_SECONDS = REGISTRY.histogram_vec(
    "lighthouse_tpu_compile_wall_seconds",
    "dispatch-call wall time by cold/warm status (cold == dominated by "
    "trace+compile)",
    ("fn", "event"),
    buckets=(0.001, 0.01, 0.1, 1.0, 5.0, 30.0, 120.0, 600.0),
)

DEFAULT_CAPACITY = 4096


class CompileLedger:
    """Bounded in-memory ring of compile/dispatch records with optional
    append-only JSONL persistence."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY, path=None):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, capacity))
        # (fn, id(jitted)) -> last observed trace-cache size; jit
        # objects live forever in the backend caches, so id() is stable
        self._cache_sizes: dict = {}
        self._path = path
        self.recorded = 0
        self.cold = 0

    # ------------------------------------------------------ configuration

    def configure(self, path=None, capacity=None):
        with self._lock:
            if path is not None:
                self._path = path or None
            if capacity is not None:
                self._ring = deque(
                    self._ring, maxlen=max(1, int(capacity))
                )

    @property
    def path(self):
        return self._path

    def reset(self):
        with self._lock:
            self._ring.clear()
            self._cache_sizes.clear()
            self.recorded = 0
            self.cold = 0

    # ------------------------------------------------------------ record

    def record(
        self,
        fn: str,
        impl_key,
        shape: str,
        event: str,
        duration_s: float | None = None,
    ) -> dict:
        entry = {
            "t": time.time(),
            "fn": fn,
            "impl_key": str(impl_key),
            "shape": shape,
            "event": event,
        }
        if duration_s is not None:
            entry["duration_s"] = round(float(duration_s), 6)
        with self._lock:
            self._ring.append(entry)
            self.recorded += 1
            if event == "cold":
                self.cold += 1
            path = self._path
        _ENTRIES_TOTAL.labels(fn, event).inc()
        if duration_s is not None:
            _COMPILE_SECONDS.labels(fn, event).observe(duration_s)
        # persistence is COLD-only: compiles are rare and cost seconds,
        # so the append is noise there; warm dispatches are the timed
        # hot path and must not pay file I/O
        if path and event == "cold":
            try:
                with open(path, "a") as f:
                    f.write(json.dumps(entry) + "\n")
            except OSError:
                # persistence is best-effort: an unwritable path must
                # not take the verify hot path down; the in-memory ring
                # and /lighthouse/compiles keep serving
                with self._lock:
                    self._path = None
        return entry

    def note_dispatch(
        self,
        fn: str,
        jitted,
        impl_key,
        shape: str,
        duration_s: float | None = None,
    ) -> int:
        """Record one dispatch through `jitted`, classifying cold/warm
        from its trace-cache growth. Returns the number of NEW traces
        this dispatch compiled (0 == warm) — the bls backend feeds its
        jit_cache_events xla layer from this return."""
        size = jitted._cache_size()
        grew = 0
        key = (fn, id(jitted))
        with self._lock:
            prev = self._cache_sizes.get(key, 0)
            if size > prev:
                grew = size - prev
                self._cache_sizes[key] = size
        self.record(
            fn,
            impl_key,
            shape,
            "cold" if grew > 0 else "warm",
            duration_s=duration_s,
        )
        return grew

    def note_compile(
        self, fn: str, jitted, impl_key, shape: str, duration_s: float
    ):
        """Record an ahead-of-dispatch compile (`jitted.lower().compile()`)
        as the bucket's cold entry. The dispatch that follows reuses the
        executable but still adds one entry to the jit's trace cache;
        that growth is booked here (one per compile, however many are
        compiled before their dispatches) so the dispatch records warm."""
        key = (fn, id(jitted))
        with self._lock:
            self._cache_sizes[key] = (
                max(self._cache_sizes.get(key, 0), jitted._cache_size()) + 1
            )
        return self.record(fn, impl_key, shape, "cold", duration_s)

    # ------------------------------------------------------------- reads

    def entries(self, limit: int | None = None) -> list:
        with self._lock:
            out = [dict(e) for e in self._ring]
        if limit is not None and limit >= 0:
            out = out[-limit:] if limit else []
        return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "capacity": self._ring.maxlen,
                "size": len(self._ring),
                "recorded": self.recorded,
                "cold": self.cold,
                "warm": self.recorded - self.cold,
                "path": self._path,
            }

    def to_jsonl(self, limit: int | None = None) -> str:
        docs = self.entries(limit)
        if not docs:
            return ""
        return "\n".join(json.dumps(d) for d in docs) + "\n"


def load_jsonl(path) -> list:
    """Read a persisted ledger file back into entry dicts (the
    round-trip test uses this; malformed lines are skipped so a
    torn tail from a killed process can't break the reader)."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    except OSError:
        return []
    return out


LEDGER = CompileLedger(
    path=os.environ.get("LIGHTHOUSE_TPU_COMPILE_LEDGER") or None
)
