"""Lightweight wall-clock span tracer for the data plane.

Role of the reference's `task_executor` timing + tracing-subscriber
layers, shaped for the TPU pipeline: `with span("verify/miller_loop",
n_sets=...)` records a nested wall-clock span. Completed ROOT spans land
in a bounded ring buffer (oldest evicted), exportable as JSONL — one
span tree per line — and served live over `GET /lighthouse/spans`.

Every span also enters a `jax.profiler.TraceAnnotation` of the same
name once the process has imported JAX, so a profiler trace shows the
program's stages on the device trace's clock; with no profiler session
active that is one inactive `TraceMe` per span. The tracer never
imports JAX itself (the validator client and the signing workers run
without it).

Leaf spans are additionally mirrored into registry histograms so the
`/metrics` scrape carries per-stage latency without a second
instrumentation pass:

  * ``<family>/<stage>`` -> ``lighthouse_tpu_<family>_stage_seconds{stage="<stage>"}``
    for the known families (verify, import, trace);
  * anything else        -> ``lighthouse_tpu_span_seconds{span="<name>"}``.

Span taxonomy (the instrumented call tree):

  bus/batch                       one verification-bus batch (root;
                                  attrs batch, trigger, live, submissions)
    verify/canary                 the known-answer sentinel pair
                                  (its own subgroup_check, marshal and
                                  device spans nest under it)
    verify                        one verify_signature_sets batch (root
                                  when called outside the bus)
      verify/subgroup_check       host signature subgroup policy
      verify/hash_to_curve        message hashing (ref path, per set)
      verify/pubkey_aggregation   host G1 aggregation (ref path)
      verify/to_affine            Jacobian -> affine conversion
      verify/miller_loop          ref-backend Miller loop
      verify/final_exp            ref-backend final exponentiation
      verify/marshal              tpu-backend host marshalling (attrs
                                  layout, n_groups, indexed, shape)
        verify/marshal/points     hash memo + simultaneous inversion
          verify/marshal/hash_to_g2   one hash-to-G2 memo miss
        verify/marshal/pack       mask/limb packing + table indices
          verify/marshal/pubkeys  pubkey slots: table indices plus
                                  overflow rows, or per-key packing
                                  (attrs path, slots, overflow, lanes,
                                  wide_sets: the live key lanes and the
                                  sets cut into several)
      verify/compile              a cold bucket's compile-ahead
      verify/rlc_sample           RLC scalar sampling
      verify/device               device dispatch + verdict force
                                  (host<->device transfer + kernels)
  chain/pubkey_table              the HBM pubkey table's build at a
                                  TPU chain's start-up (root; attrs keys)
  import/*                        block-import stages (chain.py)
  trace/*                         JAX trace-time stage attribution for
                                  the jitted device graphs (recorded
                                  once per (re)compile, not per call)

Nesting is tracked per thread; a span closed on one thread never
corrupts another thread's stack. Work handed to a helper thread stays
in its caller's tree through `current()` and `adopt()`: the guarded
executor's watchdog worker adopts the dispatching thread's open span,
so one bus batch is one tree. A child that closes after its adopted
parent closed (an abandoned worker finishing late) is dropped from the
tree; its duration still lands in the histograms. The tracer is
enabled by default with a small ring (256 roots); `configure()` (or the
`bn --trace-buffer` flag) resizes or disables span-tree buffering.
Disabling only stops tree retention — stage spans still time their
bodies and mirror into the histograms, so the /metrics scrape never
goes dark.
"""

import json
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext

from lighthouse_tpu.common.metrics import REGISTRY

# sub-millisecond stages (single field ops) up to multi-second batches
STAGE_BUCKETS = (
    0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5,
    1.0, 5.0, 30.0,
)

_STAGE_FAMILIES = {
    "verify": REGISTRY.histogram_vec(
        "lighthouse_tpu_verify_stage_seconds",
        "per-stage wall time of the signature-verification data plane",
        ("stage",),
        buckets=STAGE_BUCKETS,
    ),
    "import": REGISTRY.histogram_vec(
        "lighthouse_tpu_import_stage_seconds",
        "per-stage wall time of block import",
        ("stage",),
        buckets=STAGE_BUCKETS,
    ),
    "trace": REGISTRY.histogram_vec(
        "lighthouse_tpu_trace_stage_seconds",
        "JAX trace-time spent building each device-graph stage "
        "(one observation per (re)compile, not per call)",
        ("stage",),
        buckets=STAGE_BUCKETS,
    ),
}

_SPAN_FALLBACK = REGISTRY.histogram_vec(
    "lighthouse_tpu_span_seconds",
    "leaf span wall time for spans outside the stage families",
    ("span",),
    buckets=STAGE_BUCKETS,
)

DEFAULT_CAPACITY = 256
MAX_CHILDREN_PER_SPAN = 512

_NO_ANNOTATION = nullcontext()
_annotation = None


def _trace_annotation():
    """`jax.profiler.TraceAnnotation` once the process has imported JAX,
    else None (looked up again on the next span)."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class Span:
    __slots__ = (
        "name", "wall_start", "duration_s", "attrs", "children", "closed",
    )

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.wall_start = time.time()
        self.duration_s = 0.0
        self.attrs = attrs
        self.children: list = []
        self.closed = False

    def to_dict(self) -> dict:
        out = {
            "name": self.name,
            "wall_start": self.wall_start,
            "duration_s": self.duration_s,
        }
        if self.attrs:
            out["attrs"] = self.attrs
        out["children"] = [c.to_dict() for c in self.children]
        return out

    def leaves(self):
        if not self.children:
            return [self]
        return [l for c in self.children for l in c.leaves()]


class Tracer:
    def __init__(self, capacity: int = DEFAULT_CAPACITY, enabled=True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._roots: deque = deque(maxlen=max(1, capacity))
        self._local = threading.local()
        self.completed_roots = 0

    # ------------------------------------------------------- configuration

    @property
    def capacity(self) -> int:
        return self._roots.maxlen

    def configure(self, enabled=None, capacity=None):
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if capacity is not None:
                self._roots = deque(
                    self._roots, maxlen=max(1, int(capacity))
                )

    def reset(self):
        with self._lock:
            self._roots.clear()
            self.completed_roots = 0

    # ------------------------------------------------------------- spans

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current(self):
        """The innermost span open on this thread, or None — what a
        helper thread `adopt`s to stay in this thread's tree."""
        st = getattr(self._local, "stack", None)
        return st[-1] if st else None

    @contextmanager
    def adopt(self, parent):
        """Open this thread's spans as children of `parent`, a span
        another thread holds open (its `current()`); None adopts
        nothing."""
        if parent is None:
            yield
            return
        stack = self._stack()
        stack.append(parent)
        try:
            yield
        finally:
            stack.pop()

    @contextmanager
    def span(self, name: str, **attrs):
        annotation = _trace_annotation()
        with annotation(name) if annotation else _NO_ANNOTATION:
            if not self.enabled:
                # ring disabled: no tree retention, but the stage-family
                # histograms keep recording — /metrics must not go dark
                # because an operator turned off span buffering
                t0 = time.perf_counter()
                try:
                    yield None
                finally:
                    self._mirror_duration(
                        name, time.perf_counter() - t0, leaf=False
                    )
                return
            s = Span(name, attrs)
            stack = self._stack()
            stack.append(s)
            t0 = time.perf_counter()
            try:
                yield s
            finally:
                s.duration_s = time.perf_counter() - t0
                stack.pop()
                self._close(s, stack[-1] if stack else None)
                self._mirror(s)

    def _close(self, s: Span, parent):
        # under the lock: an adopted parent is closed on another thread
        with self._lock:
            s.closed = True
            if parent is None:
                self._roots.append(s)
                self.completed_roots += 1
            elif parent.closed:
                # a late child of a finished tree (abandoned worker):
                # dropped, its duration still mirrored
                return
            elif len(parent.children) < MAX_CHILDREN_PER_SPAN:
                parent.children.append(s)
            else:
                # bound tree size: a 30k-set ref batch would otherwise
                # pin ~6 Span objects per set in one root
                parent.attrs["children_dropped"] = (
                    parent.attrs.get("children_dropped", 0) + 1
                )

    def _mirror(self, s: Span):
        self._mirror_duration(s.name, s.duration_s, leaf=not s.children)

    def _mirror_duration(self, name: str, duration_s: float, leaf: bool):
        """Span -> registry histogram (taxonomy in the module doc).
        Every stage span (name contains '/') is mirrored — including
        parents like verify/marshal or import/block_processing, whose
        children land in their own stage series — while family-less
        spans are mirrored only as leaves (roots such as 'verify'
        already have dedicated batch histograms)."""
        if "/" in name:
            family, stage = name.split("/", 1)
            fam = _STAGE_FAMILIES.get(family)
            if fam is not None:
                fam.labels(stage).observe(duration_s)
                return
        if leaf:
            _SPAN_FALLBACK.labels(name).observe(duration_s)

    # ------------------------------------------------------------ export

    def recent(self, limit: int | None = None) -> list[dict]:
        """Most recent root span trees, oldest first; limit=0 is empty
        (roots[-0:] would be the whole deque)."""
        with self._lock:
            roots = list(self._roots)
        if limit is not None and limit >= 0:
            roots = roots[-limit:] if limit else []
        return [r.to_dict() for r in roots]

    def to_jsonl(self, limit: int | None = None) -> str:
        docs = self.recent(limit)
        if not docs:
            return ""
        return "\n".join(json.dumps(d) for d in docs) + "\n"

    def export_jsonl(self, path, limit: int | None = None) -> int:
        """Write the buffered span trees to `path`; returns tree count."""
        docs = self.recent(limit)
        with open(path, "w") as f:
            for d in docs:
                f.write(json.dumps(d) + "\n")
        return len(docs)


TRACER = Tracer()


def span(name: str, **attrs):
    """`with span("verify/miller_loop", n_sets=8):` on the default tracer."""
    return TRACER.span(name, **attrs)


def configure(enabled=None, capacity=None):
    TRACER.configure(enabled=enabled, capacity=capacity)


def tag(s, **attrs):
    """Add attributes to an open span, the value `span(...)` yielded (a
    no-op on the None a disabled tracer yields)."""
    if s is not None:
        s.attrs.update(attrs)


def find(tree: dict, name: str) -> list[dict]:
    """Every span named `name` in an exported tree (a `recent()`
    entry), depth first."""
    out = [tree] if tree["name"] == name else []
    for child in tree["children"]:
        out += find(child, name)
    return out
