"""Query-path tracing: lightweight spans + per-query ``QueryTrace`` records.

The span API is deliberately tiny (DESIGN.md §10):

    with obs.span("plan"):
        ...
    with obs.span("kernel", path="scan") as sp:
        out = launch(...)
        sp.block_on(out)          # device-sync-aware close

Spans are **host-side** objects — they never enter jit. A span wrapping a
kernel launch would otherwise stop its clock at *dispatch* (jax is async):
``Span.block_on`` registers device values and the close blocks on them
(``jax.block_until_ready``), so a kernel span measures device time, not how
fast Python returned. This is also why spans must not be opened *inside*
jit-traced Python: that code runs once at trace time and never again, so the
span would time tracing, not execution ("no trace-time capture"). Wrap the
jitted call, never the jitted body.

Two sinks, one API. A span records into the calling thread's ``Tracer``
(the span tree ``query_batch(trace=True)`` builds) and, while the
process-wide switch ``to_profiler(True)`` is on, into the JAX profiler: every
``span(name, **attrs)`` on any thread then also opens a
``jax.profiler.TraceAnnotation(f"mdrq.{name}", **attrs)``, so the span lands
on the profiler's host planes on the device trace's clock, with its
attributes as event stats (DESIGN.md §10). Attributes are ints or short
strings; ``None`` values are left out of the annotation.

Cost when both sinks are off is one thread-local lookup and one
module-global load: ``span(...)`` returns the shared ``NULL_SPAN``
singleton — no object is allocated on the hot path, which is what keeps
``trace=False`` execution at zero overhead.

Launch/host-sync attribution: every ``Tracer`` span snapshots the metrics
registry's ``mdrq_launches_total`` family at open and close (the same
counters ``kernels.ops`` bumps and tests assert budgets on), so a span knows
exactly how many kernel launches and host syncs happened under it —
wall-clock measurements on CPU cannot see either. Profiler-only spans skip
the snapshot: nothing reads it there.

``QueryTrace``/``BatchTrace`` are the records ``MDRQEngine.query_batch(...,
trace=True)`` produces: per query, the planner's chosen path, realized
bucket, estimated selectivity and cost, the realized result count (and the
observed selectivity where the spec makes it derivable), plus the bucket's
measured seconds / launches / host syncs. The drift audit (``obs.audit``)
and Flood-style layout learning both consume exactly these records.
"""
from __future__ import annotations

import dataclasses
import threading as _threading
import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation

from repro.obs import metrics as _metrics

# The one counter family the kernel layer bumps (see kernels/ops.py); the
# device->host sync pseudo-op lives in the same family under this op label.
LAUNCH_FAMILY = "mdrq_launches_total"
HOST_SYNC_OP = "host_sync"
# What the profiler sink names a span: ``mdrq.<span name>``.
PROFILER_PREFIX = "mdrq."


def _launch_snapshot() -> tuple[float, float]:
    """(kernel launches, host syncs) since process start, from the registry."""
    launches = 0.0
    syncs = 0.0
    for m in _metrics.registry().series(LAUNCH_FAMILY):
        if m.labels.get("op") == HOST_SYNC_OP:
            syncs += m.value
        else:
            launches += m.value
    return launches, syncs


class Span:
    """One timed region. Context manager; closes device-sync-aware."""

    __slots__ = ("name", "attrs", "seconds", "children", "launches",
                 "host_syncs", "_tracer", "_t0", "_c0", "_pending", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.seconds = 0.0
        self.children: list[Span] = []
        self.launches = 0
        self.host_syncs = 0
        self._tracer = tracer
        self._t0 = 0.0
        self._c0 = (0.0, 0.0)
        self._pending: list = []
        self._ann = None

    def set(self, **attrs) -> "Span":
        """Attach attributes after open (result counts, bucket sizes, ...).
        The profiler sink only sees the attributes given at open."""
        self.attrs.update(attrs)
        return self

    def block_on(self, x) -> None:
        """Register a device value the span close must block on, so the span
        measures device completion rather than async dispatch."""
        self._pending.append(x)

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        if _ANNOTATION is not None:
            self._ann = _annotation(self.name, self.attrs)
            self._ann.__enter__()
        self._c0 = _launch_snapshot()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._pending:
            import jax
            jax.block_until_ready(self._pending)
            self._pending = []
        self.seconds = time.perf_counter() - self._t0
        c1 = _launch_snapshot()
        self.launches = int(c1[0] - self._c0[0])
        self.host_syncs = int(c1[1] - self._c0[1])
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        self._tracer._pop(self)

    def find(self, name: str) -> list["Span"]:
        """All descendant spans (and self) with the given name, pre-order."""
        out = [self] if self.name == name else []
        for c in self.children:
            out.extend(c.find(name))
        return out

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.seconds * 1e6:.0f}us, "
                f"launches={self.launches}, host_syncs={self.host_syncs}, "
                f"attrs={self.attrs})")


class _NullSpan:
    """The disabled-tracing singleton: every method is a no-op. ``span()``
    returns this exact object when no tracer is active, so the hot path
    allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def set(self, **attrs) -> "_NullSpan":
        return self

    def block_on(self, x) -> None:
        return None


NULL_SPAN = _NullSpan()


class _ProfilerSpan(TraceAnnotation):
    """A span that only the profiler records (no ``Tracer`` on the thread).
    It does not block on ``block_on`` values: turning the sink on must not
    add syncs."""

    def set(self, **attrs) -> "_ProfilerSpan":
        return self

    def block_on(self, x) -> None:
        return None


# The profiler sink: None while ``to_profiler`` is off, else the annotation
# class ``span()`` opens. One process-wide switch, read by every thread.
_ANNOTATION = None


def _annotation(name: str, attrs: dict):
    return _ANNOTATION(PROFILER_PREFIX + name,
                       **{k: v for k, v in attrs.items() if v is not None})


def to_profiler(on: bool) -> bool:
    """Turn the profiler sink on or off for every thread; returns the
    previous setting. While on, each ``span()`` also writes a
    ``TraceAnnotation`` named ``mdrq.<name>``, which a running
    ``jax.profiler`` trace records (and which costs about a microsecond
    when none runs)."""
    global _ANNOTATION
    prev = _ANNOTATION is not None
    _ANNOTATION = _ProfilerSpan if on else None
    return prev


# The active tracer, *per thread*. The pipelined server (DESIGN.md §13) runs
# a dedicated finalizer thread; a process-global tracer would let that
# thread's spans interleave into the admission thread's span stack and
# corrupt the tree. Thread-local means: a Tracer installed on one thread
# sees exactly that thread's spans; other threads' span() calls return
# NULL_SPAN. (An async server would swap this for a contextvar.)
class _Local(_threading.local):
    # A class-level default: reading ``tracer`` on a thread that never set
    # it returns None without raising, where a ``getattr(..., None)`` miss
    # costs an AttributeError, about a microsecond, on every span.
    tracer: Optional["Tracer"] = None


_TLS = _Local()


def enabled() -> bool:
    """Whether a ``Tracer`` is installed on the calling thread."""
    return _TLS.tracer is not None


def active() -> bool:
    """Whether a span opened on the calling thread records anywhere (a
    ``Tracer`` here, or the profiler sink): the guard for attributes that
    cost something to compute."""
    return _ANNOTATION is not None or _TLS.tracer is not None


def span(name: str, **attrs):
    """Open a span under the calling thread's active tracer and, while
    ``to_profiler`` is on, in the profiler; the no-op singleton when neither
    records."""
    t = _TLS.tracer
    if t is None:
        if _ANNOTATION is None:
            return NULL_SPAN
        return _annotation(name, attrs)
    return Span(t, name, attrs)


def current() -> Optional["Tracer"]:
    return _TLS.tracer


class Tracer:
    """Collects a span tree. ``with Tracer() as t:`` installs it as the
    active tracer (nesting restores the previous one on exit)."""

    def __init__(self):
        self.spans: list[Span] = []   # root spans, in open order
        self._stack: list[Span] = []
        self._prev: Optional[Tracer] = None

    def __enter__(self) -> "Tracer":
        self._prev = _TLS.tracer
        _TLS.tracer = self
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        _TLS.tracer = self._prev
        self._prev = None

    def _push(self, s: Span) -> None:
        (self._stack[-1].children if self._stack else self.spans).append(s)
        self._stack.append(s)

    def _pop(self, s: Span) -> None:
        if self._stack and self._stack[-1] is s:
            self._stack.pop()

    def find(self, name: str) -> list[Span]:
        out = []
        for s in self.spans:
            out.extend(s.find(name))
        return out


# =============================================================================
# Query-trace records (what the engine emits under trace=True)
# =============================================================================

@dataclasses.dataclass(slots=True)
class QueryTrace:
    """One query's observed execution, planner estimates included.

    ``seconds``/``launches``/``host_syncs`` are the query's *amortized share*
    of its fused launch bucket (bucket totals divided by ``bucket_size``) —
    the same amortization the cost model prices, so estimated and measured
    costs are directly comparable. ``obs_selectivity`` is the realized
    match fraction where the result shape makes it derivable (ids / count /
    mask), else None.
    """

    index: int                     # position in the submitted batch
    method: str                    # access path executed
    bucket_size: int               # realized fused-launch bucket
    est_selectivity: float         # planner estimate (histograms)
    est_cost: float                # planner cost estimate, seconds (NaN when
    #                                the method was explicit, not planned)
    spec_kind: str                 # result shape served
    mq: int                        # constrained dims (audit's bytes model)
    result_size: int               # realized result magnitude (spec-typed)
    obs_selectivity: Optional[float]
    seconds: float                 # measured wall share of the bucket
    launches: float                # kernel launches / bucket_size
    host_syncs: float              # host syncs / bucket_size


@dataclasses.dataclass
class BatchTrace:
    """One ``query_batch(trace=True)`` execution: per-query records plus the
    batch-level plan/execute breakdown and the raw span tree."""

    n: int                         # dataset objects (obs selectivity divisor)
    n_queries: int
    spec_kind: str
    plan_seconds: float
    seconds: float
    queries: list[QueryTrace]
    spans: list[Span]

    def by_method(self) -> dict[str, list[QueryTrace]]:
        out: dict[str, list[QueryTrace]] = {}
        for t in self.queries:
            out.setdefault(t.method, []).append(t)
        return out
