"""RequestContext: one identity for a request across every serving layer.

Before this module, a request lost its identity at every layer boundary —
the RPC server saw a frame, the dispatcher saw a bare :class:`CSRMatrix`,
the plan builder saw positional batch slots, the cache saw a fingerprint
string — so a deadline could not follow the request, per-stage latency
could not be attributed, and shedding had nothing to key on.

:class:`RequestContext` is minted once at the edge (the RPC wire protocol
carries optional ``request_id``/``deadline_ms``/``priority`` fields;
``SolverEngine.plan/select/solve`` and ``PlanDispatcher.submit`` mint one
when the caller did not) and threaded through

    PlanRPCServer → PlanDispatcher → PlanBuilder → plan cache → solve

accumulating **span timings** (stage name → seconds) along the way, so a
``plan`` response can report exactly where its milliseconds went and the
dispatcher can *shed* a request whose deadline has already passed instead
of spending a build worker on an answer nobody is waiting for.

:func:`span` is the one timing primitive of the serving and solve paths:
it opens a ``jax.profiler.TraceAnnotation`` of the stage's name around the
work where it runs (so a device trace shows the host stage on its own
clock) and adds the wall time to the context's ``spans`` on exit. Code that
may run without a context calls it with ``ctx=None`` and gets the
annotation alone. With no profiler running an annotation costs ~1 µs.

The typed serving errors live here too — they are the vocabulary every
layer (and the RPC client, which re-raises them by name) shares:

* :class:`DeadlineExceeded` — the request's deadline passed before a plan
  could be produced; the dispatcher sheds it at dequeue time.
* :class:`QueueFull` — admission control rejected the request because the
  dispatch queue is at ``max_queue`` (backpressure, not failure).
* :class:`DispatcherClosed` — the dispatcher shut down; pending futures
  are failed with this instead of hanging forever.

All deadlines are **absolute** ``time.perf_counter()`` instants (the
monotonic clock used everywhere in the serving path), converted from the
relative ``deadline_ms`` the client sent at mint time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import threading
import time
import uuid
from typing import Dict, Optional

__all__ = ["RequestContext", "span", "ServingError", "DeadlineExceeded",
           "QueueFull", "DispatcherClosed", "SERVING_ERRORS"]


class ServingError(RuntimeError):
    """Base of the typed serving-path errors (wire name = class name)."""


class DeadlineExceeded(ServingError):
    """The request's deadline passed before its plan was produced."""


class QueueFull(ServingError):
    """Admission control: the dispatch queue is at capacity."""


class DispatcherClosed(ServingError):
    """The dispatcher shut down; the request cannot be served."""


#: wire name → class, used by the RPC client to re-raise the exact typed
#: error the server-side dispatcher raised (``error_type`` in error frames)
SERVING_ERRORS: Dict[str, type] = {
    cls.__name__: cls
    for cls in (ServingError, DeadlineExceeded, QueueFull, DispatcherClosed)
}

# request ids are "req-<8 hex>-<seq>": unique within a process by the
# counter, unique across processes by the random prefix — and cheap (no
# per-request uuid4 syscall on the hot path)
_ID_PREFIX = uuid.uuid4().hex[:8]
_ID_SEQ = itertools.count()

# nesting depth of open spans on each thread: the outermost span of a
# thread carries the request id, the ones inside it take it from nesting
_DEPTH = threading.local()


@functools.cache
def _annotation():
    # imported on first use: the RPC client imports this module for the
    # error types and never opens a span
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


@contextlib.contextmanager
def span(ctx: Optional["RequestContext"], name: str, **stats):
    """``with span(ctx, "factor.routes"): ...`` — one stage of a request.

    Opens ``jax.profiler.TraceAnnotation(name, **stats)`` (keyword
    arguments become the trace event's stats, the event keeps the bare
    name) and, when ``ctx`` is given, adds the wall time to
    ``ctx.spans[name]`` on exit, also when the body raises. The outermost
    span on a thread gets ``request_id=ctx.request_id`` as a stat. Spans
    nest: a parent's self time is its duration less its children's.
    Yields the annotation, whose ``set_metadata(**stats)`` adds stats
    known only inside the body.
    """
    depth = getattr(_DEPTH, "n", 0)
    if ctx is not None and depth == 0:
        stats.setdefault("request_id", ctx.request_id)
    _DEPTH.n = depth + 1
    t0 = time.perf_counter()
    try:
        with _annotation()(name, **stats) as ann:
            yield ann
    finally:
        if ctx is not None:
            ctx.add_span(name, time.perf_counter() - t0)
        _DEPTH.n = depth


@dataclasses.dataclass
class RequestContext:
    """Identity + budget + telemetry for one serving request.

    ``spans`` maps a stage name to accumulated seconds; re-entering a
    stage adds to it. Plan path: ``queue``, ``select`` (with
    ``select.pack`` in the trace only), ``build`` > ``reorder`` /
    ``symbolic``, ``fingerprint``, ``cache``, ``total``. Solve path
    (:data:`repro.core.plan.SOLVE_STAGES`, parent > children):
    ``permute``; ``factor`` > ``factor.schedule`` / ``factor.routes`` /
    ``factor.compile_ahead`` / ``factor.assemble`` / ``factor.device`` >
    ``factor.drain``; ``solve`` > ``solve.sweep`` > ``solve.sweep.setup``,
    ``solve`` > ``solve.refine``; ``solve.check``. ``counts`` maps a
    counter name (``compile_ahead.programs``) to a count the request
    caused. ``deadline_s`` is an absolute :func:`time.perf_counter`
    instant or ``None`` (no deadline). ``priority`` — higher is served
    first; ties are FIFO.
    """

    request_id: str
    fingerprint: Optional[str] = None
    priority: int = 0
    t_arrival: float = dataclasses.field(default_factory=time.perf_counter)
    deadline_s: Optional[float] = None
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    meta: Dict[str, object] = dataclasses.field(default_factory=dict)

    # spans may be written from the batcher thread while (e.g.) an RPC
    # handler thread snapshots them for a response frame
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    # -- construction --------------------------------------------------------
    @classmethod
    def mint(cls, *, request_id: Optional[str] = None,
             deadline_ms: Optional[float] = None, priority: int = 0,
             fingerprint: Optional[str] = None) -> "RequestContext":
        """New context; ``deadline_ms`` is relative-to-now at mint time."""
        now = time.perf_counter()
        return cls(
            request_id=(request_id if request_id
                        else f"req-{_ID_PREFIX}-{next(_ID_SEQ)}"),
            fingerprint=fingerprint, priority=int(priority), t_arrival=now,
            deadline_s=(None if deadline_ms is None
                        else now + float(deadline_ms) / 1e3))

    # -- deadline ------------------------------------------------------------
    def remaining(self) -> Optional[float]:
        """Seconds until the deadline (negative if past); None = no deadline."""
        if self.deadline_s is None:
            return None
        return self.deadline_s - time.perf_counter()

    def expired(self) -> bool:
        return (self.deadline_s is not None
                and time.perf_counter() >= self.deadline_s)

    def elapsed(self) -> float:
        """Seconds since arrival (mint time)."""
        return time.perf_counter() - self.t_arrival

    # -- span telemetry ------------------------------------------------------
    def add_span(self, stage: str, seconds: float) -> None:
        with self._lock:
            self.spans[stage] = self.spans.get(stage, 0.0) + float(seconds)

    def span(self, stage: str, **stats):
        """``with ctx.span("symbolic"): ...`` — :func:`span` on this
        context: a trace annotation, and the wall time accumulated even
        when the body raises (the time was still spent on this request)."""
        return span(self, stage, **stats)

    def add_count(self, name: str, n: int) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + int(n)

    def spans_since(self, before: Dict[str, float]) -> Dict[str, float]:
        """Seconds each stage gained since the ``dict(ctx.spans)`` snapshot
        ``before``; stages that gained nothing are left out."""
        with self._lock:
            return {k: v - before.get(k, 0.0) for k, v in self.spans.items()
                    if k not in before or v != before[k]}

    def spans_ms(self) -> Dict[str, float]:
        """Wire-friendly copy: stage → milliseconds."""
        with self._lock:
            return {k: v * 1e3 for k, v in self.spans.items()}

    def summary(self) -> Dict[str, object]:
        """Plain-data description (RPC responses, JSONL metric events)."""
        return dict(request_id=self.request_id, fingerprint=self.fingerprint,
                    priority=self.priority,
                    deadline_remaining_ms=(None if self.deadline_s is None
                                           else self.remaining() * 1e3),
                    spans_ms=self.spans_ms())

    # contexts travel inside futures between threads but never across
    # processes; strip the lock if something pickles one anyway
    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_lock", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
