"""End-to-end actuation tracing: propagated spans across the control plane.

The paper's headline claim is an actuation-latency *envelope* (sleep/wake in
~3 s, dual-pods actuation in seconds); the metrics catalog can say how long
one actuation took, but not *which hop* — SPI call, launcher RPC, child
spawn, D2H/H2D stream, rollback — ate the time. This module turns the
existing timing scaffolding into attributable timelines:

  * **Spans** — trace_id / span_id / parent, name, attrs, monotonic
    start/end — recorded into a bounded per-process ring buffer (no
    unbounded growth; old spans fall off the back).
  * **Propagation** — W3C ``traceparent`` headers threaded through the
    instrumented HTTP paths (controller `clients.py`, launcher
    `_engine_request`, the engine's admin handlers) and the
    ``FMA_TRACEPARENT`` env var into forked engine children — so one
    actuation (requester create → controller bind → launcher spawn/wake →
    engine swap commit) is a single coherent trace across processes.
  * **Export** — Chrome trace-event JSON (loads directly in Perfetto /
    chrome://tracing; each process's ring buffer exports with wall-clock
    anchored timestamps, so per-process exports concatenate into one
    timeline) and a human ``tree`` rendering. Served by the engine's
    ``GET /v1/traces`` and the controller observability port's
    ``/debug/traces``.

Overhead discipline: tracing is ON by default (a span is two monotonic
reads, one small object, and a bounded deque append), and ``FMA_TRACING=off``
(or :func:`disable`) turns every entry point into a shared no-op — hot
loops (the swap bucket loop in engine/sleep.py) hoist :func:`enabled` once
and skip span creation entirely, so the disabled path adds no per-chunk
allocations.

Spans are deliberately NOT OpenTelemetry objects: the container must not
grow a dependency, and the subset here (sync spans, explicit parents for
worker threads, context managers over the step-shaped control flow we
have) is what the actuation paths need. The wire format (traceparent) and
the export format (Chrome trace events) are the standard ones, so external
tooling plugs in unchanged.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

logger = logging.getLogger(__name__)

#: env toggles: FMA_TRACING=off|0|false disables at import; FMA_TRACE_BUFFER
#: overrides the ring capacity (spans retained per process).
ENV_VAR = "FMA_TRACING"
BUFFER_ENV_VAR = "FMA_TRACE_BUFFER"
#: the cross-fork propagation channel: the launcher stamps the current
#: traceparent here around the child fork; the engine service adopts it as
#: the parent of its startup span.
TRACEPARENT_ENV = "FMA_TRACEPARENT"

DEFAULT_BUFFER_SPANS = 4096

#: wall-clock anchor: spans carry monotonic times (immune to clock steps);
#: export maps them onto the epoch so per-process exports line up on one
#: Perfetto timeline.
_ANCHOR_WALL = time.time()
_ANCHOR_MONO = time.monotonic()


def _wall(mono_s: float) -> float:
    return _ANCHOR_WALL + (mono_s - _ANCHOR_MONO)


@dataclass
class SpanContext:
    """The propagatable identity of a span: what a child (local, HTTP, or
    forked-process) parents itself on."""

    trace_id: str
    span_id: str


@dataclass
class Span:
    """One finished (or in-flight) span. ``start_s``/``end_s`` are
    monotonic; attrs are small JSON-able scalars (bytes, bucket index,
    model name...)."""

    trace_id: str
    span_id: str
    parent_id: str
    name: str
    start_s: float
    end_s: float = 0.0
    attrs: Dict[str, Any] = field(default_factory=dict)
    pid: int = 0
    thread: str = ""

    @property
    def duration_s(self) -> float:
        return max(0.0, (self.end_s or self.start_s) - self.start_s)


class TraceBuffer:
    """Thread-safe bounded ring of finished spans (per process)."""

    def __init__(self, capacity: int = DEFAULT_BUFFER_SPANS) -> None:
        self._buf: deque = deque(maxlen=max(1, capacity))
        self._mu = threading.Lock()

    def add(self, span: Span) -> None:
        with self._mu:
            self._buf.append(span)

    def snapshot(self, trace_id: Optional[str] = None) -> List[Span]:
        with self._mu:
            spans = list(self._buf)
        if trace_id:
            spans = [s for s in spans if s.trace_id == trace_id]
        return spans

    def drain(self, trace_id: Optional[str] = None) -> List[Span]:
        """Atomic snapshot-and-remove: a span recorded between the two
        would otherwise be dropped unexported. With ``trace_id`` only
        that trace's spans are removed — other traces stay for their own
        later export."""
        with self._mu:
            spans = list(self._buf)
            self._buf.clear()
            if trace_id is None:
                return spans
            self._buf.extend(s for s in spans if s.trace_id != trace_id)
            return [s for s in spans if s.trace_id == trace_id]

    def clear(self) -> None:
        with self._mu:
            self._buf.clear()

    def __len__(self) -> int:
        with self._mu:
            return len(self._buf)


def _env_enabled() -> bool:
    return os.environ.get(ENV_VAR, "").lower() not in ("off", "0", "false")


def _env_capacity() -> int:
    try:
        return int(os.environ.get(BUFFER_ENV_VAR, "") or DEFAULT_BUFFER_SPANS)
    except ValueError:
        return DEFAULT_BUFFER_SPANS


_BUFFER = TraceBuffer(_env_capacity())
_enabled = _env_enabled()
_current: "contextvars.ContextVar[Optional[SpanContext]]" = (
    contextvars.ContextVar("fma_trace_ctx", default=None)
)


def enabled() -> bool:
    """Hot-loop guard: hoist this once per loop; when False, skip
    :func:`begin` entirely (no span objects, no attr dicts)."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset_after_fork() -> None:
    """Forked-child hygiene (the launcher's process model): the fork
    duplicates the parent's ring buffers — drop the copies so the child's
    export is its own spans only, and re-read the env so per-instance
    env_vars (FMA_TRACING / FMA_TRACE_BUFFER) win over inherited state.
    Request sampling resets to 0 (off): the child re-applies its own
    ``--trace-requests`` during engine construction."""
    global _BUFFER, _enabled, _REQ_BUFFER, _req_frac, _capturing
    global _admit_blocked, _emit_deliveries, _emit_tokens, _prefills_overlapped
    global _slow_entries, _slow_s
    _capturing = False
    _admit_blocked = 0
    _prefills_overlapped = 0
    _emit_deliveries = _emit_tokens = 0
    _slow_entries, _slow_s = 0, 0.0
    _slow_by_phase.clear()
    for name in PHASES:
        _PHASES[name] = Phase(name)
    reset_startup()
    _BUFFER = TraceBuffer(_env_capacity())
    _REQ_BUFFER = TraceBuffer(_req_env_capacity())
    _req_frac = 0.0
    _enabled = _env_enabled()
    _current.set(None)


# -- ids / W3C traceparent ----------------------------------------------------


def _new_trace_id() -> str:
    return os.urandom(16).hex()


def _new_span_id() -> str:
    return os.urandom(8).hex()


def format_traceparent(ctx: SpanContext) -> str:
    """W3C trace-context header value: 00-<trace>-<span>-01."""
    return f"00-{ctx.trace_id}-{ctx.span_id}-01"


def parse_traceparent(value: Optional[str]) -> Optional[SpanContext]:
    """Parse a ``traceparent`` header / env value; None on anything
    malformed (a bad header must never break the request that carried
    it)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, _flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return SpanContext(trace_id=trace_id, span_id=span_id)


def current_context() -> Optional[SpanContext]:
    return _current.get()


def current_traceparent() -> Optional[str]:
    ctx = _current.get()
    return format_traceparent(ctx) if ctx is not None else None


def context_from_headers(headers: Any) -> Optional[SpanContext]:
    """Adopt a remote parent from request headers (aiohttp CIMultiDict or
    any mapping with case-insensitive-enough .get)."""
    try:
        return parse_traceparent(
            headers.get("traceparent") or headers.get("Traceparent")
        )
    except Exception:  # noqa: BLE001 — odd header containers
        return None


def env_context() -> Optional[SpanContext]:
    """The cross-fork parent, if the spawning process stamped one."""
    return parse_traceparent(os.environ.get(TRACEPARENT_ENV, ""))


@contextlib.contextmanager
def use_context(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Run a block with ``ctx`` as the current span context (no-op when
    ctx is None) — the executor-thread adoption helper: HTTP handlers
    parse the remote parent on the event loop and re-establish it inside
    the worker running the blocking admin call."""
    if ctx is None:
        yield
        return
    token = _current.set(ctx)
    try:
        yield
    finally:
        _current.reset(token)


# -- spans --------------------------------------------------------------------


class _NoopSpan:
    """The disabled-path singleton: every operation is a no-op, nothing
    allocates per call site."""

    __slots__ = ()
    trace_id = ""
    span_id = ""

    ended = True

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def end(self) -> None:
        return None

    def traceparent(self) -> Optional[str]:
        return None

    def context(self) -> Optional[SpanContext]:
        return None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


class SpanHandle:
    """A live span. Usable as a context manager (``with span("x"): ...``)
    or with explicit ``end()`` for pipelined/overlapping lifetimes (the
    swap bucket loop issues several at once with ``activate=False``)."""

    __slots__ = ("_span", "_token", "_activated")

    def __init__(self, span: Span, token, activated: bool) -> None:
        self._span = span
        self._token = token
        self._activated = activated

    @property
    def trace_id(self) -> str:
        return self._span.trace_id

    @property
    def span_id(self) -> str:
        return self._span.span_id

    @property
    def ended(self) -> bool:
        return bool(self._span.end_s)

    def context(self) -> SpanContext:
        return SpanContext(self._span.trace_id, self._span.span_id)

    def traceparent(self) -> str:
        return format_traceparent(self.context())

    def set(self, **attrs: Any) -> "SpanHandle":
        self._span.attrs.update(attrs)
        return self

    def end(self) -> None:
        if self._span.end_s:
            return  # idempotent
        self._span.end_s = time.monotonic()
        if self._activated and self._token is not None:
            try:
                _current.reset(self._token)
            except ValueError:
                # ended on a different thread/context than it began on
                # (pipelined handles): the ContextVar was never theirs
                pass
            self._token = None
        _BUFFER.add(self._span)

    def __enter__(self) -> "SpanHandle":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None and "error" not in self._span.attrs:
            self._span.attrs["error"] = f"{type(exc).__name__}: {exc}"
        self.end()
        return False


def _new_span(
    name: str,
    parent: Optional[SpanContext],
    start_s: float,
    attrs: Dict[str, Any],
) -> Span:
    """A span of this thread under ``parent``, or under the current context."""
    ctx = parent if parent is not None else _current.get()
    return Span(
        trace_id=ctx.trace_id if ctx else _new_trace_id(),
        span_id=_new_span_id(),
        parent_id=ctx.span_id if ctx else "",
        name=name,
        start_s=start_s,
        attrs=attrs,
        pid=os.getpid(),
        thread=threading.current_thread().name,
    )


def begin(
    name: str,
    parent: Optional[SpanContext] = None,
    activate: bool = True,
    **attrs: Any,
):
    """Start a span. ``parent`` overrides the ambient context (worker
    threads pass the captured parent explicitly — ContextVars do not cross
    thread starts); with ``activate=False`` the span does NOT become the
    current context, which is what overlapping (pipelined) spans in one
    thread need to avoid misparenting each other."""
    if not _enabled:
        return NOOP_SPAN
    span = _new_span(name, parent, time.monotonic(), attrs)
    token = None
    if activate:
        token = _current.set(SpanContext(span.trace_id, span.span_id))
    return SpanHandle(span, token, activate)


def span(
    name: str, parent: Optional[SpanContext] = None, **attrs: Any
):
    """``with tracing.span("engine.swap", model=m): ...`` — begin +
    activate, ended (and attrs stamped with any exception) on exit."""
    return begin(name, parent=parent, activate=True, **attrs)


def record_span(
    name: str,
    start_s: float,
    end_s: float,
    parent: Optional[SpanContext] = None,
    **attrs: Any,
) -> None:
    """A finished span from explicit monotonic times, child of ``parent``
    or of the current context: for work that was timed by someone else
    (jax's compile events) or before any span could be open (the import)."""
    if not _enabled:
        return
    span = _new_span(name, parent, start_s, attrs)
    span.end_s = end_s
    _BUFFER.add(span)


def mono_of_wall(wall_s: float) -> float:
    """A ``time.time()`` reading on the ring's monotonic axis, through the
    module's anchor (jax times its compile events with ``time.time()``)."""
    return _ANCHOR_MONO + (wall_s - _ANCHOR_WALL)


def snapshot(trace_id: Optional[str] = None) -> List[Span]:
    return _BUFFER.snapshot(trace_id=trace_id)


def clear() -> None:
    _BUFFER.clear()


def buffer_len() -> int:
    return len(_BUFFER)


# -- request-scoped tracing ---------------------------------------------------
#
# The ``request.*`` span family (docs/tracing.md): one trace per served
# request, spans recorded retrospectively at lifecycle edges (explicit
# start/end monotonic times — no open handles crossing threads, no
# per-decode-step span flood). Retained spans land in a DEDICATED ring,
# separate from the actuation ring above, so decode traffic can never
# evict swap forensics (and vice versa). Retention is head sampling
# (``--trace-requests <frac>``) plus tail-keep: SLO-violated, aborted,
# and migrated requests always keep their spans.

#: ring capacity override for the request-span ring (spans per process).
REQ_BUFFER_ENV_VAR = "FMA_REQ_TRACE_BUFFER"
DEFAULT_REQ_BUFFER_SPANS = 8192


def _req_env_capacity() -> int:
    try:
        return int(
            os.environ.get(REQ_BUFFER_ENV_VAR, "")
            or DEFAULT_REQ_BUFFER_SPANS
        )
    except ValueError:
        return DEFAULT_REQ_BUFFER_SPANS


_REQ_BUFFER = TraceBuffer(_req_env_capacity())
_req_frac = 0.0


def configure_request_sampling(frac: float) -> None:
    """Set the head-sampling fraction for request traces
    (``--trace-requests``). 0 — the default — keeps the serving hot path
    byte-inert: no RequestTrace objects are created and every hook
    reduces to one ``is None`` check."""
    global _req_frac
    try:
        _req_frac = min(1.0, max(0.0, float(frac)))
    except (TypeError, ValueError):
        _req_frac = 0.0


def request_sampling() -> float:
    return _req_frac


def sample_request() -> bool:
    """One head-sampling draw, decided at request creation. The draw is
    carried on the RequestTrace (``sampled``) so tail-keep can overrule
    a negative draw at completion — not the other way around."""
    return _enabled and _req_frac > 0.0 and random.random() < _req_frac


class RequestTrace:
    """Per-request span collector.

    Spans accumulate privately on the instance (appends are GIL-atomic;
    the engine's step discipline serializes real mutators anyway) and
    nothing touches any ring until :meth:`finish` decides retention:
    head-sampled requests keep their spans, everyone else's are dropped
    at completion unless tail-keep (SLO violation / abort / migration)
    overrules. The lifecycle root's span_id is allocated up front so
    child spans — including spans recorded by ANOTHER process after a
    migration, via :meth:`context` serialized into the parked bundle —
    parent on it before it is finished."""

    __slots__ = ("trace_id", "root_id", "parent_id", "sampled", "spans",
                 "_done")

    def __init__(
        self,
        sampled: bool = False,
        parent: Optional[SpanContext] = None,
    ) -> None:
        self.trace_id = parent.trace_id if parent else _new_trace_id()
        self.parent_id = parent.span_id if parent else ""
        self.root_id = _new_span_id()
        self.sampled = bool(sampled)
        self.spans: List[Span] = []
        self._done = False

    def context(self) -> SpanContext:
        """What a child recorded elsewhere (another thread, or another
        process across the migration wire) parents on: the lifecycle
        root."""
        return SpanContext(self.trace_id, self.root_id)

    def traceparent(self) -> str:
        return format_traceparent(self.context())

    def add(
        self,
        name: str,
        start_s: float,
        end_s: float,
        parent_id: Optional[str] = None,
        **attrs: Any,
    ) -> str:
        """Record one retrospective child span from explicit monotonic
        times; returns its span_id (for grandchildren)."""
        span = Span(
            trace_id=self.trace_id,
            span_id=_new_span_id(),
            parent_id=self.root_id if parent_id is None else parent_id,
            name=name,
            start_s=float(start_s),
            end_s=float(end_s),
            attrs=dict(attrs) if attrs else {},
            pid=os.getpid(),
            thread=threading.current_thread().name,
        )
        self.spans.append(span)
        return span.span_id

    def finish(
        self,
        start_s: float,
        end_s: float,
        keep: bool,
        name: str = "request.lifecycle",
        **attrs: Any,
    ) -> str:
        """Build the ``request.lifecycle`` root over [start_s, end_s] and,
        iff ``keep``, flush root + children to the request ring. Always
        returns the trace_id; idempotent (a double finish flushes
        nothing twice)."""
        if self._done:
            return self.trace_id
        self._done = True
        if keep:
            _REQ_BUFFER.add(
                Span(
                    trace_id=self.trace_id,
                    span_id=self.root_id,
                    parent_id=self.parent_id,
                    name=name,
                    start_s=float(start_s),
                    end_s=float(end_s),
                    attrs=dict(attrs) if attrs else {},
                    pid=os.getpid(),
                    thread=threading.current_thread().name,
                )
            )
            for s in self.spans:
                _REQ_BUFFER.add(s)
        self.spans = []
        return self.trace_id


def request_snapshot(trace_id: Optional[str] = None) -> List[Span]:
    return _REQ_BUFFER.snapshot(trace_id=trace_id)


def request_buffer_len() -> int:
    return len(_REQ_BUFFER)


def clear_requests() -> None:
    _REQ_BUFFER.clear()


# -- start-up stages ----------------------------------------------------------
#
# The ``start.*`` family (docs/tracing.md "Start-up stages and program
# compiles"): where the seconds go between the OS starting an engine process
# and its listener accepting. Like a phase, a stage always adds its seconds
# to a per-process table (two monotonic reads on a preallocated object:
# ``GET /v1/stats`` ``startup``); between :func:`startup_begin` and
# :func:`startup_ready` it is also a span of its name under ``engine.start``.
# Once the listener accepts the table is frozen: a later swap or wake builds
# through the same code and writes nothing here (it has spans of its own).

STAGES = (
    "start.import", "start.entry", "start.backend", "start.resolve",
    "start.weights", "start.pool", "start.programs", "start.sleeper",
    "start.listen",
)


def _process_start() -> Tuple[float, str]:
    """(when the OS started this process, on the monotonic clock; where
    that was read): ``/proc/self/stat`` field 22, ticks since boot, against
    ``CLOCK_BOOTTIME`` (``proc``), else this module's import (``import``)."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # the fields after the command's closing bracket start at 3
            ticks = int(f.read().rpartition(b")")[2].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
            "SC_CLK_TCK"
        )
        if age >= 0:
            return time.monotonic() - age, "proc"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return _ANCHOR_MONO, "import"


class Stage:
    """One row of the start-up table, and the context manager that fills
    it. Stages do not nest; one may be entered more than once (the weights
    are loaded by the server and placed by the engine) and adds up."""

    __slots__ = ("name", "seconds", "_t0", "_span")

    def __init__(self, name: str) -> None:
        self.name = name
        self.seconds = 0.0
        self._t0: Optional[float] = None
        self._span: Any = NOOP_SPAN

    @property
    def timing(self) -> bool:
        """Entered and counting: False once the table is frozen."""
        return self._t0 is not None

    def __enter__(self) -> "Stage":
        if _startup.ready_s is None:
            if _startup.span is not None:
                # under engine.start itself, not under engine.build_runtime
                self._span = begin(self.name, parent=_startup.span.context())
            self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._t0 is not None:
            self.seconds += time.monotonic() - self._t0
            self._t0 = None
            span, self._span = self._span, NOOP_SPAN
            span.__exit__(exc_type, exc, tb)
        return False

    def set(self, **attrs: Any) -> None:
        """Attributes of the stage's span (``bytes``); nothing without one."""
        self._span.set(**attrs)


class _Startup:
    """The table and what closes it; one per process (a forked child's is
    made anew by :func:`reset_startup`)."""

    def __init__(self) -> None:
        self.process_start, self.source = _process_start()
        self.stages = {name: Stage(name) for name in STAGES}
        #: the end of ``start.import``; None in a process that had the
        #: server imported before it was forked
        self.imported_at: Optional[float] = None
        self.backend_made_in = "backend"
        #: ``engine.start`` once :func:`startup_begin` has opened it
        self.span: Any = None
        #: process start to listener accepting; None until then
        self.ready_s: Optional[float] = None


_startup = _Startup()


def reset_startup() -> None:
    global _startup
    _startup = _Startup()


def stage(name: str) -> Stage:
    """``with tracing.stage("start.weights") as st: ...`` — the stage named
    ``name`` (one of :data:`STAGES`)."""
    return _startup.stages[name]


def server_imported() -> None:
    """The last line of ``engine/server.py``: interpreter, jax, aiohttp and
    the package are in. Closes ``start.import``."""
    st = _startup
    if st.imported_at is None and st.ready_s is None:
        st.imported_at = time.monotonic()
        st.stages["start.import"].seconds = st.imported_at - st.process_start


def startup_begin(backend_ready: bool, **attrs: Any) -> None:
    """The entry of ``run_server``. Closes ``start.entry`` (what the entry
    point did between importing the server and calling it: the benchmark's
    child makes the backend's client there, and ``backend_ready`` says so)
    and opens ``engine.start``, back-dated to the process's start, under
    the ``FMA_TRACEPARENT`` parent the spawning launcher stamped."""
    st = _startup
    if st.span is not None or st.ready_s is not None:
        return
    now = time.monotonic()
    imported = st.process_start if st.imported_at is None else st.imported_at
    st.stages["start.entry"].seconds = now - imported
    st.backend_made_in = "entry" if backend_ready else "backend"
    st.span = begin(
        "engine.start",
        parent=env_context(),
        process_start_unix=round(_wall(st.process_start), 3),
        **attrs,
    )
    if st.span is not NOOP_SPAN:
        st.span._span.start_s = st.process_start
        record_span("start.import", st.process_start, imported)
        record_span("start.entry", imported, now)


def startup_ready() -> None:
    """The listener accepts: closes ``start.listen`` and ``engine.start``
    and freezes the table."""
    st = _startup
    if st.ready_s is not None:
        return
    st.stages["start.listen"].__exit__(None, None, None)
    st.ready_s = time.monotonic() - st.process_start
    if st.span is not None:
        st.span.end()


def startup_stats() -> Dict[str, Any]:
    """The ``startup`` block of ``GET /v1/stats``. ``stage_s`` by stage
    (keys without the ``start.`` prefix), ``ready_s`` from the process's
    start to the listener accepting (to now, while it does not yet), and
    ``other_s``, what of that no stage covers. ``backend_s`` is the stage
    in which the backend's client was made, ``backend_made_in`` its name."""
    st = _startup
    stage_s = {
        name.partition(".")[2]: s.seconds for name, s in st.stages.items()
    }
    ready_s = st.ready_s
    if ready_s is None:
        ready_s = time.monotonic() - st.process_start
    return {
        "process_start_unix": _wall(st.process_start),
        "process_start_source": st.source,
        "ready_s": ready_s,
        "backend_made_in": st.backend_made_in,
        "backend_s": stage_s[st.backend_made_in],
        "stage_s": stage_s,
        "other_s": ready_s - sum(stage_s.values()),
    }


# -- scheduler phases ---------------------------------------------------------
#
# The ``sched.*`` family (docs/tracing.md "Scheduler phases"): what the ONE
# scheduler thread of an engine process is doing, part by part. A phase
# always adds its seconds and a count to a per-process table (two monotonic
# reads and a few additions on a preallocated object: ``GET /v1/stats``
# ``scheduler``), and only while a profiler capture runs
# (``POST /v1/profile`` .. ``DELETE``) also leaves a span of its name in the
# actuation ring and a ``jax.profiler.TraceAnnotation`` of its name in the
# capture's host plane, on the device trace's own clock.

PHASES = (
    "sched.intake", "sched.admit", "sched.prefill_dispatch",
    "sched.prefill_fetch", "sched.upload", "sched.chunk_dispatch",
    "sched.chunk_fetch", "sched.emit", "sched.observe", "sched.wait",
)
#: phases in which the loop itself waits, on the device or for work: never
#: part of ``host_only_s``
WAITING_PHASES = ("sched.prefill_fetch", "sched.chunk_fetch", "sched.wait")

_capturing = False
#: steps in which the head of the waiting queue was refused a slot or pages
_admit_blocked = 0
#: calls of a request's streaming hook from ``sched.emit``, and the tokens
#: they carried: tokens / deliveries is about ``--decode-chunk`` at steady
#: decode and 1 if a delivery is ever made a token at a time
_emit_deliveries = 0
_emit_tokens = 0
#: prefills dispatched while an earlier prefill of the same step was still
#: unfetched (``engine.py:_prefill_waiting``); ``phase_n.prefill_dispatch``
#: is its denominator
_prefills_overlapped = 0
#: an entry of a phase that took longer than a steady one ever does (steady
#: entries are 0.06-19 ms, a fetch 86-300 ms): a stall names itself.
#: ``sched.wait`` is never slow
SLOW_ENTRY_S = 0.05
SLOW_FETCH_S = 1.0
_slow_entries = 0
_slow_s = 0.0
_slow_by_phase: Dict[str, float] = {}
#: what the engine says of itself in a slow entry's log line (live
#: sequences, the program in flight); set by the one engine of the process
_slow_describer: Optional[Any] = None


class Phase:
    """One row of the table, and the context manager that fills it. One
    object per name for the life of the process; phases do not nest and
    belong to one thread at a time (the scheduler thread, or an admin
    call that holds the service lock while that thread waits)."""

    __slots__ = ("name", "seconds", "count", "host_only_s", "_overlapped",
                 "_slow_after", "_t0", "_span", "_ann")

    def __init__(self, name: str) -> None:
        self.name = name
        self.seconds = 0.0
        self.count = 0
        #: the seconds of it in which no dispatched program was in flight
        self.host_only_s = 0.0
        self._overlapped = False
        self._slow_after = (
            SLOW_ENTRY_S if name not in WAITING_PHASES
            else SLOW_FETCH_S if name != "sched.wait"
            else float("inf")
        )
        self._t0 = 0.0
        self._span: Any = None
        self._ann: Any = None

    def __enter__(self) -> "Phase":
        if _capturing:
            self._span = begin(self.name, activate=False)
            self._ann = _annotate(self.name)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.monotonic() - self._t0
        self.seconds += dt
        self.count += 1
        if not self._overlapped:
            self.host_only_s += dt
        if dt > self._slow_after:
            _count_slow(self, dt)
        if self._span is not None:
            ann, self._ann = self._ann, None
            ann.__exit__(exc_type, exc, tb)
            span, self._span = self._span, None
            span.end()
        return False

    def set(self, **attrs: Any) -> None:
        """Attributes of the phase's span; outside a capture it costs the
        call and does nothing."""
        if self._span is not None:
            self._span.set(**attrs)


_PHASES: Dict[str, Phase] = {name: Phase(name) for name in PHASES}


def phase(name: str, overlapped: bool = False) -> Phase:
    """``with tracing.phase("sched.admit", program_in_flight): ...`` — the
    phase named ``name`` (one of :data:`PHASES`). ``overlapped`` says a
    dispatched program is still unfetched, a decode chunk or the step's
    previous prefill, so the phase's seconds are not the host holding the
    chip back (``host_only_s``)."""
    p = _PHASES[name]
    p._overlapped = overlapped
    return p


def _count_slow(p: Phase, dt: float) -> None:
    global _slow_entries, _slow_s
    _slow_entries += 1
    _slow_s += dt
    key = p.name.partition(".")[2]
    _slow_by_phase[key] = _slow_by_phase.get(key, 0.0) + dt
    if _startup.ready_s is None:
        return  # still starting: the first compiles are slow and known
    what = f"program unfetched: {p._overlapped}"
    if _slow_describer is not None:
        try:
            what = _slow_describer(p._overlapped)
        except Exception as err:  # noqa: BLE001 — a log line must not stop the loop
            what += f" (no description: {err!r})"
    logger.warning(
        "slow scheduler entry: %s took %.3f s; %s", p.name, dt, what
    )


def describe_slow_entries_with(fn: Optional[Any]) -> None:
    """``fn(overlapped)`` gives the words a slow entry's log line ends in:
    the live sequences and the program in flight, as the engine's owner
    knows them (``overlapped``: the phase ran with a program unfetched).
    Called on the scheduler thread, inside the slow entry's own exit."""
    global _slow_describer
    _slow_describer = fn


def count_admit_blocked() -> None:
    global _admit_blocked
    _admit_blocked += 1


def count_prefill_overlapped() -> None:
    global _prefills_overlapped
    _prefills_overlapped += 1


def count_emit_delivery(tokens: int) -> None:
    global _emit_deliveries, _emit_tokens
    _emit_deliveries += 1
    _emit_tokens += tokens


def emit_deliveries() -> int:
    return _emit_deliveries


def _annotate(name: str, **args: Any) -> Any:
    """An entered ``jax.profiler.TraceAnnotation``. Imported here, not at
    the top: only a process with a capture running (an engine, which has
    jax) ever gets here, and the control plane imports this module too."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name, **args)
    ann.__enter__()
    return ann


def capturing() -> bool:
    return _capturing


def capture_started() -> None:
    """A profiler capture has just started in this process: phases record
    spans and annotations from here on, and one zero-length ``fma.clock``
    annotation carries this module's wall clock (the ``ts`` of every
    ``GET /v1/traces`` event) into the capture, so that whoever reads both
    can lay one on the other exactly."""
    global _capturing
    _annotate(
        "fma.clock", wall_us=int(_wall(time.monotonic()) * 1e6)
    ).__exit__(None, None, None)
    _capturing = True


def capture_stopped() -> None:
    global _capturing
    _capturing = False


def phase_stats() -> Dict[str, Any]:
    """The ``scheduler`` block of ``GET /v1/stats``, cumulative since the
    process started: seconds and entries of each phase (keys without the
    ``sched.`` prefix), ``host_only_s``, the seconds of every phase but
    the waiting ones in which no dispatched program (a decode chunk, the
    step's previous prefill) was in flight, ``admit_blocked``, the steps
    in which the head of the waiting queue was refused a slot or pages,
    ``prefills_overlapped``, the prefills dispatched under an earlier one
    of their step, and ``emit_deliveries`` / ``emit_tokens``, the
    streaming-hook calls of ``sched.emit`` and the tokens they carried;
    ``slow_entries`` / ``slow_s`` / ``slow_by_phase``, the entries that took
    longer than :data:`SLOW_ENTRY_S` (a fetch: :data:`SLOW_FETCH_S`), their
    seconds, and those by phase: a stall shows here under the phase it sat in."""
    rows = [(p.name.partition(".")[2], p) for p in _PHASES.values()]
    return {
        "phase_s": {k: p.seconds for k, p in rows},
        "phase_n": {k: p.count for k, p in rows},
        "host_only_s": sum(
            p.host_only_s for _, p in rows if p.name not in WAITING_PHASES
        ),
        "admit_blocked": _admit_blocked,
        "prefills_overlapped": _prefills_overlapped,
        "emit_deliveries": _emit_deliveries,
        "emit_tokens": _emit_tokens,
        "slow_entries": _slow_entries,
        "slow_s": _slow_s,
        "slow_by_phase": dict(_slow_by_phase),
    }


# -- export -------------------------------------------------------------------


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


def export_chrome(spans: List[Span]) -> Dict[str, Any]:
    """Chrome trace-event JSON (the JSON Array Format with complete "X"
    events) — loads directly in Perfetto and chrome://tracing. Timestamps
    are wall-anchored microseconds, so exports from several processes
    concatenate into one coherent timeline; args carry the span identity
    for cross-process tree reassembly."""
    events = []
    for s in spans:
        events.append(
            {
                "name": s.name,
                "cat": "fma",
                "ph": "X",
                "ts": round(_wall(s.start_s) * 1e6, 3),
                "dur": round(s.duration_s * 1e6, 3),
                "pid": s.pid,
                "tid": s.thread or "main",
                "args": {
                    "trace_id": s.trace_id,
                    "span_id": s.span_id,
                    "parent_id": s.parent_id,
                    **{k: _jsonable(v) for k, v in s.attrs.items()},
                },
            }
        )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def spans_from_chrome(payload: Dict[str, Any]) -> List[Span]:
    """Inverse of :func:`export_chrome` (identity fields + timings): lets
    a caller merge another process's export (e.g. the engine child's
    ``GET /v1/traces``) with its own spans into one tree."""
    out: List[Span] = []
    for e in payload.get("traceEvents", []):
        args = dict(e.get("args") or {})
        trace_id = args.pop("trace_id", "")
        span_id = args.pop("span_id", "")
        parent_id = args.pop("parent_id", "")
        if not trace_id or not span_id:
            continue
        start = float(e.get("ts", 0.0)) / 1e6
        dur = float(e.get("dur", 0.0)) / 1e6
        # wall-anchored ts mapped back onto THIS process's monotonic axis,
        # so merged spans sort/nest consistently with local ones
        start_mono = _ANCHOR_MONO + (start - _ANCHOR_WALL)
        out.append(
            Span(
                trace_id=trace_id,
                span_id=span_id,
                parent_id=parent_id,
                name=str(e.get("name", "")),
                start_s=start_mono,
                end_s=start_mono + dur,
                attrs=args,
                pid=int(e.get("pid", 0) or 0),
                thread=str(e.get("tid", "")),
            )
        )
    return out


def build_tree(
    spans: List[Span],
) -> Tuple[List[Span], Dict[str, List[Span]]]:
    """(roots, children-by-span_id). A span whose parent is absent from
    the set (evicted from the ring, or recorded by a process we did not
    merge) is treated as a root rather than dropped."""
    by_id = {s.span_id: s for s in spans}
    children: Dict[str, List[Span]] = {}
    roots: List[Span] = []
    for s in spans:
        if s.parent_id and s.parent_id in by_id:
            children.setdefault(s.parent_id, []).append(s)
        else:
            roots.append(s)
    for v in children.values():
        v.sort(key=lambda s: s.start_s)
    roots.sort(key=lambda s: s.start_s)
    return roots, children


def render_tree(spans: List[Span]) -> str:
    """Human rendering: one indented tree per trace, durations in ms,
    attrs inline — the "why was THIS actuation slow" view."""
    lines: List[str] = []
    by_trace: Dict[str, List[Span]] = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    for trace_id in sorted(by_trace):
        lines.append(f"trace {trace_id}")
        roots, children = build_tree(by_trace[trace_id])

        def walk(node: Span, depth: int) -> None:
            attrs = " ".join(
                f"{k}={_jsonable(v)}" for k, v in node.attrs.items()
            )
            lines.append(
                "  " * (depth + 1)
                + f"{node.name}  {node.duration_s * 1e3:.2f}ms"
                + (f"  [{attrs}]" if attrs else "")
            )
            for c in children.get(node.span_id, []):
                walk(c, depth + 1)

        for r in roots:
            walk(r, 0)
    return "\n".join(lines) + "\n"


def export_http(
    fmt: str = "chrome",
    trace_id: Optional[str] = None,
    clear: bool = False,
) -> Tuple[int, str, str]:
    """(status, body, content_type) — the shared body of the three export
    endpoints (engine ``/v1/traces``, launcher ``/v2/vllm/traces``,
    controller ``/debug/traces``), so format validation and the
    snapshot/clear semantics cannot drift between them. ``fmt`` is
    ``chrome`` (Perfetto-loadable JSON, the default) or ``tree`` (text);
    ``clear`` drains atomically with the snapshot, and composed with
    ``trace_id`` removes ONLY the exported trace — other traces' spans
    are never dropped unexported. Exports the union of the actuation
    ring and the request-span ring (a ``trace_id`` filter naturally
    scopes to whichever ring holds that trace)."""
    import json

    if fmt not in ("chrome", "tree"):
        return 400, "format must be chrome or tree\n", "text/plain"
    spans = (
        _BUFFER.drain(trace_id) if clear else _BUFFER.snapshot(trace_id)
    )
    spans += (
        _REQ_BUFFER.drain(trace_id)
        if clear
        else _REQ_BUFFER.snapshot(trace_id)
    )
    if fmt == "tree":
        return 200, render_tree(spans), "text/plain"
    return 200, json.dumps(export_chrome(spans)), "application/json"


def wrap_with_headers(headers: Any, fn):
    """Zero-arg callable running ``fn`` with the headers' ``traceparent``
    (if any) as the current context — the run_in_executor adoption
    pattern shared by the engine and launcher REST handlers (ContextVars
    don't follow executor dispatch on their own)."""
    ctx = context_from_headers(headers)

    def call():
        with use_context(ctx):
            return fn()

    return call


def run_traced(loop: Any, headers: Any, fn):
    """``loop.run_in_executor`` of a blocking call with the headers'
    remote ``traceparent`` adopted inside the worker thread — the one
    REST-handler dispatch pattern every traced server uses."""
    return loop.run_in_executor(None, wrap_with_headers(headers, fn))
