"""Distributed request correlation.

The reference threads an ``x-request-id`` through every hop: client-side
interceptor generates/injects it, each server RPC runs inside a span carrying
it, and replication chains forward the same id (dfs/common/src/lib.rs:5-51,
chunkserver.rs:787,1045). Here the id lives in a contextvar; the RPC layer
(tpudfs.common.rpc) injects it into outgoing gRPC metadata and adopts it from
incoming metadata, so the chain client → master → chunkserver → replica logs a
single id end to end.

Spans extend the same identifier into a trace: ``span(name)`` times one
stage of one request (or, with ``request=None``, of a background task that
serves many), records nest through a second contextvar, and finished records
wait in a bounded in-memory buffer until ``drain()``. Tracing is off unless
``enable()`` was called; off, a site costs one boolean test. The clock is
``time.time_ns()``, the one the benchmark maps onto the profiler's, so
program spans and device events share a timeline. This module never imports
JAX: server processes import it.
"""

from __future__ import annotations

import contextvars
import itertools
import logging
import os
import threading
import time
import uuid
from typing import Any, Callable, NamedTuple

REQUEST_ID_KEY = "x-request-id"

_request_id: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "tpudfs_request_id", default=None
)


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


def current_request_id() -> str:
    """The in-flight request id, minting one if this is the chain's origin."""
    rid = _request_id.get()
    if rid is None:
        rid = new_request_id()
        _request_id.set(rid)
    return rid


def set_request_id(rid: str | None) -> contextvars.Token:
    return _request_id.set(rid)


class _RequestIdFilter(logging.Filter):
    def filter(self, record: logging.LogRecord) -> bool:
        record.request_id = _request_id.get() or "-"
        return True


def setup_logging(level: str | None = None) -> None:
    """Structured logging with the request id on every line (the reference's
    tracing-subscriber EnvFilter equivalent; bin/master.rs:101-107)."""
    level = level or os.environ.get("TPUDFS_LOG", "INFO")
    handler = logging.StreamHandler()
    handler.setFormatter(
        logging.Formatter(
            "%(asctime)s %(levelname)s [%(request_id)s] %(name)s: %(message)s"
        )
    )
    handler.addFilter(_RequestIdFilter())
    root = logging.getLogger()
    root.handlers[:] = [handler]
    root.setLevel(level.upper())


# ---------------------------------------------------------------- spans


class SpanRecord(NamedTuple):
    name: str
    span_id: int
    parent_id: int | None
    request_id: str | None
    start_ns: int
    end_ns: int
    attrs: dict[str, Any]


#: Records the buffer holds before it drops (and counts) the rest: ~20 MB.
BUFFER_CAP = 1 << 16

_INHERIT: Any = object()

_enabled = False
_sink: Callable[[SpanRecord], None] | None = None
_records: list[SpanRecord] = []
_dropped = 0
_dropped_lock = threading.Lock()  # spans may end in worker threads
_span_ids = itertools.count(1)
_current_span: contextvars.ContextVar["_Span | None"] = contextvars.ContextVar(
    "tpudfs_current_span", default=None
)


class _NoopSpan:
    """What ``span()`` returns while tracing is off: one shared object."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        return None

    def end(self, **attrs) -> None:
        return None


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "span_id", "parent_id", "request_id", "start_ns",
                 "attrs", "_owns_request", "_tokens")

    def __init__(self, name: str, parent_id: int | None,
                 request_id: str | None, owns_request: bool, attrs: dict):
        self.name = name
        self.span_id = next(_span_ids)
        self.parent_id = parent_id
        self.request_id = request_id
        self.attrs = attrs
        self._owns_request = owns_request
        self._tokens = None
        self.start_ns = time.time_ns()

    def __enter__(self) -> "_Span":
        # A span that brought its own request makes it the context's for
        # its duration, so RPCs made inside carry the same id across hops.
        self._tokens = (
            _current_span.set(self),
            _request_id.set(self.request_id) if self._owns_request else None,
        )
        return self

    def __exit__(self, *exc) -> None:
        span_token, request_token = self._tokens
        if request_token is not None:
            _request_id.reset(request_token)
        _current_span.reset(span_token)
        self.end()

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def end(self, **attrs) -> None:
        """Closes a span that was not entered as a context manager (one
        that starts in one task and ends in another)."""
        global _dropped
        end_ns = time.time_ns()
        if not _enabled:
            return
        self.attrs.update(attrs)
        record = SpanRecord(self.name, self.span_id, self.parent_id,
                            self.request_id, self.start_ns, end_ns,
                            self.attrs)
        if _sink is not None:
            _sink(record)
        elif len(_records) < BUFFER_CAP:
            _records.append(record)
        else:
            with _dropped_lock:
                _dropped += 1


def span(name: str, *, request: str | None = _INHERIT,
         parent: "_Span | _NoopSpan | None" = None, **attrs):
    """One timed stage, as a context manager (or ``.end()`` by hand).

    By default it belongs to the request of the span open around it in this
    context (its parent), else to the context's request id, else it starts a
    request of its own. ``request=`` detaches it from the context: pass
    ``None`` in a background task that serves many requests (such a task
    inherited the context of whoever started it, which says nothing), and
    ``parent=`` to hang it under a span kept by hand."""
    if not _enabled:
        return _NOOP
    owns_request = True
    if request is _INHERIT:
        if parent is None:
            parent = _current_span.get()
        if isinstance(parent, _Span):
            request, owns_request = parent.request_id, False
        else:
            request = _request_id.get()
            owns_request = request is None
            if owns_request:
                request = new_request_id()
    parent_id = parent.span_id if isinstance(parent, _Span) else None
    return _Span(name, parent_id, request, owns_request, attrs)


def enable(sink: Callable[[SpanRecord], None] | None = None) -> None:
    """Starts recording. With ``sink``, every finished record is handed to
    it (in the context that ended the span) instead of the buffer."""
    global _enabled, _sink, _dropped
    _records.clear()
    _dropped = 0
    _sink = sink
    _enabled = True


def enabled() -> bool:
    """Whether spans are being recorded: a site that asks another process
    for a number only a span would carry asks only then."""
    return _enabled


def disable() -> None:
    global _enabled, _sink
    _enabled = False
    _sink = None


def drain() -> list[SpanRecord]:
    """The buffered records, oldest first; the buffer is left empty."""
    out = _records[:]
    _records.clear()
    return out


def dropped() -> int:
    """Records refused since ``enable()`` because the buffer was full."""
    return _dropped


def chrome_trace(records: list[SpanRecord]) -> dict:
    """Chrome trace-event JSON (Perfetto loads it beside an ``.xplane.pb``):
    complete events in microseconds of the wall clock, laid on as few
    tracks as keep every track properly nested."""
    lanes: list[list[int]] = []  # per track: end_ns of the spans open on it
    events = []
    for r in sorted(records, key=lambda r: (r.start_ns, -r.end_ns)):
        for tid, open_ends in enumerate(lanes):
            while open_ends and open_ends[-1] <= r.start_ns:
                open_ends.pop()
            if not open_ends or r.end_ns <= open_ends[-1]:
                break
        else:
            tid, open_ends = len(lanes), []
            lanes.append(open_ends)
        open_ends.append(r.end_ns)
        events.append({
            "name": r.name, "ph": "X", "pid": os.getpid(), "tid": tid,
            "ts": r.start_ns / 1e3, "dur": (r.end_ns - r.start_ns) / 1e3,
            "args": {**r.attrs, "span": r.span_id, "parent": r.parent_id,
                     "request": r.request_id},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
