"""Spans recorded from the benchmark's own files, around the calls into
each layer (choosing-metrics §4). Kept in memory; the per-layer readers and
the idle-gap attribution read them after the window.

The clock is ``time.time_ns()``: the trace reduction maps it onto the
profiler's clock through one marker annotation (``trace_reduce.CLOCK_MARK``).
"""

from __future__ import annotations

import contextlib
import contextvars
import time

from tpudfs.common.rpc import RpcClient

#: The operation (one put, one file read, one sweep call) the running task
#: belongs to; spans carry it so a put's master calls can be summed.
CURRENT_OP: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "bench_current_op", default=None)


class Spans:
    def __init__(self) -> None:
        #: (name, op id or None, start ns, end ns)
        self.rows: list[tuple[str, int | None, int, int]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        start = time.time_ns()
        try:
            yield
        finally:
            self.rows.append((name, CURRENT_OP.get(), start, time.time_ns()))

    def between(self, start_ns: int, end_ns: int, name: str | None = None):
        return [r for r in self.rows if r[3] > start_ns and r[2] < end_ns
                and (name is None or r[0] == name)]


class SpanRpcClient(RpcClient):
    """The benchmark's own RpcClient, given to its ``Client`` as
    ``rpc_client=``: every call to the master service is a ``master_rpc``
    span of the operation that made it."""

    def __init__(self, spans: Spans):
        super().__init__()
        self.spans = spans

    async def call(self, addr, service, method, request, timeout=10.0):
        if service != "MasterService":
            return await super().call(addr, service, method, request,
                                      timeout=timeout)
        with self.spans.span("master_rpc"):
            return await super().call(addr, service, method, request,
                                      timeout=timeout)
