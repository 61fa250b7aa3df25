"""The chunkservers' own read clocks (``Stats`` -> ``read_stages``: the
native engine's and the asyncio twin's, summed) on the counters the
harness takes at the window's two ends. ``attach`` wraps the bring-up's
``counters`` from a per-layer reader's ``setup`` (so only in a traced run)
and adds ``cs.read_stages.<key>``, summed over the chunkservers the
bring-up's own ``counters`` ask (the served processes', or the in-process
cluster's). The engine serves each connection on a thread of its own, so
what the client waits for before a response header is the engine's read
(``*_read_ns``) plus the wire and the client's loop.

A program without the group (the parent of the PR that brought it) adds
nothing, and every reader finds nothing.
"""

from __future__ import annotations

import asyncio

from benchmarks.deployments import CS_SERVICE
from tpudfs.common.rpc import RpcError

KEY = "engine_read_stages"
PREFIX = "cs.read_stages."


def _chunkservers(bringup) -> list[str]:
    endpoints = getattr(bringup, "endpoints", None)
    if endpoints:
        return list(endpoints["chunkservers"])
    cluster = getattr(bringup, "cluster", None)
    return [cs.address for cs in getattr(cluster, "chunkservers", [])]


def attach(ctx) -> None:
    """Idempotent per run; every reader of the engine's clocks calls it
    from ``setup(ctx)``."""
    if ctx.setup_readings.get(KEY):
        return
    ctx.setup_readings[KEY] = True
    bringup = ctx.bringup
    counters = bringup.counters

    async def with_read_stages(rpc) -> dict:
        out = await counters(rpc)
        # A server the cell killed before the window answers at neither
        # end (the served bring-up drops it from its list; an in-process
        # cluster keeps it there).
        for stats in await asyncio.gather(*(
                rpc.call(addr, CS_SERVICE, "Stats", {}, timeout=10.0)
                for addr in _chunkservers(bringup)), return_exceptions=True):
            if isinstance(stats, RpcError):
                continue
            if isinstance(stats, BaseException):
                raise stats
            for key, val in (stats.get("read_stages") or {}).items():
                out[PREFIX + key] = out.get(PREFIX + key, 0) + val
        return out

    bringup.counters = with_read_stages


def ms_per(win, numerators: tuple[str, ...], denominator: str
           ) -> float | None:
    """Sum of the ``numerators`` (ns) over ``denominator``, deltas over the
    window, in ms; None where the denominator did not move."""
    count = win.delta(PREFIX + denominator)
    if not count:
        return None
    total = 0
    for key in numerators:
        moved = win.delta(PREFIX + key)
        if moved is None:
            return None
        total += moved
    return total / count / 1e6
