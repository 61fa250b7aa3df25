"""Raw-TCP bulk data plane ("blockport") for block payloads.

The reference pushes block bytes through tonic gRPC (compiled Rust, where
HTTP/2 framing is cheap — chunkserver.rs:722-1087). This build's control
plane is Python, and gRPC there measures ~2.3 ms of the single bench core
per 1 MiB unary message — more CPU than the durable write it carries. Bulk
block payloads therefore ride a dedicated length-framed TCP protocol on a
separate listener, while EVERY control RPC — and any peer that doesn't
advertise a blockport — stays on the msgpack-gRPC substrate. This is the
DCN half of the SURVEY §2.6 transport split; the colocated half is ICI
collectives (tpu/ici_replication.py).

The two ends receive differently. The server (``BlockPortServer``, the
asyncio fallback of the native engine) reads frames off asyncio streams.
The client (``BlockConn``, pooled by ``BlockConnPool``) is an
``asyncio.BufferedProtocol``: headers and small frames are parsed out of a
small buffer the connection owns, and a response payload is received by
the kernel straight into its destination — the caller's scatter segments
(a ``ReadBlocks`` round lands in the combiner's round buffer with one
``recv_into`` a wake-up) or, when the caller gave none, one buffer of the
payload's size that becomes ``resp["data"]``.

Frame, both directions::

    u32 header_len | msgpack(header) | u64 payload_len | payload bytes

Request header: ``{"m": <method>, **fields}``; the payload carries what the
gRPC twin would put in ``req["data"]``. Response header ``{"ok": True,
**fields}`` (payload = ``resp["data"]`` for reads) or ``{"ok": False,
"code": <grpc StatusCode name>, "message": str}`` — errors re-raise as
RpcError so caller retry logic is transport-agnostic.

Discovery: callers resolve a peer's blockport once via the ``DataPort``
gRPC method (negative-cached when absent, so pre-blockport peers keep
working over gRPC). Aliased addresses (``Client.host_aliases`` — the
Docker/FaultProxy indirections) DELIBERATELY stay on gRPC: a fault proxy
interposed on the gRPC address must not be bypassed by a side-channel
data connection.

TLS parity: the blockport wraps the same certificate material as the gRPC
listeners (ServerTls/ClientTls), including mTLS client-cert requirements.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import os
import ssl
import struct
import time

import grpc
import msgpack

from tpudfs.common import telemetry
from tpudfs.common.resilience import (
    TENANT_FRAME_KEY,
    OVERLOADED_PREFIX,
    BreakerBoard,
    BudgetExhausted,
    Deadline,
    attempt_timeout,
    overloaded_message,
    raw_tenant,
    remaining_budget,
    set_deadline,
    set_tenant,
)
from tpudfs.common.rpc import ClientTls, RpcClient, RpcError, ServerTls

import socket as _socket


def _read_cap(name: str) -> int:
    try:
        with open(f"/proc/sys/net/core/{name}") as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return 0


#: Explicit socket buffers DISABLE kernel autotuning and clamp to
#: net.core.{w,r}mem_max — a net loss on default-sysctl hosts (~208 KiB
#: caps, autotuning would have grown past them). Only pin big buffers
#: where the caps actually allow them (>= 1 MiB: one sendmsg lands a
#: whole block instead of trickling in lockstep with a same-core
#: reader); otherwise leave autotuning alone.
_SOCK_BUF = min(4 << 20, _read_cap("wmem_max"), _read_cap("rmem_max"))
if _SOCK_BUF < (1 << 20):
    _SOCK_BUF = 0


def _tune_socket(sock) -> None:
    if not _SOCK_BUF:
        return
    try:
        sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF, _SOCK_BUF)
        sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, _SOCK_BUF)
    except OSError:
        pass

logger = logging.getLogger(__name__)

_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")
_MAX_HEADER = 1 << 20
_MAX_PAYLOAD = 100 * 1024 * 1024  # parity with MAX_MESSAGE_BYTES
#: Server side only: asyncio stream buffer limit of the serve loop. The
#: default 64 KiB makes readexactly() on a multi-MiB request frame (a
#: whole-block write) wake the protocol once per 64 KiB; 4 MiB matches the
#: pinned socket-buffer target in _tune_socket. The client side has no
#: stream buffer (BlockConn).
_STREAM_LIMIT = 4 * 1024 * 1024
#: Client side: the connection's own receive buffer, as large as one recv
#: of an asyncio stream (256 KiB), so that a small frame (a write-stream
#: ack, an EC shard, an error) arrives whole in ONE wake-up and is handed
#: out from here. A recv that runs past a large frame's header brings at
#: most this much of the payload with it, which is copied to its place
#: once; the rest of the payload is received in place. Whatever is larger
#: (a header too) is received into a buffer of its own.
_RX_BUF = 256 * 1024


def enabled() -> bool:
    return os.environ.get("TPUDFS_BLOCKPORT", "1") != "0"


def _pack_frame(header: dict, payload) -> list[bytes]:
    """``payload=None`` means "no data field"; ``b""`` is a real, empty
    data field (an empty block is valid DFS content) — the ``_d`` header
    flag keeps the two distinguishable across the wire.

    ``payload`` may also be a list/tuple of buffers (the handler
    scatter-framing contract, ``data_parts``): the parts ride straight
    into ``writelines`` without ever being concatenated — the kernel
    gathers them off the list."""
    if payload is not None:
        header["_d"] = 1
    h = msgpack.packb(header, use_bin_type=True)
    if isinstance(payload, (list, tuple)):
        plen = sum(len(p) for p in payload)
        out = [_U32.pack(len(h)), h, _U64.pack(plen)]
        out.extend(p for p in payload if len(p))
        return out
    out = [_U32.pack(len(h)), h, _U64.pack(len(payload) if payload else 0)]
    if payload:
        out.append(payload)
    return out


async def _read_frame(r) -> tuple[dict, bytes]:
    """One whole frame off ``r``: an ``asyncio.StreamReader`` (server
    side) or a ``BlockConn`` (the client side of a write stream) — any
    object with ``readexactly``."""
    header, plen = await _read_header(r)
    return header, await r.readexactly(plen) if plen else b""


async def _read_header(r) -> tuple[dict, int]:
    hlen = _U32.unpack(await r.readexactly(4))[0]
    if hlen > _MAX_HEADER:
        raise ConnectionError(f"blockport header too large: {hlen}")
    header = msgpack.unpackb(await r.readexactly(hlen), raw=False,
                             strict_map_key=False)
    plen = _U64.unpack(await r.readexactly(8))[0]
    if plen > _MAX_PAYLOAD:
        raise ConnectionError(f"blockport payload too large: {plen}")
    return header, plen


#: The reads whose request carries ``READ_TIMING_KEY`` while tracing is on:
#: the server (native/dataplane.cc, or the chunkserver's handlers) answers
#: with its own time to the header, ns, under ``READ_NS_KEY`` in it.
_TIMED_READS = frozenset({"ReadBlock", "ReadBlocks"})
READ_TIMING_KEY = "_rt"
READ_NS_KEY = "_rns"

#: Serve-loop backpressure watermark: an unconditional ``await
#: w.drain()`` per response frame costs an event-loop round-trip per
#: frame even when the kernel buffer is empty; only pay it once the
#: transport's write buffer actually backs up past this.
_DRAIN_WATERMARK = 1 << 18


async def _drain_backpressure(w) -> None:
    transport = w.transport
    if transport is None or \
            transport.get_write_buffer_size() > _DRAIN_WATERMARK:
        await w.drain()


class BlockPortServer:
    """Framed-TCP front over the same async handlers the gRPC service
    registers — the payload rides outside msgpack, everything else is
    identical (handlers see ``req["data"]``, reads return ``resp["data"]``)."""

    def __init__(self, handlers: dict, tls: ServerTls | None = None,
                 stream_handlers: dict | None = None):
        self.handlers = handlers
        #: method -> ``async fn(req, reader, writer) -> bool`` taking over
        #: the connection for a multi-frame exchange (the write-stream
        #: protocol, tpudfs/common/writestream.py). The handler writes its
        #: own response frames; returning False means the connection can
        #: no longer be framed (torn/aborted stream) and must close.
        self.stream_handlers = stream_handlers or {}
        self._tls = tls
        self._server: asyncio.AbstractServer | None = None
        self.port: int = 0
        #: method -> ns spent writing its response frames (and waiting for
        #: the transport to drain them): the send stage of the chunkserver's
        #: ``read_stages``, as the native engine's send_frame clock.
        self.send_ns: collections.Counter[str] = collections.Counter()
        #: live connections; closed at stop() — wait_closed() would
        #: otherwise block on peers' POOLED (idle but open) connections.
        self._conns: set[asyncio.StreamWriter] = set()

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        ctx = None
        if self._tls is not None:
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self._tls.cert_path, self._tls.key_path)
            if self._tls.ca_path:
                ctx.load_verify_locations(self._tls.ca_path)
                ctx.verify_mode = ssl.CERT_REQUIRED
        self._server = await asyncio.start_server(
            self._handle, host, port, ssl=ctx, limit=_STREAM_LIMIT
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self.port

    async def stop(self) -> None:
        # Swap-then-await so a concurrent stop() can't double-close.
        server, self._server = self._server, None
        if server is not None:
            server.close()
            for w in list(self._conns):
                w.close()
            await server.wait_closed()

    async def _handle(self, r: asyncio.StreamReader,
                      w: asyncio.StreamWriter) -> None:
        self._conns.add(w)
        sock = w.get_extra_info("socket")
        if sock is not None:
            _tune_socket(sock)
        try:
            while True:
                try:
                    header, payload = await _read_frame(r)
                except (asyncio.IncompleteReadError, ConnectionError,
                        ConnectionResetError):
                    return
                method = header.pop("m", "")
                fn = self.handlers.get(method)
                sfn = self.stream_handlers.get(method)
                if fn is None and sfn is None:
                    w.writelines(_pack_frame(
                        {"ok": False, "code": "UNIMPLEMENTED",
                         "message": f"no blockport method {method!r}"}, None))
                    await _drain_backpressure(w)
                    continue
                req = header
                # Deadline parity with the gRPC plane: adopt the caller's
                # remaining budget (`_db`, relative seconds) and reject
                # expired work before executing it.
                budget = req.pop("_db", None)
                if not isinstance(budget, (int, float)):
                    budget = None
                if budget is not None and budget <= 0:
                    w.writelines(_pack_frame(
                        {"ok": False, "code": "DEADLINE_EXCEEDED",
                         "message": "deadline budget exhausted before "
                                    f"blockport {method} executed"}, None))
                    await _drain_backpressure(w)
                    continue
                if req.pop("_d", 0):
                    req["data"] = payload
                dl_token = set_deadline(
                    Deadline.after(budget) if budget is not None else None
                )
                # Tenant parity with the gRPC plane's x-tenant metadata.
                tn = req.pop(TENANT_FRAME_KEY, None)
                tn_token = set_tenant(tn if isinstance(tn, str) and tn else None)
                if sfn is not None:
                    # Stream handler: owns the connection for a
                    # multi-frame exchange and writes its own responses.
                    try:
                        keep = await sfn(req, r, w)
                    except asyncio.CancelledError:
                        raise
                    except (asyncio.IncompleteReadError, ConnectionError,
                            ConnectionResetError):
                        return
                    except Exception:
                        logger.exception(
                            "blockport stream handler %s failed", method)
                        w.writelines(_pack_frame(
                            {"ok": False, "code": "INTERNAL",
                             "message": "internal error"}, None))
                        await _drain_backpressure(w)
                        # Stream position unknown: the frame boundary may
                        # be lost, so the connection cannot be reused.
                        return
                    finally:
                        try:
                            dl_token.var.reset(dl_token)
                        except ValueError:
                            pass
                        try:
                            tn_token.var.reset(tn_token)
                        except ValueError:
                            pass
                    if not keep:
                        return
                    continue
                try:
                    resp = await fn(req)
                except RpcError as e:
                    w.writelines(_pack_frame(
                        {"ok": False, "code": e.code.name,
                         "message": e.message}, None))
                    await _drain_backpressure(w)
                    continue
                except asyncio.CancelledError:
                    raise
                except Exception:
                    logger.exception("blockport handler %s failed", method)
                    w.writelines(_pack_frame(
                        {"ok": False, "code": "INTERNAL",
                         "message": "internal error"}, None))
                    await _drain_backpressure(w)
                    continue
                finally:
                    try:
                        dl_token.var.reset(dl_token)
                    except ValueError:
                        pass
                    try:
                        tn_token.var.reset(tn_token)
                    except ValueError:
                        pass
                out = dict(resp)
                data = out.pop("data", None) if "data" in out else None
                if "data_parts" in out:
                    data = out.pop("data_parts")
                out["ok"] = True
                t_send = time.perf_counter_ns()
                w.writelines(_pack_frame(out, data))
                await _drain_backpressure(w)
                self.send_ns[method] += time.perf_counter_ns() - t_send
        except (ConnectionResetError, BrokenPipeError):
            pass
        finally:
            self._conns.discard(w)
            w.close()


def _settle(fut: asyncio.Future | None, exc: BaseException | None) -> None:
    if fut is not None and not fut.done():
        if exc is None:
            fut.set_result(None)
        else:
            fut.set_exception(exc)


class BlockConn(asyncio.BufferedProtocol):
    """Client side of one blockport connection: the protocol AND the small
    stream surface its users need (``readexactly``, ``writelines``,
    ``drain``, ``close``, ``is_closing``, ``transport``), so request/
    response calls, write streams and a hop's relay leg share one class.

    One reader at a time. While nothing larger is awaited, received bytes
    land in the connection's own ``_RX_BUF`` bytes and ``readexactly``
    hands them out (headers, back-to-back ack frames). While a payload is
    awaited, ``get_buffer`` IS the destination: the kernel copies into the
    caller's segment and nothing else touches the bytes.

    The destination may be a pooled buffer of the caller's, so a receive
    that ends early (cancellation, timeout, transport error) drops the
    destination and aborts the transport before it returns: nothing that
    arrives later is written anywhere but here."""

    def __init__(self) -> None:
        self.transport: asyncio.Transport | None = None
        self._loop = asyncio.get_running_loop()
        self._view = memoryview(bytearray(_RX_BUF))
        #: unread bytes of the own buffer are ``_view[_r:_w]``.
        self._r = 0
        self._w = 0
        #: run being received in place (its part still to fill), and the
        #: runs after it, last first.
        self._dst: memoryview | None = None
        self._rest: list[memoryview] = []
        self._waiter: asyncio.Future | None = None
        self._exc: BaseException | None = None
        self._rx_paused = False
        self._tx_paused = False
        self._drain_waiters: list[asyncio.Future] = []

    # ------------------------------------------------ protocol callbacks

    def connection_made(self, transport) -> None:
        self.transport = transport

    def get_buffer(self, sizehint: int) -> memoryview:
        if self._dst is not None:
            return self._dst
        if self._w == _RX_BUF:
            self._compact()  # _r > 0: a buffer full of unread bytes paused
        return self._view[self._w:]

    def buffer_updated(self, nbytes: int) -> None:
        dst = self._dst
        if dst is not None:
            if nbytes < len(dst):
                self._dst = dst[nbytes:]
            elif self._rest:
                self._dst = self._rest.pop()
            else:
                self._dst = None
                self._wake()
            return
        self._w += nbytes
        if self._w == _RX_BUF and not self._r:
            self._rx_paused = True
            self.transport.pause_reading()
        self._wake()

    def eof_received(self) -> None:
        self._fail(asyncio.IncompleteReadError(b"", None))

    def connection_lost(self, exc) -> None:
        self._fail(exc or ConnectionResetError("blockport connection lost"))

    def pause_writing(self) -> None:
        self._tx_paused = True

    def resume_writing(self) -> None:
        self._tx_paused = False
        self._wake_drains()

    # ------------------------------------------------------------ receive

    async def readexactly(self, n: int):
        """``n`` bytes in stream order: out of the connection's buffer
        when they fit there, else received into a buffer of their own."""
        if n > _RX_BUF:
            data = bytearray(n)
            await self._recv_into([memoryview(data)])
            return data
        if n > _RX_BUF - self._r:
            self._compact()
        while self._w - self._r < n:
            await self._wait()
        data = bytes(self._view[self._r:self._r + n])
        self._r += n
        self._consumed()
        return data

    async def read_payload(self, header: dict, plen: int, into=None):
        """The ``plen`` payload bytes that follow ``header``. ``into``:
        optional scatter callback ``(header, plen) -> segments`` (writable
        buffers whose lengths sum to ``plen``; adjacent destinations
        should come as ONE segment, each segment is one run of
        ``recv_into``). Returns ``(None, direct)`` when the payload went
        into the segments, ``direct`` being the bytes the kernel put
        there itself (all but what one recv brought along with the
        header); ``(data, 0)`` when there were none — no callback, a None
        result, an error frame, an empty payload."""
        segments = None
        if plen and into is not None and header.get("ok"):
            segments = into(header, plen)
        if segments is None:
            return (await self.readexactly(plen) if plen else b""), 0
        views = [memoryview(seg).cast("B") for seg in segments]
        covered = sum(len(v) for v in views)
        if covered != plen:
            # The connection is mid-payload and cannot be resynced.
            raise ConnectionError(
                f"scatter segments cover {covered} of {plen} payload bytes")
        return None, plen - await self._recv_into(views)

    async def _recv_into(self, views: list[memoryview]) -> int:
        """Fill ``views`` in order. Bytes of theirs that a recv already
        brought into the own buffer are copied over first (their count is
        the result); the rest arrives in place."""
        rest = [v for v in reversed(views) if len(v)]
        copied = 0
        while rest and self._r < self._w:
            dst = rest[-1]
            k = min(len(dst), self._w - self._r)
            dst[:k] = self._view[self._r:self._r + k]
            self._r += k
            copied += k
            if k == len(dst):
                rest.pop()
            else:
                rest[-1] = dst[k:]
        self._consumed()
        if rest:
            self._dst = rest.pop()
            self._rest = rest
            try:
                await self._wait()
            except BaseException:
                self._abort()
                raise
        return copied

    async def _wait(self) -> None:
        if self._exc is not None:
            raise self._exc
        if self._waiter is not None:
            raise RuntimeError("a BlockConn has one reader at a time")
        self._waiter = self._loop.create_future()
        try:
            await self._waiter
        finally:
            self._waiter = None

    def _wake(self, exc: BaseException | None = None) -> None:
        _settle(self._waiter, exc)

    def _wake_drains(self, exc: BaseException | None = None) -> None:
        waiters, self._drain_waiters = self._drain_waiters, []
        for w in waiters:
            _settle(w, exc)

    def _compact(self) -> None:
        n = self._w - self._r
        self._view[:n] = self._view[self._r:self._w]
        self._r, self._w = 0, n

    def _consumed(self) -> None:
        if self._r == self._w:
            self._r = self._w = 0
        if self._rx_paused:
            self._rx_paused = False
            self.transport.resume_reading()

    def _fail(self, exc: BaseException) -> None:
        self._dst = None
        self._rest = []
        if self._exc is None:
            self._exc = exc
        self._wake(exc)
        self._wake_drains(exc)

    def _abort(self) -> None:
        """Mid-payload exit: forget the destination and remove the reader
        synchronously (``abort`` does, for TCP and for TLS — a TLS
        ``close`` would still flush incoming data through the protocol)."""
        self._fail(ConnectionError("blockport receive abandoned mid-payload"))
        if self.transport is not None:
            self.transport.abort()

    # --------------------------------------------------------------- send

    def writelines(self, parts) -> None:
        self.transport.writelines(parts)

    async def drain(self) -> None:
        if self.transport.is_closing():
            await asyncio.sleep(0)  # let connection_lost say why
        if self._exc is not None:
            raise self._exc
        if self._tx_paused:
            waiter = self._loop.create_future()
            self._drain_waiters.append(waiter)
            await waiter

    def is_closing(self) -> bool:
        return self._exc is not None or self.transport.is_closing()

    def idle(self) -> bool:
        """At a frame boundary with nothing unread: fit for the pool."""
        return (self._dst is None and self._waiter is None
                and self._r == self._w and not self.is_closing())

    def close(self) -> None:
        self._dst = None
        self._rest = []
        self.transport.close()


class BlockConnPool:
    """Per-address pooled blockport client with gRPC-probed discovery and
    transparent gRPC fallback.

    ``call(rpc, addr, method, req)`` sends over the peer's blockport when
    one is advertised (``DataPort`` probe, cached; transport failures open
    a per-address circuit breaker) and over ``rpc`` otherwise — so every
    caller keeps exactly one code path and legacy/faulted peers degrade
    gracefully."""

    #: idle connections kept per peer; extras close on release.
    MAX_IDLE_PER_PEER = 8

    def __init__(self, tls: ClientTls | None = None):
        self._tls = tls
        self._free: dict[str, list[BlockConn]] = {}
        #: addr -> (port | None). None = peer has no blockport (final,
        #: from an UNIMPLEMENTED probe). Transport-level probe/call
        #: failures instead open the per-address breaker below.
        self._ports: dict[str, int | None] = {}
        #: addr -> whether the advertised blockport is the native engine
        #: (chain-forwards only to blockports; see chain_info()).
        self._native: dict[str, bool] = {}
        #: addr -> whether the peer speaks the WriteStream frame protocol
        #: (tpudfs/common/writestream.py). FAIL CLOSED on version skew: a
        #: peer that predates the `stream` probe field gets False and
        #: keeps receiving whole-block writes.
        self._stream: dict[str, bool] = {}
        #: Per-address breakers replacing the old flat retry-at negative
        #: cache: one failure opens for 5 s, consecutive opens double the
        #: window up to 30 s, and a single half-open probe per window
        #: re-tests the peer (the old cache re-probed blind on expiry).
        self.breakers = BreakerBoard(failure_threshold=1, reset_timeout=5.0,
                                     max_reset=30.0)
        #: in-flight DataPort probes, shared so a concurrent first burst
        #: fires ONE probe per peer instead of one per caller.
        self._probes: dict[str, asyncio.Task] = {}
        self._ssl_ctx: ssl.SSLContext | None = None
        if tls is not None:
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            ctx.load_verify_locations(tls.ca_path)
            # Hostname verification stays ON (the PROTOCOL_TLS_CLIENT
            # default): _call_blockport passes the peer's host as
            # server_hostname, so the bulk data plane validates the target
            # name against the cert SANs exactly like the gRPC plane's
            # secure_channel — without it, any single CA-issued cert could
            # impersonate every chunkserver on the data side channel.
            if tls.cert_path and tls.key_path:
                ctx.load_cert_chain(tls.cert_path, tls.key_path)
            self._ssl_ctx = ctx

    async def _data_port(self, rpc: RpcClient, addr: str,
                         service: str) -> int | None:
        if addr in self._ports:
            return self._ports[addr]
        if not self.breakers.allow(addr):
            return None  # breaker open: stay on gRPC until a probe heals it
        probe = self._probes.get(addr)
        if probe is None:
            probe = asyncio.create_task(self._probe(rpc, addr, service))
            self._probes[addr] = probe
            probe.add_done_callback(
                lambda _t, a=addr: self._probes.pop(a, None)
            )
        try:
            return await asyncio.shield(probe)
        except asyncio.CancelledError:
            raise
        except Exception:
            logger.debug("blocknet probe of %s failed", addr, exc_info=True)
            return None

    async def _probe(self, rpc: RpcClient, addr: str,
                     service: str) -> int | None:
        try:
            resp = await rpc.call(addr, service, "DataPort", {}, timeout=5.0)
            port = int(resp.get("port") or 0) or None
        except RpcError as e:
            if e.code == grpc.StatusCode.UNIMPLEMENTED:
                self._ports[addr] = None  # pre-blockport peer: final
                self.breakers.record_success(addr)
            else:
                self.breakers.record_failure(addr)
            return None
        self.breakers.record_success(addr)
        self._ports[addr] = port
        # FAIL CLOSED on version skew: a peer that advertises a blockport
        # but predates the `native` field might still be the native engine
        # (which forwards only to blockports) — treat it as such so mixed
        # chains route around it instead of silently under-replicating.
        self._native[addr] = bool(resp.get("native", port is not None))
        self._stream[addr] = bool(resp.get("stream", False))
        return port

    async def data_ports(self, rpc: RpcClient, addrs: list[str],
                         service: str) -> list[int]:
        """Resolve every address's blockport concurrently; 0 = none.
        Chain writers attach the result as ``next_data_ports`` so a native
        data-plane engine (native/dataplane.cc) can forward hop-to-hop
        without its own discovery."""
        if not enabled() or not addrs:
            return [0] * len(addrs)
        ports = await asyncio.gather(
            *(self._data_port(rpc, a, service) for a in addrs)
        )
        return [int(p or 0) for p in ports]

    async def chain_info(self, rpc: RpcClient, addrs: list[str],
                         service: str) -> tuple[list[int], bool]:
        """(ports, first_hop_safe): whether sending the CHAIN through the
        first hop's blockport preserves full replication. The native
        engine forwards only to blockports, so it needs the whole
        remaining chain resolvable; the asyncio blockport (and the gRPC
        handler) re-resolve per hop and handle mixed chains."""
        ports = await self.data_ports(rpc, addrs, service)
        if not ports or not ports[0]:
            return ports, False
        if all(ports):
            return ports, True
        return ports, not self._native.get(addrs[0], False)

    def stream_chain_ok(self, addrs: list[str]) -> bool:
        """True when EVERY chain member's probed blockport speaks the
        WriteStream frame protocol (probe data cached by a prior
        chain_info/data_ports call). The native engine relays streams
        only to stream-capable blockports, so a mixed chain takes the
        whole-block path instead — never silent under-replication."""
        return bool(addrs) and all(self._stream.get(a, False) for a in addrs)

    async def write_stream(self, rpc: RpcClient, addr: str, service: str,
                           req: dict, data,
                           timeout: float = 60.0) -> dict | None:
        """Send one block as a pipelined write stream to ``addr``'s
        blockport. Returns the final response dict, or None when the peer
        can't take a stream (no blockport / no stream support) — the
        caller then falls back to the whole-block ``call`` path. Failure
        mapping mirrors ``call``: transport failures surface UNAVAILABLE
        and open the per-address breaker."""
        if not enabled():
            return None
        try:
            timeout = attempt_timeout(timeout)
        except BudgetExhausted:
            raise RpcError(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                f"deadline budget exhausted before WriteStream to {addr}",
            ) from None
        port = await self._data_port(rpc, addr, service)
        if port is None or not self._stream.get(addr, False):
            return None
        from tpudfs.common import writestream  # noqa: PLC0415 (cycle)

        host = addr.rsplit(":", 1)[0]
        hostport = f"{host}:{port}"
        try:
            conn = await self._checkout(hostport)
        except (OSError, ConnectionError) as e:
            # Dead/refusing peer at dial time (e.g. the chain head was
            # just SIGKILLed): same UNAVAILABLE mapping as a mid-stream
            # transport failure, so caller failover loops keep working.
            self._ports.pop(addr, None)
            self.breakers.record_failure(addr)
            raise RpcError(grpc.StatusCode.UNAVAILABLE,
                           f"write stream dial {hostport}: {e!r}") from None
        header = dict(req)
        rem = remaining_budget()
        if rem is not None:
            header["_db"] = rem
        tenant = raw_tenant()
        if tenant is not None:
            header[TENANT_FRAME_KEY] = tenant
        try:
            resp = await asyncio.wait_for(
                writestream.send_block_stream(conn, conn, header, data),
                timeout=timeout,
            )
        except RpcError as e:
            if getattr(e, "stream_clean", False):
                # Pre-stream rejection (no data frames on the wire): the
                # connection is still framed — reuse it.
                self._release(hostport, conn)
                if e.code == grpc.StatusCode.UNIMPLEMENTED:
                    self._mark_stream_unsupported(addr)
                    return None
            else:
                conn.close()
            raise
        except asyncio.TimeoutError:
            conn.close()
            raise RpcError(grpc.StatusCode.DEADLINE_EXCEEDED,
                           f"write stream to {hostport} timed out") from None
        except asyncio.CancelledError:
            conn.close()
            raise
        except (OSError, ConnectionError, asyncio.IncompleteReadError,
                ValueError, msgpack.exceptions.UnpackException) as e:
            conn.close()
            self._ports.pop(addr, None)
            self.breakers.record_failure(addr)
            raise RpcError(grpc.StatusCode.UNAVAILABLE,
                           f"write stream {hostport}: {e!r}") from None
        self.breakers.record_success(addr)
        self._release(hostport, conn)
        return resp

    async def stream_checkout(self, rpc: RpcClient, addr: str,
                              service: str) -> tuple[str, BlockConn] | None:
        """Checkout a (possibly pooled) blockport connection to a
        stream-capable peer for a hop's downstream relay leg. Returns
        ``(hostport, conn)`` or None when the peer can't take
        a stream. Pair with :meth:`stream_release` (clean finish) or
        :meth:`stream_discard` (mid-stream failure)."""
        if not enabled():
            return None
        port = await self._data_port(rpc, addr, service)
        if port is None or not self._stream.get(addr, False):
            return None
        host = addr.rsplit(":", 1)[0]
        hostport = f"{host}:{port}"
        return hostport, await self._checkout(hostport)

    def stream_release(self, hostport: str, conn) -> None:
        self._release(hostport, conn)

    def stream_discard(self, addr: str, conn) -> None:
        conn.close()
        self._ports.pop(addr, None)
        self.breakers.record_failure(addr)

    async def call(self, rpc: RpcClient, addr: str, service: str,
                   method: str, req: dict, timeout: float = 30.0,
                   payload_into=None) -> dict:
        """Blockport when advertised, gRPC otherwise. ``req["data"]`` (if
        any) travels as the raw payload frame. ``payload_into``: scatter
        callback for the RESPONSE payload (see BlockConn.read_payload) — honored on
        the blockport transport only; the gRPC path (and a None callback
        result) returns the payload as ``resp["data"]`` and the caller
        copies it itself."""
        try:
            timeout = attempt_timeout(timeout)
        except BudgetExhausted:
            raise RpcError(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                f"deadline budget exhausted before {method} to {addr}",
            ) from None
        port = None
        if enabled():
            port = await self._data_port(rpc, addr, service)
        if port is None:
            return await rpc.call(addr, service, method, req, timeout=timeout)
        host = addr.rsplit(":", 1)[0]
        try:
            resp = await asyncio.wait_for(
                self._call_blockport(f"{host}:{port}", method, req,
                                     payload_into),
                timeout=timeout,
            )
        except RpcError:
            raise
        except asyncio.TimeoutError:
            raise RpcError(grpc.StatusCode.DEADLINE_EXCEEDED,
                           f"blockport call to {host}:{port} timed out") \
                from None
        except (OSError, ConnectionError, asyncio.IncompleteReadError,
                ValueError, msgpack.exceptions.UnpackException) as e:
            # Connection-level OR framing failure (a corrupt/desynced frame
            # surfaces as an unpack error): drop the cached port so a later
            # probe re-resolves it (the peer may have restarted on a new
            # port), open the breaker, and surface the same UNAVAILABLE the
            # gRPC path would so caller failover loops keep working.
            self._ports.pop(addr, None)
            self.breakers.record_failure(addr)
            raise RpcError(grpc.StatusCode.UNAVAILABLE,
                           f"blockport {host}:{port}: {e!r}") from None
        self.breakers.record_success(addr)
        return resp

    def _mark_stream_unsupported(self, addr: str) -> None:
        """Negative stream-capability memo off a fresh UNIMPLEMENTED reply:
        the peer just told us it doesn't serve streams (restart race onto
        an older build), so this write is authoritative no matter what a
        concurrent capability probe recorded meanwhile — later probes may
        legitimately flip it back."""
        self._stream[addr] = False

    async def _checkout(self, hostport: str) -> BlockConn:
        """Pop a pooled connection to ``hostport`` or open a fresh one."""
        free = self._free.setdefault(hostport, [])
        while free:
            conn = free.pop()
            if conn.is_closing():
                continue
            return conn
        host, port = hostport.rsplit(":", 1)
        _, conn = await asyncio.get_running_loop().create_connection(
            BlockConn, host, int(port), ssl=self._ssl_ctx,
            server_hostname=host if self._ssl_ctx is not None else None,
        )
        sock = conn.transport.get_extra_info("socket")
        if sock is not None:
            _tune_socket(sock)
        return conn

    def _release(self, hostport: str, conn: BlockConn) -> None:
        """Return a still-framed connection to the idle pool (extras
        close). Only call when the frame boundary is intact — a torn or
        aborted stream must close the connection instead."""
        free = self._free.setdefault(hostport, [])
        if len(free) < self.MAX_IDLE_PER_PEER and conn.idle():
            free.append(conn)
        else:
            conn.close()

    async def _call_blockport(self, hostport: str, method: str,
                              req: dict, payload_into=None) -> dict:
        conn = await self._checkout(hostport)
        try:
            header = {k: v for k, v in req.items() if k != "data"}
            header["m"] = method
            rem = remaining_budget()
            if rem is not None:
                header["_db"] = rem
            tenant = raw_tenant()
            if tenant is not None:
                header[TENANT_FRAME_KEY] = tenant
            # While tracing is on, a read asks the peer for its own read
            # time; otherwise the frames are the same bytes as ever.
            timed = method in _TIMED_READS and telemetry.enabled()
            if timed:
                header[READ_TIMING_KEY] = 1
            conn.writelines(_pack_frame(header, req.get("data")))
            await conn.drain()
            # The wait for the header holds the peer's work up to it (a
            # ReadBlock's whole read; a native ReadBlocks frame's opens and
            # sizes, its preads follow the header), the request's and the
            # header's time on the wire, and this loop's lag in noticing
            # the header. ``engine_read_ms`` is the first of those, by the
            # peer's own clock; the rest of the span is wire and loop. The
            # payload is the wire's, this loop's and a frame's preads.
            with telemetry.span("blockport.wait_header", method=method,
                                addr=hostport) as waited:
                resp, plen = await _read_header(conn)
                waited.set(bytes=plen)
                if timed and READ_NS_KEY in resp:
                    waited.set(engine_read_ms=resp.pop(READ_NS_KEY) / 1e6)
            with telemetry.span("blockport.recv_payload", method=method,
                                addr=hostport, bytes=plen) as received:
                payload, direct = await conn.read_payload(resp, plen,
                                                          payload_into)
                received.set(direct=direct)
        except BaseException:
            conn.close()
            raise
        self._release(hostport, conn)
        has_data = resp.pop("_d", 0)
        if not resp.pop("ok", False):
            code = getattr(grpc.StatusCode, str(resp.get("code")),
                           grpc.StatusCode.INTERNAL)
            message = str(resp.get("message") or "")
            hinted = resp.get("retry_after")
            if (isinstance(hinted, (int, float))
                    and code is grpc.StatusCode.RESOURCE_EXHAUSTED
                    and not message.startswith(OVERLOADED_PREFIX)):
                # Native sheds carry a structured retry_after next to the
                # human-readable message; fold it into the Overloaded envelope
                # so the retry budget sleeps the server-suggested interval.
                message = overloaded_message(float(hinted), message)
            raise RpcError(code, message)
        if has_data:
            resp["data"] = payload
        return resp

    async def close(self) -> None:
        for conns in self._free.values():
            for conn in conns:
                conn.close()
        self._free.clear()
