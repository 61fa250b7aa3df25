"""Sub-block frame protocol for streaming chain writes ("write streams").

The whole-block write path frames one block as ONE payload: the receiving
hop buffers the entire block off the socket, CRCs it, writes it, forwards
it — four serialized stages, each a full block long. This module is the
shared framing layer that instead cuts a block into ~256 KiB frames and
pipelines them, so network receive, CRC fold, disk append, and chain
fanout overlap at frame granularity (the pipelined-execution idea from
PAPERS.md applied to chain replication). Three parties speak it:

- the client (``Client._write_replicated_block`` via
  ``BlockConnPool.write_stream``),
- the asyncio blockport fallback (``chunkserver/service.py``
  ``rpc_write_stream``),
- the native engine (``native/dataplane.cc`` ``handle_write_stream``) —
  byte-identical wire format, so mixed native/asyncio chains interop.

Wire protocol (rides the blockport framing of blocknet.py, ``u32
header_len | msgpack(header) | u64 payload_len | payload``):

1. begin  (client -> hop):   ``{"m": "WriteStream", "block_id", "size",
   "frame_size", "expected_crc32c", "master_term", "master_shard",
   "next_servers", "next_data_ports"}`` — no payload. ``_db`` (relative
   deadline budget, seconds) and the tenant header ride exactly like any
   other blockport request and are honored MID-STREAM (expiry aborts the
   whole chain; see docs/resilience.md).
2. ready  (hop -> client):   ``{"ok": True, "ready": 1}``. An error frame
   here (UNIMPLEMENTED from a pre-streaming peer, FAILED_PRECONDITION
   from fencing, DEADLINE_EXCEEDED, RESOURCE_EXHAUSTED) leaves the
   connection in sync — the client falls back to the whole-block path.
3. frames (client -> hop):   ``ceil(size / frame_size)`` data frames,
   header ``{"q": seq, "c": crc32c(frame)}``, pipelined without waiting
   for acks (socket backpressure is the flow control).
4. watermark acks (hop -> client): ``{"ok": True, "w": n}`` — frames
   ``[0, n)`` received, CRC-verified, and queued to disk at this hop.
   The tail coalesces per-frame progress into one ack every
   ``ACK_EVERY`` frames; watermarks are MAX-merged by receivers, so
   reordered or dropped acks never move progress backwards.
5. final  (hop -> client):   ``{"ok": True, "final": 1, "success",
   "error_message", "replicas_written"}`` — sent only after the hop's
   group commit made the block durable AND the downstream final ack
   arrived, i.e. the durable watermark covers the whole block.

Abort semantics: an error frame sent after any data frame was consumed
means the stream cannot resync — both sides close the connection. A hop
that aborts (CRC mismatch, mid-stream deadline expiry, torn upstream)
closes its downstream stream too, so the abort propagates down the chain
and every hop discards its partial staged file (never published).
"""

from __future__ import annotations

import asyncio

import grpc

from tpudfs.common.blocknet import (
    _drain_backpressure,
    _pack_frame,
    _read_frame,
)
from tpudfs.common.checksum import crc32c
from tpudfs.common.resilience import OVERLOADED_PREFIX, overloaded_message
from tpudfs.common.rpc import RpcError

#: Frame payload size. Big enough that per-frame header/syscall overhead
#: amortizes (~0.1% at 256 KiB), small enough that four pipeline stages
#: and a 4-deep buffer ring stay ~1.25 MiB per in-flight block.
FRAME_SIZE = 256 * 1024

#: Blocks below this ride the whole-block path: a 2-frame stream pays the
#: begin/ready round trip without overlapping anything.
MIN_STREAM_BYTES = 2 * FRAME_SIZE

#: Streamed-block ceiling (the whole-block path's 100 MiB frame cap does
#: not apply per-stream; each FRAME is bounded by frame_size instead).
MAX_STREAM_BYTES = 1 << 30

#: Watermark-ack coalescing: one ack per this many frames.
ACK_EVERY = 8


def frame_count(size: int, frame_size: int = FRAME_SIZE) -> int:
    return max(1, (size + frame_size - 1) // frame_size)


def begin_header(block_id: str, size: int, *, expected_crc32c: int,
                 master_term: int, master_shard: str,
                 next_servers: list[str], next_data_ports: list[int],
                 frame_size: int = FRAME_SIZE) -> dict:
    return {
        "m": "WriteStream",
        "block_id": block_id,
        "size": size,
        "frame_size": frame_size,
        "expected_crc32c": expected_crc32c,
        "master_term": master_term,
        "master_shard": master_shard,
        "next_servers": next_servers,
        "next_data_ports": next_data_ports,
    }


def _raise_error_frame(header: dict) -> None:
    code = getattr(grpc.StatusCode, str(header.get("code")),
                   grpc.StatusCode.INTERNAL)
    message = str(header.get("message") or "")
    hinted = header.get("retry_after")
    if (isinstance(hinted, (int, float))
            and code is grpc.StatusCode.RESOURCE_EXHAUSTED
            and not message.startswith(OVERLOADED_PREFIX)):
        # Mid-stream native sheds carry a structured retry_after; fold it
        # into the Overloaded envelope for the retry-budget path.
        message = overloaded_message(float(hinted), message)
    raise RpcError(code, message)


async def send_block_stream(r, w, begin: dict, data) -> dict:
    """Client-side sender over an open blockport connection: ``r`` and
    ``w`` are both the pool's ``BlockConn`` (or a stream pair).

    Sends the begin frame, waits for ready, pipelines the data frames
    while a concurrent reader task folds watermark acks (max-merge), and
    returns the final response dict (with the observed high watermark as
    ``_watermark``). Raises RpcError for protocol-level errors; the
    ``stream_clean`` attribute on the exception tells the caller whether
    the connection is still in sync (error before any data frame) or must
    be discarded."""
    size = int(begin["size"])
    frame_size = int(begin["frame_size"])
    nframes = frame_count(size, frame_size)
    w.writelines(_pack_frame(dict(begin), None))
    await w.drain()
    try:
        h, _ = await _read_frame(r)
    except (asyncio.IncompleteReadError, ConnectionError) as e:
        raise ConnectionError(f"write stream begin failed: {e!r}") from None
    if not h.pop("ok", False):
        try:
            _raise_error_frame(h)
        except RpcError as e:
            e.stream_clean = True  # no data frames sent: conn in sync
            raise
    if not h.get("ready"):
        raise ConnectionError("write stream peer sent no ready ack")

    watermark = 0

    async def _read_acks() -> dict:
        nonlocal watermark
        while True:
            hh, _ = await _read_frame(r)
            if not hh.pop("ok", False):
                _raise_error_frame(hh)
            if hh.get("final"):
                return hh
            # MAX-merge: reordered/duplicated watermark acks never move
            # progress backwards (see test_writestream watermark tests).
            watermark = max(watermark, int(hh.get("w") or 0))

    mv = memoryview(data)
    sent_any = False
    reader = asyncio.create_task(_read_acks())
    try:
        for seq in range(nframes):
            if reader.done():
                # Early error/final from the hop (CRC mismatch, deadline
                # expiry): stop pushing frames immediately.
                break
            frame = mv[seq * frame_size:min((seq + 1) * frame_size, size)]
            w.writelines(_pack_frame({"q": seq, "c": crc32c(frame)}, frame))
            sent_any = True
            await _drain_backpressure(w)
        await w.drain()
        final = await reader
    except RpcError as e:
        e.stream_clean = not sent_any
        raise
    except (ConnectionError, OSError, asyncio.IncompleteReadError):
        # The hop tore the connection mid-stream; if its error frame got
        # through first, surface THAT instead of the transport failure.
        if not reader.done():
            reader.cancel()
        try:
            final = await reader
        except RpcError as e:
            e.stream_clean = False
            raise
        except (Exception, asyncio.CancelledError):
            raise ConnectionError("write stream torn mid-frame") from None
    finally:
        # No-op when the reader already returned/raised; stops it on
        # every other exit (including cancellation of this coroutine).
        reader.cancel()
    final["_watermark"] = max(watermark, int(final.get("w") or 0))
    return final


class ForwardStream:
    """A hop's downstream leg: relays frames as they arrive upstream.

    Used by the asyncio fallback handler (service.rpc_write_stream) to
    fan each verified frame out to the next chain hop before the local
    disk append — the native engine does the same in C++."""

    def __init__(self, r, w):
        #: both the pool's BlockConn (BlockConnPool.stream_checkout).
        self.r = r
        self.w = w
        self.ok = False

    async def begin(self, begin: dict) -> None:
        """Send the downstream begin and consume the ready ack. Raises
        RpcError (connection still in sync) or ConnectionError."""
        self.w.writelines(_pack_frame(dict(begin), None))
        await self.w.drain()
        h, _ = await _read_frame(self.r)
        if not h.pop("ok", False):
            _raise_error_frame(h)
        if not h.get("ready"):
            raise ConnectionError("downstream sent no ready ack")
        self.ok = True

    async def send(self, seq: int, crc: int, payload) -> None:
        self.w.writelines(_pack_frame({"q": seq, "c": crc}, payload))
        await _drain_backpressure(self.w)

    async def finish(self) -> dict:
        """Drain downstream watermark acks and return its final dict."""
        await self.w.drain()
        while True:
            h, _ = await _read_frame(self.r)
            if not h.pop("ok", False):
                _raise_error_frame(h)
            if h.get("final"):
                return h

    def close(self) -> None:
        self.ok = False
        self.w.close()
