"""Blockport data plane: protocol edges, fallback, and the native engine.

Covers what the end-to-end suites only exercise implicitly: empty-payload
framing, gRPC fallback when a peer has no blockport, per-shard fencing
through the NATIVE engine, its corrupt-read flagging, and chain transport
safety on mixed clusters (native first hop + blockport-less tail must not
degrade replication).
"""

from __future__ import annotations

import asyncio
import contextlib

import msgpack
import numpy as np
import pytest

from tests.test_chunkserver import Cluster, _rand, _write
from tpudfs.common import blocknet, native, telemetry, writestream
from tpudfs.common.blocknet import (
    BlockConnPool,
    BlockPortServer,
    _pack_frame,
    _read_frame,
)
from tpudfs.common.checksum import crc32c
from tpudfs.common.rpc import ClientTls, RpcError, ServerTls
from tpudfs.chunkserver.service import SERVICE
from tpudfs.testing.certs import make_test_pki


@pytest.fixture
def cluster():
    return Cluster()


async def test_blockport_roundtrip_and_empty_payload(cluster, tmp_path):
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    pool = BlockConnPool()
    data = _rand(70_000, 1)
    for payload in (data, b""):
        bid = f"bp-{len(payload)}"
        resp = await pool.call(cluster.client, cs.address, SERVICE,
                               "WriteBlock", {
                                   "block_id": bid, "data": payload,
                                   "next_servers": [],
                                   "expected_crc32c": crc32c(payload),
                                   "master_term": 0,
                               })
        assert resp["success"] and resp["replicas_written"] == 1
        back = await pool.call(cluster.client, cs.address, SERVICE,
                               "ReadBlock", {"block_id": bid,
                                             "offset": 0, "length": 0})
        assert back["data"] == payload
        assert back["total_size"] == len(payload)
    await pool.close()
    await cluster.stop()


async def test_blockport_grpc_fallback_when_disabled(cluster, tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("TPUDFS_BLOCKPORT", "0")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    assert cs.data_port == 0  # no blockport at all
    data = _rand(5000, 2)
    resp = await _write(cluster.client, cs.address, "fb", data)
    assert resp["success"]
    pool = BlockConnPool()
    back = await pool.call(cluster.client, cs.address, SERVICE, "ReadBlock",
                           {"block_id": "fb", "offset": 0, "length": 0})
    assert back["data"] == data  # transparently served over gRPC
    await pool.close()
    await cluster.stop()


async def test_native_engine_running_and_counts(cluster, tmp_path):
    if not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    assert cs._native_dp is not None and cs.data_port > 0
    pool = BlockConnPool()
    data = _rand(33_000, 3)
    await pool.call(cluster.client, cs.address, SERVICE, "WriteBlock", {
        "block_id": "nat", "data": data, "next_servers": [],
        "expected_crc32c": crc32c(data), "master_term": 0,
    })
    await pool.call(cluster.client, cs.address, SERVICE, "ReadBlock",
                    {"block_id": "nat", "offset": 0, "length": 0})
    stats = cs.data_plane_stats()
    assert stats["writes"] >= 1 and stats["reads"] >= 1
    # The engine's writes are visible to the Python store (same format).
    assert cs.store.read("nat") == data
    cs.store.verify_full("nat")
    await pool.close()
    await cluster.stop()


async def test_native_engine_per_shard_fencing(cluster, tmp_path):
    if not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    pool = BlockConnPool()
    data = _rand(4000, 4)

    async def write(term, shard, bid):
        return await pool.call(cluster.client, cs.address, SERVICE,
                               "WriteBlock", {
                                   "block_id": bid, "data": data,
                                   "next_servers": [],
                                   "expected_crc32c": crc32c(data),
                                   "master_term": term,
                                   "master_shard": shard,
                               })

    assert (await write(5, "shard-a", "f1"))["success"]
    # Stale term in the SAME shard is fenced...
    with pytest.raises(RpcError) as ei:
        await write(3, "shard-a", "f2")
    assert "Stale master term" in ei.value.message
    # ...but a lower term in a DIFFERENT shard is fine (independent Raft
    # groups — the chaos-tier regression).
    assert (await write(2, "shard-b", "f3"))["success"]
    # And Python-side fencing sees the native-learned epoch via its own
    # observe path (push direction).
    cs.observe_term(9, "shard-a")
    with pytest.raises(RpcError):
        await write(8, "shard-a", "f4")
    await pool.close()
    await cluster.stop()


async def test_native_engine_corrupt_read_flags_bad_block(cluster, tmp_path):
    if not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    pool = BlockConnPool()
    data = _rand(20_000, 5)
    await pool.call(cluster.client, cs.address, SERVICE, "WriteBlock", {
        "block_id": "rot", "data": data, "next_servers": [],
        "expected_crc32c": crc32c(data), "master_term": 0,
    })
    # Bit-rot the stored file (sidecar untouched).
    p = cs.store.block_path("rot")
    raw = bytearray(p.read_bytes())
    raw[123] ^= 0xFF
    p.write_bytes(bytes(raw))
    with pytest.raises(RpcError) as ei:
        await pool.call(cluster.client, cs.address, SERVICE, "ReadBlock",
                        {"block_id": "rot", "offset": 0, "length": 0})
    assert "corruption" in ei.value.message.lower()
    cs.poll_native_bad_blocks()  # the heartbeat hook
    assert "rot" in cs.pending_bad_blocks
    await pool.close()
    await cluster.stop()


async def test_mixed_chain_keeps_full_replication(cluster, tmp_path,
                                                  monkeypatch):
    """Mixed chains must never silently degrade replication. Exercised on
    the two hazard paths: (a) gRPC entry whose Python handler must route
    the next (blockport-less) hop over gRPC, and (b) the CLIENT chain
    entry — chain_info must refuse to hand a mixed chain to cs0's NATIVE
    engine (which forwards only to blockports)."""
    await cluster.start_master()
    cs0 = await cluster.add_cs(tmp_path, 0)
    monkeypatch.setenv("TPUDFS_BLOCKPORT", "0")
    cs1 = await cluster.add_cs(tmp_path, 1)  # no blockport
    monkeypatch.delenv("TPUDFS_BLOCKPORT")
    cs2 = await cluster.add_cs(tmp_path, 2)
    assert cs1.data_port == 0 and cs0.data_port > 0
    data = _rand(60_000, 6)
    resp = await _write(cluster.client, cs0.address, "mix", data,
                        next_servers=[cs1.address, cs2.address])
    assert resp["success"], resp
    assert resp["replicas_written"] == 3, resp
    for s in (cs0, cs1, cs2):
        assert s.store.read("mix") == data

    # (b) The client's chain entry: with cs0's native engine up front and
    # a blockport-less member in the chain, _write_replicated_block must
    # pick the gRPC entry (first_hop_safe False) — all replicas land.
    from tpudfs.client.client import Client

    client = Client(["127.0.0.1:1"], rpc_client=cluster.client)
    ports, safe = await client.block_pool.chain_info(
        cluster.client, [cs0.address, cs1.address, cs2.address], SERVICE
    )
    assert ports[0] > 0 and ports[1] == 0 and not safe
    await client._write_replicated_block(
        "mix2", data, [cs0.address, cs1.address, cs2.address], term=0
    )
    for s in (cs0, cs1, cs2):
        assert s.store.read("mix2") == data
    # All-blockport chains DO fuse through the native engine.
    ports, safe = await client.block_pool.chain_info(
        cluster.client, [cs0.address, cs2.address], SERVICE
    )
    assert safe and all(ports)
    await client._write_replicated_block(
        "mix3", data, [cs0.address, cs2.address], term=0
    )
    assert cs0.store.read("mix3") == data
    assert cs2.store.read("mix3") == data
    assert cs0.data_plane_stats()["forwards"] >= 1  # native chain engaged
    await cluster.stop()


async def test_read_blocks_caps_budget(cluster, tmp_path):
    """ReadBlocks slots beyond the count/byte budget return -1 (caller
    falls back) instead of unbounded buffering."""
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    data = _rand(2000, 7)
    for i in range(3):
        await _write(cluster.client, cs.address, f"cap{i}", data)
    # Count cap: ask for more slots than allowed.
    cs.READ_BATCH_MAX_SLOTS = 2
    resp = await cs.rpc_read_blocks(
        {"block_ids": ["cap0", "cap1", "cap2"]})
    assert resp["sizes"] == [len(data), len(data), -1]
    assert b"".join(resp["data_parts"]) == data + data
    # Byte cap: second slot would cross the budget.
    cs.READ_BATCH_MAX_SLOTS = 256
    cs.READ_BATCH_MAX_BYTES = len(data) + 10
    resp = await cs.rpc_read_blocks(
        {"block_ids": ["cap0", "cap1", "missing"]})
    assert resp["sizes"] == [len(data), -1, -1]
    await cluster.stop()


async def test_native_engine_lru_cache_and_invalidation(cluster, tmp_path):
    """The engine's block cache: repeated full reads hit memory (counted),
    writes and Python-side invalidation (delete/recovery paths) drop the
    entry, and range reads slice the cached block (reference
    chunkserver.rs:67-76 semantics on the native hot path)."""
    if not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    pool = BlockConnPool()
    data = _rand(8192, 11)

    async def write(bid, payload):
        return await pool.call(cluster.client, cs.address, SERVICE,
                               "WriteBlock", {
                                   "block_id": bid, "data": payload,
                                   "next_servers": [],
                                   "expected_crc32c": crc32c(payload),
                                   "master_term": 0,
                               })

    async def read(bid, offset=0, length=0):
        return await pool.call(cluster.client, cs.address, SERVICE,
                               "ReadBlock", {"block_id": bid,
                                             "offset": offset,
                                             "length": length})

    await write("lru", data)
    s0 = cs.data_plane_stats()
    assert (await read("lru"))["data"] == data          # miss, populates
    assert (await read("lru"))["data"] == data          # hit
    assert (await read("lru", 100, 50))["data"] == data[100:150]  # hit
    s1 = cs.data_plane_stats()
    assert s1["cache_misses"] - s0["cache_misses"] == 1
    assert s1["cache_hits"] - s0["cache_hits"] == 2
    # Stats RPC reports the COMBINED planes.
    rpc_stats = await cs.rpc_stats({})
    assert rpc_stats["cache_hits"] >= 2

    # A write invalidates: the next read re-reads (and re-verifies) disk.
    data2 = _rand(8192, 12)
    await write("lru", data2)
    assert (await read("lru"))["data"] == data2         # miss
    s2 = cs.data_plane_stats()
    assert s2["cache_misses"] - s1["cache_misses"] == 1

    # Python-side invalidation (the delete/recovery paths use this helper)
    # also drops the native entry.
    assert (await read("lru"))["data"] == data2         # hit again
    cs.invalidate_cached("lru")
    assert (await read("lru"))["data"] == data2         # miss after drop
    s3 = cs.data_plane_stats()
    assert s3["cache_misses"] - s2["cache_misses"] == 1

    # Batched reads ride the same cache.
    resp = await pool.call(cluster.client, cs.address, SERVICE,
                           "ReadBlocks", {"block_ids": ["lru"]})
    assert resp["sizes"] == [len(data2)] and resp["data"] == data2
    s4 = cs.data_plane_stats()
    assert s4["cache_hits"] - s3["cache_hits"] == 1
    await pool.close()
    await cluster.stop()


async def test_native_term_drain_closes_python_plane_window(cluster,
                                                            tmp_path):
    """Terms the engine learns from blockport requests flow back into
    ChunkServer.known_terms via sync_native_terms (heartbeat loop), so a
    deposed master's stale write arriving on the gRPC/Python plane is
    fenced BEFORE the next master heartbeat (the round-3 advisor's
    one-way-sync window)."""
    if not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0)
    pool = BlockConnPool()
    data = _rand(1000, 13)
    await pool.call(cluster.client, cs.address, SERVICE, "WriteBlock", {
        "block_id": "td", "data": data, "next_servers": [],
        "expected_crc32c": crc32c(data), "master_term": 7,
        "master_shard": "shard-x",
    })
    # Engine learned term 7; Python hasn't seen it yet.
    assert cs.known_terms.get("shard-x", 0) < 7
    cs.sync_native_terms()
    assert cs.known_terms["shard-x"] == 7
    # The Python/gRPC plane now fences a stale-term write immediately.
    with pytest.raises(RpcError) as ei:
        await cluster.client.call(cs.address, SERVICE, "WriteBlock", {
            "block_id": "td2", "data": data, "next_servers": [],
            "expected_crc32c": crc32c(data), "master_term": 5,
            "master_shard": "shard-x",
        })
    assert "Stale master term" in ei.value.message
    await pool.close()
    await cluster.stop()


# ------------- the client's receive path (BlockConn), plain and over TLS
#
# One family over a loopback BlockPortServer. Handlers answer whole frames;
# the cases that need a frame cut in two use a stream handler, which owns
# the connection and writes raw bytes.

_ADDR = "127.0.0.1:9"  # the peer's "gRPC" address: its port is cached
_SENTINEL = 0xAA


@pytest.fixture(scope="module")
def pki(tmp_path_factory):
    return make_test_pki(tmp_path_factory.mktemp("pki"))


class _Loopback:
    def __init__(self, tls: bool, pki: dict):
        self.server = BlockPortServer(
            {}, tls=ServerTls(pki["server_cert"], pki["server_key"])
            if tls else None)
        self.handlers = self.server.handlers
        self.stream_handlers = self.server.stream_handlers
        self.pool = BlockConnPool(
            tls=ClientTls(ca_path=pki["ca"]) if tls else None)

    async def __aenter__(self):
        port = await self.server.start()
        self.hostport = f"127.0.0.1:{port}"
        self.pool._ports[_ADDR] = port
        return self

    async def __aexit__(self, *exc):
        await self.pool.close()
        await self.server.stop()

    def call(self, method, req=None, **kw):
        return self.pool.call(None, _ADDR, "Svc", method, req or {}, **kw)

    def pooled(self) -> int:
        return len(self.pool._free.get(self.hostport, []))

    def cut_frame(self, payload: bytes, first: int, then):
        """Stream handler ``Cut``: header, the payload's first ``first``
        bytes, ``await then()``, and the rest if that returned True."""
        sent_first = asyncio.Event()

        async def cut(_req, _r, w):
            h = msgpack.packb({"ok": True, "_d": 1})
            w.write(blocknet._U32.pack(len(h)) + h
                    + blocknet._U64.pack(len(payload)) + payload[:first])
            await w.drain()
            sent_first.set()
            if await then():
                w.write(payload[first:])
                await w.drain()
            return False

        self.stream_handlers["Cut"] = cut
        return sent_first


@contextlib.contextmanager
def _received():
    """The attributes of the ``blockport.recv_payload`` spans of the calls
    made inside (tracing is on for those alone)."""
    got: list[dict] = []
    telemetry.enable(sink=lambda r: got.append(r.attrs)
                     if r.name == "blockport.recv_payload" else None)
    try:
        yield got
    finally:
        telemetry.disable()


def _into(buf, spans):
    """Scatter callback handing out ``buf[a:b]`` for each span."""
    view = memoryview(buf)
    return lambda _header, _plen: [view[a:b] for a, b in spans]


async def _case_direct_fills_segments(lb):
    """Slots of a round land in place; a slot of another size than asked
    is drained to scratch and the stream stays framed."""
    parts = [_rand(300_000, 1), _rand(70_000, 2), _rand(200_000, 3)]

    async def read(_req):
        return {"sizes": [len(p) for p in parts], "data_parts": parts}

    lb.handlers["Read"] = read
    flat = bytearray([_SENTINEL]) * 600_000
    scratch = bytearray(70_000)
    view = memoryview(flat)

    def scatter(header, plen):
        assert header["sizes"] == [300_000, 70_000, 200_000]
        assert plen == 570_000
        return [view[0:300_000], scratch, view[300_000:500_000]]

    with _received() as received:
        for _ in range(2):  # the second frame rides the pooled connection
            resp = await lb.call("Read", payload_into=scatter)
            assert resp["data"] is None
            assert flat[:300_000] == parts[0]
            assert scratch == parts[1]
            assert flat[300_000:500_000] == parts[2]
            assert flat[500_000:] == bytes([_SENTINEL]) * 100_000
    assert [a["bytes"] for a in received] == [570_000, 570_000]
    # All but what one recv brought along with each header.
    assert all(570_000 - blocknet._RX_BUF <= a["direct"] <= 570_000
               for a in received)
    assert lb.pooled() == 1


async def _case_zero_length_payload(lb):
    async def read(_req):
        return {"data": b"", "total_size": 0}

    lb.handlers["Read"] = read
    asked = []
    with _received() as received:
        resp = await lb.call(
            "Read", payload_into=lambda h, n: asked.append(n))
    assert resp["data"] == b"" and resp["total_size"] == 0
    assert not asked, "an empty payload has no destination to ask for"
    assert [(a["bytes"], a["direct"]) for a in received] == [(0, 0)]
    assert lb.pooled() == 1


async def _case_segments_short_of_plen(lb):
    data = _rand(100_000, 4)

    async def read(_req):
        return {"data": data}

    lb.handlers["Read"] = read
    buf = bytearray(100_000)
    with pytest.raises(ConnectionError, match="cover 99999 of 100000"):
        await lb.pool._call_blockport(lb.hostport, "Read", {},
                                      _into(buf, [(0, 99_999)]))
    assert lb.pooled() == 0, "a connection mid-payload went to the pool"
    with pytest.raises(RpcError) as ei:  # and through call(): UNAVAILABLE
        await lb.call("Read", payload_into=_into(buf, [(0, 99_999)]))
    assert ei.value.code.name == "UNAVAILABLE"


async def _case_buffered_path_keeps_data(lb):
    """No callback, a callback that declines, and an error frame: the
    payload comes back as ``resp["data"]``, equal to the bytes sent."""
    import grpc

    sent = {n: _rand(n, 5)
            for n in (100, blocknet._RX_BUF, blocknet._RX_BUF + 1, 600_000)}

    async def read(req):
        if req["n"] < 0:
            raise RpcError(grpc.StatusCode.NOT_FOUND, "no such block")
        return {"data": sent[req["n"]]}

    lb.handlers["Read"] = read
    total = 0
    with _received() as received:
        for n, data in sent.items():
            for into in (None, lambda _h, _n: None):
                resp = await lb.call("Read", {"n": n}, payload_into=into)
                assert resp["data"] == data and len(resp["data"]) == n
                total += n
        asked = []
        with pytest.raises(RpcError) as ei:
            await lb.call("Read", {"n": -1},
                          payload_into=lambda h, n: asked.append(h))
    assert ei.value.code.name == "NOT_FOUND" and not asked
    assert all(a["direct"] == 0 for a in received)
    assert sum(a["bytes"] for a in received) == total
    assert lb.pooled() == 1  # an error frame leaves the stream framed


async def _case_peer_closes_mid_payload(lb):
    payload = _rand(1 << 20, 6)

    async def hang_up():
        return False

    lb.cut_frame(payload, 1 << 19, hang_up)
    buf = bytearray([_SENTINEL]) * (1 << 20)
    with pytest.raises(RpcError) as ei:
        await lb.call("Cut", payload_into=_into(buf, [(0, 1 << 20)]))
    assert ei.value.code.name == "UNAVAILABLE"
    assert not lb.pool.breakers.allow(_ADDR), "the breaker stayed closed"
    assert _ADDR not in lb.pool._ports, "the cached port was kept"
    assert lb.pooled() == 0
    assert buf[: 1 << 19] == payload[: 1 << 19]
    assert buf[1 << 19 :] == bytes([_SENTINEL]) * (1 << 19)


async def _abandoned_mid_payload(lb, abandon):
    """The call ends (``abandon``) with half a payload in; the other half
    is sent afterwards and must not reach the caller's buffer, which by
    then may belong to another round."""
    payload = _rand(1 << 20, 7)
    go_on = asyncio.Event()

    async def rest_later():
        await go_on.wait()
        return True

    sent_first = lb.cut_frame(payload, 1 << 19, rest_later)
    buf = bytearray([_SENTINEL]) * (1 << 20)
    await abandon(lb.call("Cut", timeout=0.5,
                          payload_into=_into(buf, [(0, 1 << 20)])),
                  sent_first)
    go_on.set()
    await asyncio.sleep(0.3)
    assert buf[1 << 19 :] == bytes([_SENTINEL]) * (1 << 19), \
        "bytes that arrived after the call returned were written"
    assert lb.pooled() == 0, "a connection mid-payload went to the pool"


async def _case_timeout_mid_payload(lb):
    async def time_out(call, _sent_first):
        with pytest.raises(RpcError) as ei:
            await call
        assert ei.value.code.name == "DEADLINE_EXCEEDED"

    await _abandoned_mid_payload(lb, time_out)


async def _case_cancel_mid_payload(lb):
    async def cancel(call, sent_first):
        task = asyncio.ensure_future(call)
        await sent_first.wait()
        await asyncio.sleep(0.05)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    await _abandoned_mid_payload(lb, cancel)


async def _case_fifty_calls_one_connection(lb):
    """Direct and buffered payloads of changing sizes, back to back on
    one pooled connection: every frame boundary holds."""
    async def read(req):
        return {"data": _rand(req["n"], req["n"]), "n": req["n"]}

    lb.handlers["Read"] = read
    for i in range(50):
        n = (i * 79_193) % 600_000 + (i % 3)  # both sides of _RX_BUF
        if i % 2:
            buf = bytearray(n)
            resp = await lb.call(
                "Read", {"n": n},
                payload_into=_into(buf, [(0, n // 3), (n // 3, n)]))
            got = buf
        else:
            resp = await lb.call("Read", {"n": n})
            got = resp["data"]
        assert resp["n"] == n and got == _rand(n, n)
    assert len(lb.server._conns) == 1 and lb.pooled() == 1


async def _case_write_stream_acks_back_to_back(lb):
    """A write stream's receive side on the same connection class: ready,
    then every watermark ack and the final in ONE write, so they arrive
    in one recv and have to come apart again."""
    data = _rand(5 * writestream.FRAME_SIZE + 17, 8)
    frames = writestream.frame_count(len(data))
    received = bytearray()

    async def write_stream(_req, r, w):
        w.writelines(_pack_frame({"ok": True, "ready": 1}, None))
        for _ in range(frames):
            _h, chunk = await _read_frame(r)
            received.extend(chunk)
        acks = []
        for n in range(1, frames + 1):
            acks += _pack_frame({"ok": True, "w": n}, None)
        acks += _pack_frame({"ok": True, "final": 1, "success": True}, None)
        w.write(b"".join(acks))
        await w.drain()
        return True

    async def ping(_req):
        return {"pong": 1}

    lb.stream_handlers["WriteStream"] = write_stream
    lb.handlers["Ping"] = ping
    conn = await lb.pool._checkout(lb.hostport)
    begin = writestream.begin_header(
        "b", len(data), expected_crc32c=crc32c(data), master_term=0,
        master_shard="", next_servers=[], next_data_ports=[])
    final = await writestream.send_block_stream(conn, conn, begin, data)
    assert final["success"] and final["_watermark"] == frames
    assert received == data
    lb.pool._release(lb.hostport, conn)
    assert lb.pooled() == 1, "the stream left the connection unframed"
    assert (await lb.call("Ping"))["pong"] == 1
    assert len(lb.server._conns) == 1


async def _case_backlog_larger_than_the_buffer(lb):
    """More unread small frames than the connection's buffer holds: the
    transport is paused, not overrun, and every frame comes out whole
    and in order as the reader catches up."""
    n = 4 * blocknet._RX_BUF // 256

    async def flood(_req, _r, w):
        w.write(b"".join(
            b"".join(_pack_frame({"ok": True, "w": i, "pad": "x" * 240}, None))
            for i in range(n)))
        await w.drain()
        return True

    lb.stream_handlers["Flood"] = flood
    conn = await lb.pool._checkout(lb.hostport)
    conn.writelines(_pack_frame({"m": "Flood"}, None))
    await conn.drain()
    await asyncio.sleep(0.2)  # let the backlog build before reading
    for i in range(n):
        header, payload = await _read_frame(conn)
        assert header["w"] == i and payload == b""
    lb.pool._release(lb.hostport, conn)
    assert lb.pooled() == 1


_CASES = [
    _case_direct_fills_segments,
    _case_zero_length_payload,
    _case_segments_short_of_plen,
    _case_buffered_path_keeps_data,
    _case_peer_closes_mid_payload,
    _case_timeout_mid_payload,
    _case_cancel_mid_payload,
    _case_fifty_calls_one_connection,
    _case_write_stream_acks_back_to_back,
    _case_backlog_larger_than_the_buffer,
]


@pytest.mark.parametrize("tls", [False, True], ids=["plain", "tls"])
@pytest.mark.parametrize("case", _CASES,
                         ids=[c.__name__[len("_case_"):] for c in _CASES])
async def test_client_receive(case, tls, pki):
    async with _Loopback(tls, pki) as lb:
        await case(lb)


# ------------------------------------------------- the read path's clocks


_PLANES = ["native", "python"]


async def _plane(cluster, tmp_path, plane):
    if plane == "native" and not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0,
                              python_data_plane=plane == "python")
    assert (cs._native_dp is not None) == (plane == "native")
    return cs


def _moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


@pytest.mark.parametrize("plane", _PLANES)
async def test_read_stages_count_each_read(cluster, tmp_path, plane):
    """``read_stages`` on either plane: one call and its bytes for a whole,
    a cache-served and a ranged ``ReadBlock``, a ``NOT_FOUND``, one frame
    with its slots and a missing slot for ``ReadBlocks``; ``Stats`` carries
    the group."""
    from tpudfs.chunkserver.service import READ_STAGE_KEYS

    cs = await _plane(cluster, tmp_path, plane)
    pool = BlockConnPool()
    data = _rand(70_000, 21)
    try:
        await pool.call(cluster.client, cs.address, SERVICE, "WriteBlock", {
            "block_id": "rs", "data": data, "next_servers": [],
            "expected_crc32c": crc32c(data), "master_term": 0})

        async def read(method, req):
            before = cs.read_stage_stats()
            resp = await pool.call(cluster.client, cs.address, SERVICE,
                                   method, req)
            assert blocknet.READ_NS_KEY not in resp
            return resp, _moved(before, cs.read_stage_stats())

        whole = {"block_id": "rs", "offset": 0, "length": 0}
        resp, moved = await read("ReadBlock", whole)  # a miss: from disk
        assert resp["data"] == data
        assert moved.pop("rb_read_ns") > 0 and moved.pop("rb_send_ns") > 0
        assert moved == {"rb_calls": 1, "rb_bytes": 70_000}
        resp, moved = await read("ReadBlock", whole)  # from the cache
        assert resp["data"] == data
        assert {"rb_read_ns", "rb_send_ns"} <= set(moved)
        assert (moved["rb_calls"], moved["rb_bytes"],
                moved["rb_cache_calls"]) == (1, 70_000, 1)
        resp, moved = await read(
            "ReadBlock", {"block_id": "rs", "offset": 100, "length": 5000})
        assert resp["data"] == data[100:5100]
        assert (moved["rb_calls"], moved["rb_bytes"]) == (1, 5000)
        assert moved["rb_read_ns"] > 0

        before = cs.read_stage_stats()
        with pytest.raises(RpcError) as ei:
            await pool.call(cluster.client, cs.address, SERVICE, "ReadBlock",
                            {"block_id": "nope", "offset": 0, "length": 0})
        assert ei.value.code.name == "NOT_FOUND"
        moved = _moved(before, cs.read_stage_stats())
        assert moved.pop("rb_read_ns") > 0
        assert moved == {"rb_calls": 1, "rb_not_found": 1}

        resp, moved = await read("ReadBlocks",
                                 {"block_ids": ["rs", "nope", "rs"]})
        assert resp["sizes"] == [70_000, -1, 70_000]
        assert moved.pop("rbs_read_ns") > 0 and moved.pop("rbs_send_ns") > 0
        assert moved == {"rbs_frames": 1, "rbs_slots": 3, "rbs_missing": 1,
                         "rbs_bytes": 140_000}

        stages = (await cs.rpc_stats({}))["read_stages"]
        assert tuple(stages) == READ_STAGE_KEYS
        assert stages == cs.read_stage_stats()
        assert stages["rb_admit_ns"] == stages["rbs_admit_ns"] == 0  # no QoS
    finally:
        await pool.close()
        await cluster.stop()


@pytest.mark.parametrize("plane", _PLANES)
async def test_engine_read_time_rides_the_wait_header_span(cluster, tmp_path,
                                                           plane):
    """With tracing on, each read's ``blockport.wait_header`` carries the
    server's own read time, which is a part of the wait; the caller's
    response does not."""
    cs = await _plane(cluster, tmp_path, plane)
    pool = BlockConnPool()
    data = _rand(200_000, 22)
    records = []
    try:
        await pool.call(cluster.client, cs.address, SERVICE, "WriteBlock", {
            "block_id": "wh", "data": data, "next_servers": [],
            "expected_crc32c": crc32c(data), "master_term": 0})
        telemetry.enable(sink=records.append)
        try:
            for req in ({"block_id": "wh", "offset": 0, "length": 0},
                        {"block_id": "wh", "offset": 512, "length": 7000}):
                resp = await pool.call(cluster.client, cs.address, SERVICE,
                                       "ReadBlock", req)
                assert blocknet.READ_NS_KEY not in resp
            resp = await pool.call(cluster.client, cs.address, SERVICE,
                                   "ReadBlocks", {"block_ids": ["wh", "x"]})
            assert resp["sizes"] == [200_000, -1]
            assert blocknet.READ_NS_KEY not in resp
        finally:
            telemetry.disable()
    finally:
        await pool.close()
        await cluster.stop()
    waits = [r for r in records if r.name == "blockport.wait_header"]
    assert [r.attrs["method"] for r in waits] == \
        ["ReadBlock", "ReadBlock", "ReadBlocks"]
    for r in waits:
        engine_ms = r.attrs["engine_read_ms"]
        assert 0 < engine_ms <= (r.end_ns - r.start_ns) / 1e6, r


def _raw_call(port: int, header: dict) -> bytes:
    """One request frame on a socket of its own; the response's header
    bytes, as they came off the wire."""
    import socket

    with socket.create_connection(("127.0.0.1", port), timeout=10) as sock:
        sock.sendall(b"".join(blocknet._pack_frame(header, None)))
        f = sock.makefile("rb")
        hlen = blocknet._U32.unpack(f.read(4))[0]
        return f.read(hlen)


@pytest.mark.parametrize("plane", _PLANES)
async def test_tracing_off_leaves_every_read_frame_as_it_was(
        cluster, tmp_path, plane, monkeypatch):
    """With tracing off no request carries the timing flag, and the
    responses are the bytes they always were; a request with the flag is
    answered with the read time as one more key of the header."""
    cs = await _plane(cluster, tmp_path, plane)
    pool = BlockConnPool()
    data = _rand(3000, 23)
    sent = []
    real_pack = blocknet._pack_frame

    def pack(header, payload):
        sent.append(dict(header))
        return real_pack(header, payload)

    monkeypatch.setattr(blocknet, "_pack_frame", pack)
    try:
        await pool.call(cluster.client, cs.address, SERVICE, "WriteBlock", {
            "block_id": "bi", "data": data, "next_servers": [],
            "expected_crc32c": crc32c(data), "master_term": 0})
        one = {"block_id": "bi", "offset": 0, "length": 0}
        many = {"block_ids": ["bi", "zz"]}
        await pool.call(cluster.client, cs.address, SERVICE, "ReadBlock", one)
        await pool.call(cluster.client, cs.address, SERVICE, "ReadBlocks",
                        many)
        # (the asyncio server packs its responses here too: no "m")
        assert [h["m"] for h in sent if "m" in h] == \
            ["WriteBlock", "ReadBlock", "ReadBlocks"]
        assert not any(blocknet.READ_TIMING_KEY in h
                       or blocknet.READ_NS_KEY in h for h in sent)

        port = cs.data_port
        got_one = await asyncio.to_thread(
            _raw_call, port, {"m": "ReadBlock", **one})
        got_many = await asyncio.to_thread(
            _raw_call, port, {"m": "ReadBlocks", **many})
        if plane == "native":
            want_one = {"ok": True, "_d": 1, "bytes_read": 3000,
                        "total_size": 3000}
            want_many = {"ok": True, "_d": 1, "sizes": [3000, -1]}
        else:  # the handler's keys, then the server's
            want_one = {"bytes_read": 3000, "total_size": 3000, "ok": True,
                        "_d": 1}
            want_many = {"sizes": [3000, -1], "ok": True, "_d": 1}
        assert got_one == msgpack.packb(want_one)
        assert got_many == msgpack.packb(want_many)

        timed = await asyncio.to_thread(
            _raw_call, port,
            {"m": "ReadBlock", **one, blocknet.READ_TIMING_KEY: 1})
        header = msgpack.unpackb(timed)
        assert header.pop(blocknet.READ_NS_KEY) > 0
        assert msgpack.packb(header) == got_one  # one key more, no other
    finally:
        await pool.close()
        await cluster.stop()


# ------------------------------------- a ReadBlocks frame, sent as it is read


def _recv_exact(sock, n: int) -> bytes:
    """``n`` bytes off ``sock``, or fewer where the peer closes first."""
    got = bytearray()
    while len(got) < n:
        part = sock.recv(min(n - len(got), 1 << 20))
        if not part:
            break
        got += part
    return bytes(got)


def _frame_bytes(header: dict, payload: bytes) -> bytes:
    h = msgpack.packb(header)
    return (blocknet._U32.pack(len(h)) + h + blocknet._U64.pack(len(payload))
            + payload)


def _read_blocks_raw(port: int, *requests: list[str]) -> list[bytes]:
    """Each ``ReadBlocks`` request in turn on one connection; each response
    frame's bytes as they came off the wire."""
    import socket

    out = []
    with socket.create_connection(("127.0.0.1", port), timeout=10) as sock:
        for ids in requests:
            sock.sendall(b"".join(blocknet._pack_frame(
                {"m": "ReadBlocks", "block_ids": ids}, None)))
            head = _recv_exact(sock, 4)
            head += _recv_exact(sock, blocknet._U32.unpack(head)[0] + 8)
            plen = blocknet._U64.unpack(head[-8:])[0]
            out.append(head + _recv_exact(sock, plen))
    return out


async def test_read_blocks_frame_is_the_same_bytes_sent_block_by_block(
        cluster, tmp_path):
    """The native engine opens and sizes every slot, sends the header and
    then each block as it reads it: the frame is byte for byte the one it
    built whole before, for present, missing, bad, cached and short last
    blocks and slots over the budget, and the connection stays framed."""
    cs = await _plane(cluster, tmp_path, "native")
    pool = BlockConnPool()
    full, cached, short = _rand(3000, 24), _rand(2000, 25), _rand(1000, 26)
    try:
        for bid, data in (("sa", full), ("sc", cached), ("sl", short)):
            cs.store.write(bid, data)
        back = await pool.call(cluster.client, cs.address, SERVICE,
                               "ReadBlock", {"block_id": "sc", "offset": 0,
                                             "length": 0})
        assert back["data"] == cached  # a verified whole read: now cached
        # 256 slots fit a frame; the last two are over the slot budget.
        ids = ["sa", "gone", "sc", "../sa", "sl"] + ["sa"] * 251 \
            + ["sc", "sl"]
        sizes = [3000, -1, 2000, -1, 1000] + [3000] * 251 + [-1, -1]
        payload = full + cached + short + full * 251
        before, hits = cs.read_stage_stats(), cs.data_plane_stats()
        got = await asyncio.to_thread(_read_blocks_raw, cs.data_port, ids,
                                      ["sl", "sa"])
        assert got[0] == _frame_bytes(
            {"ok": True, "_d": 1, "sizes": sizes}, payload)
        assert got[1] == _frame_bytes(
            {"ok": True, "_d": 1, "sizes": [1000, 3000]}, short + full)
        moved = _moved(before, cs.read_stage_stats())
        assert moved.pop("rbs_read_ns") > 0 and moved.pop("rbs_send_ns") > 0
        assert moved == {"rbs_frames": 2, "rbs_slots": 260, "rbs_missing": 4,
                         "rbs_bytes": len(payload) + 4000}
        assert cs.data_plane_stats()["cache_hits"] - hits["cache_hits"] == 1
    finally:
        await pool.close()
        await cluster.stop()


@pytest.mark.parametrize("after_header",
                         ["unlinked", "replaced", "truncated"])
async def test_read_blocks_block_changed_after_the_header(cluster, tmp_path,
                                                          after_header):
    """Every slot is open before the header goes out. Blocks unlinked or
    replaced (a new file renamed over it) after the header are still sent
    whole, as they were when opened. A block cut short after the header
    tears the frame: the engine sends nothing it did not read, closes the
    connection mid-payload and counts the frame in ``rbs_torn``. The frame
    is larger than both sockets' buffers together, so the last block is
    read after the client has seen the header."""
    import os
    import socket

    cs = await _plane(cluster, tmp_path, "native")
    size = 4 << 20
    blocks = {f"big{i}": _rand(size, 30 + i) for i in range(5)}
    for bid, data in blocks.items():
        cs.store.write(bid, data)
    paths = {bid: cs.store.block_path(bid) for bid in blocks}
    ids = list(blocks)
    want = b"".join(blocks.values())
    empty = _frame_bytes({"ok": True, "_d": 1, "sizes": []}, b"")

    def exchange() -> tuple[bytes, bytes, bytes]:
        with socket.socket() as sock:
            # A small receive window: the engine blocks in its writes long
            # before it reaches the last block.
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 64 << 10)
            sock.settimeout(30)
            sock.connect(("127.0.0.1", cs.data_port))
            sock.sendall(b"".join(blocknet._pack_frame(
                {"m": "ReadBlocks", "block_ids": ids}, None)))
            head = _recv_exact(sock, 4)
            head += _recv_exact(sock, blocknet._U32.unpack(head)[0] + 8)
            for bid, path in paths.items():
                if after_header == "unlinked":
                    path.unlink()
                elif after_header == "replaced":
                    cs.store.write(bid, bytes(size))
                elif bid == ids[-1]:
                    os.truncate(path, size // 2)
            payload = _recv_exact(sock, len(want))
            if len(payload) < len(want):
                return head, payload, sock.recv(1)  # b"": closed
            # Still framed: the next request is answered.
            sock.sendall(b"".join(blocknet._pack_frame(
                {"m": "ReadBlocks", "block_ids": []}, None)))
            return head, payload, _recv_exact(sock, len(empty))

    try:
        before = cs.read_stage_stats()
        head, payload, after = await asyncio.to_thread(exchange)
        assert head == _frame_bytes(
            {"ok": True, "_d": 1, "sizes": [size] * 5}, want)[:len(head)]
        moved = _moved(before, cs.read_stage_stats())
        if after_header == "truncated":
            # The four whole blocks, then the close: not one byte of the
            # block whose read came up short.
            assert payload == want[:4 * size] and after == b""
            assert moved["rbs_torn"] == 1
            assert moved["rbs_bytes"] == 4 * size
        else:
            assert payload == want and after == empty
            assert "rbs_torn" not in moved
            assert moved["rbs_bytes"] == len(want)
            assert moved["rbs_frames"] == 2
        assert "rbs_missing" not in moved
    finally:
        await cluster.stop()


async def test_torn_read_blocks_frame_falls_back_in_the_combiner(tmp_path):
    """A block whose pread fails after the header (here its replica's path
    on the origin is a directory: it opens and sizes, and every pread of it
    fails) tears the origin's frame. The combiner's round fails as on any
    transport error and its blocks fall back to the verified per-block
    path; the bytes handed over are the file's."""
    import jax

    from tests.test_tpu import _cluster_with_files, _confirmed_bytes
    from tpudfs.tpu.hbm_reader import HbmReader

    if not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    data = _rand(8 * 64 * 1024, 27)
    c, client = await _cluster_with_files(tmp_path, [("/torn/f", data)])
    try:
        client.local_reads = False
        meta = await client.get_file_info("/torn/f")
        origin_addr = meta["blocks"][0]["locations"][0]
        origin = next(cs for cs in c.chunkservers
                      if cs.address == origin_addr)
        assert origin._native_dp is not None
        real = client.get_file_info

        async def one_origin(path):
            # Every block's first replica is the origin: one frame a round.
            m = await real(path)
            return dict(m, blocks=[
                dict(b, locations=[origin_addr] + sorted(
                    a for a in b["locations"] if a != origin_addr))
                for b in m["blocks"]])

        client.get_file_info = one_origin
        bad = meta["blocks"][5]["block_id"]
        path = origin.store.block_path(bad)
        path.unlink()
        path.mkdir()
        (path / "entry").write_bytes(b"x")  # a directory of non-zero size
        origin.invalidate_cached(bad)
        reader = HbmReader(client, jax.devices()[:1], batch_reads=2)
        before = origin.read_stage_stats()
        blocks = await reader.read_file_to_device_blocks("/torn/f",
                                                         verify="lazy")
        moved = _moved(before, origin.read_stage_stats())
        assert moved["rbs_torn"] == 1
        assert blocks[5].batch is None and blocks[4].batch is None
        assert await _confirmed_bytes(reader, blocks) == data
    finally:
        await c.stop()
