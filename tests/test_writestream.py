"""Streaming write engine: frame protocol, abort paths, and interop.

Covers the WriteStream protocol edges the end-to-end suites only exercise
on the happy path: torn mid-frame connections (both directions),
group-commit watermark MAX-merge under reordered acks, a CRC mismatch on
frame N quarantining the staged block, mid-stream deadline-budget expiry,
tenant headers riding native hops, and blockport<->native interop on
mixed chains (the shared frame protocol is the fallback contract).
"""

from __future__ import annotations

import asyncio

import pytest

from tests.test_chunkserver import Cluster, _rand
from tpudfs.common import native, writestream
from tpudfs.common.blocknet import BlockConnPool, _pack_frame, _read_frame
from tpudfs.common.checksum import crc32c
from tpudfs.common.rpc import RpcError
from tpudfs.chunkserver.service import SERVICE


@pytest.fixture
def cluster():
    return Cluster()


def _frames(data: bytes, frame_size: int = writestream.FRAME_SIZE):
    mv = memoryview(data)
    for seq in range(writestream.frame_count(len(data), frame_size)):
        chunk = mv[seq * frame_size:(seq + 1) * frame_size]
        yield seq, bytes(chunk)


async def _begin_stream(port: int, begin: dict):
    """Dial a blockport, send the begin frame, and consume the ready ack."""
    r, w = await asyncio.open_connection("127.0.0.1", port)
    w.writelines(_pack_frame(dict(begin), None))
    await w.drain()
    header, _ = await _read_frame(r)
    return r, w, header


async def _wait_no_tmp(hot_dir, timeout: float = 5.0):
    """Staged tmps are unlinked asynchronously after an abort; poll."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if not list(hot_dir.glob("*.tmp-*")):
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"staged tmp leaked: {list(hot_dir.glob('*.tmp-*'))}")


async def _wait_aborts(cs, n: int, timeout: float = 5.0):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cs.stream_stage_stats()["aborts"] >= n:
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"abort count stuck at "
                         f"{cs.stream_stage_stats()['aborts']}, wanted {n}")


def test_frame_count_edges():
    fs = writestream.FRAME_SIZE
    assert writestream.frame_count(0) == 1
    assert writestream.frame_count(1) == 1
    assert writestream.frame_count(fs) == 1
    assert writestream.frame_count(fs + 1) == 2
    assert writestream.frame_count(3 * fs - 1) == 3
    assert writestream.frame_count(3 * fs) == 3


async def test_watermark_max_merge_under_reordered_acks():
    """Receivers MAX-merge watermark acks, so a stale (reordered) ack can
    never regress the client's view of durable progress."""
    data = _rand(writestream.FRAME_SIZE * 3 + 17, 41)
    nframes = writestream.frame_count(len(data))
    served = asyncio.Event()

    async def serve(r, w):
        await _read_frame(r)  # begin
        w.writelines(_pack_frame({"ok": True, "ready": 1}, None))
        await w.drain()
        for _ in range(nframes):
            await _read_frame(r)
        # Deliberately reordered: a high watermark, then a stale lower
        # one, then a final WITHOUT "w" — the client's reported watermark
        # must be max over the acks (nframes), not the last one seen (1).
        for ack in ({"ok": True, "w": nframes}, {"ok": True, "w": 1},
                    {"ok": True, "final": 1, "success": True,
                     "error_message": "", "replicas_written": 1}):
            w.writelines(_pack_frame(dict(ack), None))
        await w.drain()
        served.set()
        # Python 3.12's Server.wait_closed() waits for every connection's
        # transport, so the handler has to close its side.
        w.close()

    server = await asyncio.start_server(serve, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    r, w = await asyncio.open_connection("127.0.0.1", port)
    begin = writestream.begin_header(
        "wm", len(data), expected_crc32c=crc32c(data), master_term=0,
        master_shard="", next_servers=[], next_data_ports=[])
    final = await writestream.send_block_stream(r, w, begin, data)
    assert final["_watermark"] == nframes
    assert final["success"]
    await served.wait()
    w.close()
    server.close()
    await server.wait_closed()


async def test_client_sees_torn_stream_mid_frame():
    """The server dying mid-stream surfaces as a connection-level error,
    never as a silent short write."""

    async def serve(r, w):
        await _read_frame(r)
        w.writelines(_pack_frame({"ok": True, "ready": 1}, None))
        await w.drain()
        await _read_frame(r)  # one frame, then die
        w.transport.abort()

    server = await asyncio.start_server(serve, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    r, w = await asyncio.open_connection("127.0.0.1", port)
    data = _rand(writestream.FRAME_SIZE * 8, 42)
    begin = writestream.begin_header(
        "torn", len(data), expected_crc32c=crc32c(data), master_term=0,
        master_shard="", next_servers=[], next_data_ports=[])
    with pytest.raises((ConnectionError, RpcError)):
        await writestream.send_block_stream(r, w, begin, data)
    w.close()
    server.close()
    await server.wait_closed()


@pytest.mark.parametrize("native_hop", [False, True])
async def test_server_discards_staged_block_on_torn_connection(
        cluster, tmp_path, native_hop):
    """Killing the sender mid-frame must leave no staged tmp behind and
    never publish a torn block."""
    if native_hop and not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0,
                              python_data_plane=not native_hop)
    data = _rand(writestream.FRAME_SIZE * 4, 43)
    begin = writestream.begin_header(
        "torn-srv", len(data), expected_crc32c=crc32c(data), master_term=0,
        master_shard="", next_servers=[], next_data_ports=[])
    r, w, ready = await _begin_stream(cs.data_port, begin)
    assert ready.get("ready") == 1, ready
    frames = list(_frames(data))
    seq0, p0 = frames[0]
    w.writelines(_pack_frame({"q": seq0, "c": crc32c(p0)}, p0))
    # Half of frame 1 — header plus a truncated payload — then EOF.
    _, p1 = frames[1]
    parts = _pack_frame({"q": 1, "c": crc32c(p1)}, p1)
    w.write(b"".join(bytes(p) for p in parts)[:len(p1) // 2])
    await w.drain()
    w.close()
    await _wait_aborts(cs, 1)
    await _wait_no_tmp(tmp_path / "cs0/hot")
    assert not cs.store.exists("torn-srv")
    await cluster.stop()


@pytest.mark.parametrize("native_hop", [False, True])
async def test_crc_mismatch_on_frame_quarantines_staged_block(
        cluster, tmp_path, native_hop):
    """A corrupt frame N aborts the stream with DATA_LOSS, unlinks the
    staged tmps, and tears the connection (pipelined frames are unread)."""
    if native_hop and not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0,
                              python_data_plane=not native_hop)
    data = _rand(writestream.FRAME_SIZE * 3, 44)
    begin = writestream.begin_header(
        "crcq", len(data), expected_crc32c=crc32c(data), master_term=0,
        master_shard="", next_servers=[], next_data_ports=[])
    r, w, ready = await _begin_stream(cs.data_port, begin)
    assert ready.get("ready") == 1, ready
    # Send only frames 0 and 1 (1 corrupted): the server aborts at 1, so
    # nothing unread is left behind to turn its close into an RST that
    # could destroy the error frame in flight.
    for seq, payload in list(_frames(data))[:2]:
        crc = crc32c(payload) if seq != 1 else crc32c(payload) ^ 0xBAD
        w.writelines(_pack_frame({"q": seq, "c": crc}, payload))
    await w.drain()
    err, _ = await _read_frame(r)
    assert err.get("ok") is False, err
    assert err.get("code") == "DATA_LOSS", err
    assert "quarantined" in err.get("message", ""), err
    # The stream handler closes the connection after the abort.
    assert await r.read(1) == b""
    w.close()
    await _wait_no_tmp(tmp_path / "cs0/hot")
    assert not cs.store.exists("crcq")
    assert cs.stream_stage_stats()["aborts"] == 1
    await cluster.stop()


@pytest.mark.parametrize("native_hop", [False, True])
async def test_mid_stream_deadline_expiry_aborts_chain(
        cluster, tmp_path, native_hop):
    """A `_db` budget that expires after the ready ack aborts the stream
    with DEADLINE_EXCEEDED on both engines (the QoS contract: deadline
    budgets are honored on streamed frames, not just unary calls)."""
    if native_hop and not native.has_dataplane():
        pytest.skip("native dataplane unavailable")
    await cluster.start_master()
    cs = await cluster.add_cs(tmp_path, 0,
                              python_data_plane=not native_hop)
    data = _rand(writestream.FRAME_SIZE * 3, 45)
    begin = writestream.begin_header(
        "dl", len(data), expected_crc32c=crc32c(data), master_term=0,
        master_shard="", next_servers=[], next_data_ports=[])
    # Positive at begin-parse time (so it passes pre-execution admission
    # and the ready ack goes out) but certainly expired by the frame-0
    # budget check: staging the block file alone takes longer than 1 us.
    begin["_db"] = 1e-6
    r, w, ready = await _begin_stream(cs.data_port, begin)
    assert ready.get("ready") == 1, ready
    # Send NO frames: the deadline check runs before the frame read, and
    # with nothing unread at the server its close delivers the error
    # frame cleanly instead of racing an RST.
    err, _ = await _read_frame(r)
    assert err.get("ok") is False, err
    assert err.get("code") == "DEADLINE_EXCEEDED", err
    w.close()
    await _wait_no_tmp(tmp_path / "cs0/hot")
    assert not cs.store.exists("dl")
    assert cs.stream_stage_stats()["aborts"] == 1
    await cluster.stop()


@pytest.mark.skipif(not native.has_dataplane(),
                    reason="native dataplane unavailable")
async def test_mixed_chain_interop_both_directions(cluster, tmp_path):
    """blockport<->native interop: the shared frame protocol must stream
    through an asyncio hop relaying to a native hop AND a native hop
    relaying to an asyncio hop, full replication both ways."""
    await cluster.start_master()
    cs_py = await cluster.add_cs(tmp_path, 0, python_data_plane=True)
    cs_nat = await cluster.add_cs(tmp_path, 1)
    assert cs_nat._native_dp is not None
    pool = BlockConnPool()
    data = _rand(writestream.FRAME_SIZE * 3 + 999, 46)
    for bid, chain in (("py-first", [cs_py, cs_nat]),
                       ("nat-first", [cs_nat, cs_py])):
        addrs = [s.address for s in chain]
        ports, safe = await pool.chain_info(cluster.client, addrs, SERVICE)
        assert safe and all(ports), (ports, safe)
        assert pool.stream_chain_ok(addrs)
        begin = writestream.begin_header(
            bid, len(data), expected_crc32c=crc32c(data), master_term=0,
            master_shard="", next_servers=addrs[1:],
            next_data_ports=ports[1:])
        resp = await pool.write_stream(cluster.client, addrs[0], SERVICE,
                                       begin, data)
        assert resp is not None and resp["success"], (bid, resp)
        assert resp["replicas_written"] == 2, (bid, resp)
        for s in chain:
            assert s.store.read_verified(bid) == data, (bid, s.address)
    await pool.close()
    await cluster.stop()


@pytest.mark.skipif(not native.has_dataplane(),
                    reason="native dataplane unavailable")
async def test_native_hop_forwards_tenant_and_budget(cluster, tmp_path):
    """A native first hop must pass `_tn` (and `_db`) through to its
    downstream — a QoS'd asyncio tail still sees the tenant for
    admission/accounting on relayed stream frames."""
    await cluster.start_master()
    cs_nat = await cluster.add_cs(tmp_path, 0)
    cs_py = await cluster.add_cs(tmp_path, 1, python_data_plane=True)
    assert cs_nat._native_dp is not None

    seen = []

    class RecordingShedder:
        async def acquire(self, tenant):
            seen.append(tenant)

        def release(self, tenant, elapsed=0.0):
            pass

    cs_py.shedder = RecordingShedder()
    pool = BlockConnPool()
    addrs = [cs_nat.address, cs_py.address]
    ports, safe = await pool.chain_info(cluster.client, addrs, SERVICE)
    assert safe and all(ports)
    data = _rand(writestream.FRAME_SIZE * 2 + 5, 47)
    begin = writestream.begin_header(
        "tn-fwd", len(data), expected_crc32c=crc32c(data), master_term=0,
        master_shard="", next_servers=addrs[1:], next_data_ports=ports[1:])
    begin["_tn"] = "tenant-x"
    begin["_db"] = 30.0
    r, w, ready = await _begin_stream(cs_nat.data_port, begin)
    assert ready.get("ready") == 1, ready
    for seq, payload in _frames(data):
        w.writelines(_pack_frame({"q": seq, "c": crc32c(payload)}, payload))
    await w.drain()
    while True:
        ack, _ = await _read_frame(r)
        assert ack.get("ok"), ack
        if ack.get("final"):
            break
    assert ack["success"] and ack["replicas_written"] == 2, ack
    assert seen == ["tenant-x"], seen
    assert cs_py.store.read_verified("tn-fwd") == data
    w.close()
    await pool.close()
    await cluster.stop()
