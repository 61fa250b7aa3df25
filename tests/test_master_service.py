"""Master service integration: live masters + chunkservers in-process.

Exercises the reference's end-to-end flows (SURVEY.md §3.1/§3.5): safe mode,
create→allocate→write-pipeline→complete→read-path metadata, heartbeat command
delivery, liveness-driven healing, tiering scans, leader redirects."""

import asyncio
import socket

import grpc
import numpy as np
import pytest

from tpudfs.common.checksum import crc32c
from tpudfs.common.rpc import RpcClient, RpcError, RpcServer
from tpudfs.chunkserver.blockstore import BlockStore
from tpudfs.chunkserver.heartbeat import HeartbeatLoop
from tpudfs.chunkserver.service import ChunkServer
from tpudfs.master.service import Master
from tpudfs.master.state import BlockInfo, FileMetadata
from tpudfs.raft.core import NotLeaderError, Timings

FAST_RAFT = Timings(election_min=0.3, election_max=0.6, heartbeat=0.1,
                    snapshot_threshold=200)


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class MiniCluster:
    def __init__(self, tmp_path, n_masters=1, n_cs=3, cs_kw=None,
                 hb_interval=0.5, **master_kw):
        self.tmp = tmp_path
        self.n_masters = n_masters
        self.n_cs = n_cs
        self.cs_kw = dict(cs_kw or {})
        self.hb_interval = hb_interval
        self.master_kw = master_kw
        self.masters: dict[str, Master] = {}
        self.servers: dict[str, RpcServer] = {}
        self.chunkservers: list[ChunkServer] = []
        self.heartbeats: list[HeartbeatLoop] = []
        self.client = RpcClient()

    async def start(self):
        addrs = [f"127.0.0.1:{_free_port()}" for _ in range(self.n_masters)]
        for i, addr in enumerate(addrs):
            peers = [a for a in addrs if a != addr]
            m = Master(addr, peers, str(self.tmp / f"m{i}"),
                       raft_timings=FAST_RAFT, **self.master_kw)
            server = RpcServer(port=int(addr.rsplit(":", 1)[1]))
            m.attach(server)
            await server.start()
            await m.start()
            self.masters[addr] = m
            self.servers[addr] = server
        for i in range(self.n_cs):
            store = BlockStore(self.tmp / f"cs{i}/hot", self.tmp / f"cs{i}/cold")
            cs = ChunkServer(store, rack_id=f"rack-{i}", master_addrs=addrs,
                             rpc_client=self.client, **self.cs_kw)
            await cs.start(scrubber=False)
            hb = HeartbeatLoop(cs, addrs, interval=self.hb_interval)
            hb.start()
            self.chunkservers.append(cs)
            self.heartbeats.append(hb)

    async def leader(self, timeout=10.0) -> Master:
        deadline = asyncio.get_event_loop().time() + timeout
        while asyncio.get_event_loop().time() < deadline:
            for m in self.masters.values():
                if m.raft.is_leader:
                    return m
            await asyncio.sleep(0.05)
        raise AssertionError("no master leader")

    async def wait_out_of_safe_mode(self, m: Master, timeout=10.0):
        deadline = asyncio.get_event_loop().time() + timeout
        while asyncio.get_event_loop().time() < deadline:
            if not m.state.safe_mode:
                return
            await asyncio.sleep(0.1)
        raise AssertionError("still in safe mode")

    async def call(self, addr, method, req, timeout=10.0):
        return await self.client.call(addr, "MasterService", method, req,
                                      timeout=timeout)

    async def put_file(self, path, data, leader: Master):
        """Manual client write path (the real client library lands next)."""
        addr = leader.address
        created = await self.call(addr, "CreateFile", {"path": path})
        token = created.get("write_token") or ""
        alloc = await self.call(addr, "AllocateBlock",
                                {"path": path, "token": token})
        block = alloc["block"]
        servers = alloc["chunk_server_addresses"]
        resp = await self.client.call(
            servers[0], "ChunkServerService", "WriteBlock",
            {
                "block_id": block["block_id"],
                "data": data,
                "next_servers": servers[1:],
                "expected_crc32c": crc32c(data),
                "master_term": alloc["master_term"],
            },
        )
        assert resp["success"], resp
        await self.call(addr, "CompleteFile", {
            "path": path, "size": len(data), "etag_md5": "",
            "block_checksums": [{
                "block_id": block["block_id"],
                "checksum_crc32c": crc32c(data),
                "actual_size": len(data),
            }],
            "token": token,
        })
        return block["block_id"], servers

    async def stop(self):
        for hb in self.heartbeats:
            hb.stop()
        for cs in self.chunkservers:
            await cs.stop()
        for m in self.masters.values():
            await m.stop()
        for s in self.servers.values():
            await s.stop()
        await self.client.close()


async def test_full_write_read_metadata_flow(tmp_path):
    c = MiniCluster(tmp_path, n_masters=1, n_cs=3)
    try:
        await c.start()
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        data = _rand(300_000)
        block_id, servers = await c.put_file("/docs/a.bin", data, leader)
        assert len(servers) == 3  # replication factor
        # Every CS in the pipeline holds the block.
        for cs in c.chunkservers:
            if cs.address in servers:
                assert cs.store.read(block_id) == data
        info = await c.call(leader.address, "GetFileInfo", {"path": "/docs/a.bin"})
        assert info["found"]
        meta = info["metadata"]
        assert meta["size"] == len(data)
        assert meta["blocks"][0]["block_id"] == block_id
        assert sorted(meta["blocks"][0]["locations"]) == sorted(servers)
        locs = await c.call(leader.address, "GetBlockLocations",
                            {"block_id": block_id})
        assert locs["found"] and sorted(locs["locations"]) == sorted(servers)
        ls = await c.call(leader.address, "ListFiles", {"path": "/docs/"})
        assert ls["files"] == ["/docs/a.bin"]
        # Access stats recorded via raft (fire-and-forget).
        for _ in range(40):
            if leader.state.files["/docs/a.bin"].access_count > 0:
                break
            await asyncio.sleep(0.05)
        assert leader.state.files["/docs/a.bin"].access_count > 0
    finally:
        await c.stop()


async def test_safe_mode_blocks_writes(tmp_path):
    c = MiniCluster(tmp_path, n_masters=1, n_cs=1)
    try:
        await c.start()
        leader = await c.leader()
        # Pause heartbeats so one can't re-register the CS (and exit safe
        # mode, total blocks being 0) between enter_safe_mode and the call;
        # the sleep lets any already-received Heartbeat handler finish.
        for hb in c.heartbeats:
            hb.stop()
        await asyncio.sleep(0.2)
        leader.state.enter_safe_mode()
        leader.state.chunk_servers.clear()  # force: no CS registered
        with pytest.raises(RpcError) as ei:
            await c.call(leader.address, "CreateFile", {"path": "/x"})
        assert "safe mode" in ei.value.message.lower()
        # CS heartbeats bring it out (total blocks 0 → exit on first report).
        for hb in c.heartbeats:
            hb.start()
        await c.wait_out_of_safe_mode(leader)
        await c.call(leader.address, "CreateFile", {"path": "/x"})
    finally:
        await c.stop()


async def test_allocate_errors(tmp_path):
    c = MiniCluster(tmp_path, n_masters=1, n_cs=2)
    try:
        await c.start()
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        with pytest.raises(RpcError):  # no such file
            await c.call(leader.address, "AllocateBlock", {"path": "/nope"})
        # EC file needing 6 servers with only 2 available.
        r = await c.call(leader.address, "CreateFile",
                         {"path": "/e", "ec_data_shards": 4,
                          "ec_parity_shards": 2})
        with pytest.raises(RpcError) as ei:
            await c.call(leader.address, "AllocateBlock",
                         {"path": "/e", "token": r.get("write_token") or ""})
        assert "chunkserver" in ei.value.message.lower()
    finally:
        await c.stop()


async def test_liveness_removal_triggers_healing(tmp_path):
    c = MiniCluster(
        tmp_path, n_masters=1, n_cs=4,
        liveness_cutoff_ms=1500,
        intervals={"liveness": 0.5, "healer": 3600, "balancer": 3600,
                   "tiering": 3600},
    )
    try:
        await c.start()
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        data = _rand(50_000, 1)
        block_id, servers = await c.put_file("/f", data, leader)
        # Kill one replica-holding CS (stop server + its heartbeat).
        victim = next(cs for cs in c.chunkservers if cs.address in servers)
        c.heartbeats[c.chunkservers.index(victim)].stop()
        await victim.stop()
        # Liveness check drops it and the healer queues a REPLICATE; the
        # spare CS (not in original 3) receives the block via command flow.
        spare = next(cs for cs in c.chunkservers if cs.address not in servers)
        for _ in range(200):
            if spare.store.exists(block_id):
                break
            await asyncio.sleep(0.1)
        assert spare.store.exists(block_id)
        assert spare.store.read(block_id) == data
        # Metadata updated once the source CS acks the REPLICATE on its next
        # heartbeat (improvement over reference, which leaves it stale).
        for _ in range(100):
            locs = await c.call(leader.address, "GetBlockLocations",
                                {"block_id": block_id})
            if spare.address in locs["locations"]:
                break
            await asyncio.sleep(0.1)
        assert spare.address in locs["locations"]
    finally:
        await c.stop()


QUIET = {"liveness": 3600, "healer": 3600, "balancer": 3600, "tiering": 3600}
SRC, B, C, DST = (f"127.0.0.1:{7001 + i}" for i in range(4))

# name: (locations before, result reported by SRC, propose fails,
#        locations proposed or None, DELETE queued to SRC)
MOVE_CASES = {
    "swap": ([SRC, B, C], "REPLICATE", False, [B, C, DST], True),
    "failed_propose": ([SRC, B, C], "REPLICATE", True, [B, C, DST], False),
    "re_reported": ([B, C, DST], "REPLICATE", False, None, True),
    "would_shorten": ([SRC, B], "REPLICATE", False, [SRC, B, DST], False),
    "delete_result": ([SRC, B, C], "DELETE", False, None, False),
}


@pytest.mark.parametrize("case", list(MOVE_CASES))
async def test_balance_move_is_one_metadata_step(tmp_path, case):
    """A balancer move reported by its source: the target goes in and the
    source comes out in ONE committed entry, and the source's DELETE is
    queued only after that entry committed (never on a failed propose,
    never where dropping the source would leave the block under RF)."""
    before, rtype, fails, proposed, deletes = MOVE_CASES[case]
    c = MiniCluster(tmp_path, n_masters=1, n_cs=0, intervals=QUIET)
    try:
        await c.start()
        m = await c.leader()
        bid = "blk-move"
        m.state.files["/moved"] = FileMetadata(
            "/moved", size=1, complete=True,
            blocks=[BlockInfo(bid, size=1, locations=list(before))])
        real_propose = m.raft.propose
        seen = []  # (entry, the source's queue when it went out)

        async def propose(cmd, **kw):
            seen.append((cmd, list(m.state.pending_commands.get(SRC, []))))
            if fails:
                raise NotLeaderError(None)
            return await real_propose(cmd, **kw)

        m.raft.propose = propose
        res = {"type": rtype, "block_id": bid, "success": True}
        if rtype == "REPLICATE":
            res.update(target_chunk_server_address=DST,
                       balance_delete_source=True)
        assert await m._process_command_results(SRC, [res]) is not fails
        assert [cmd["locations"] for cmd, _ in seen] == \
            ([proposed] if proposed else [])
        assert all(queued == [] for _, queued in seen)
        assert m.state.pending_commands.get(SRC, []) == \
            ([{"type": "DELETE", "block_id": bid}] if deletes else [])
        locations = m.state.find_block(bid)[1].locations
        assert locations == (proposed if proposed and not fails else before)
    finally:
        await c.stop()


async def _read_replica(c: MiniCluster, addr: str, block_id: str):
    """One named replica's bytes, or None where it answers NOT_FOUND."""
    try:
        resp = await c.client.call(addr, "ChunkServerService", "ReadBlock",
                                   {"block_id": block_id, "offset": 0,
                                    "length": 0})
    except RpcError as e:
        if e.code != grpc.StatusCode.NOT_FOUND:
            raise
        return None
    if "data_parts" in resp:
        return b"".join(bytes(p) for p in resp["data_parts"])
    return bytes(resp["data"])


async def test_a_balance_move_never_names_a_deleted_replica(tmp_path):
    """A reader that polls the record and then every replica it names, for
    the whole of a balance move on 0.1 s heartbeats: whenever the record
    stood still across one poll, every replica it named answered with the
    block's bytes. The move ends with the target in, the source out and
    its copy gone."""
    await asyncio.wait_for(_balance_move_under_a_reader(tmp_path), 20)


async def _balance_move_under_a_reader(tmp_path):
    c = MiniCluster(tmp_path, n_masters=1, n_cs=4, hb_interval=0.1,
                    intervals=QUIET)
    try:
        await c.start()
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        data = _rand(200_000, 4)
        block_id, servers = await c.put_file("/moved", data, leader)
        source, target = servers[0], next(
            cs.address for cs in c.chunkservers if cs.address not in servers)
        source_store = next(cs.store for cs in c.chunkservers
                            if cs.address == source)

        async def named():
            info = await c.call(leader.address, "GetFileInfo",
                                {"path": "/moved"})
            return info["metadata"]["blocks"][0]["locations"]

        async def poll():
            """The record, its replicas' answers, the record again."""
            first = await named()
            answers = {a: await _read_replica(c, a, block_id) for a in first}
            return first, answers, await named()

        leader.state.queue_command(source, {
            "type": "REPLICATE", "block_id": block_id,
            "target_chunk_server_address": target,
            "balance_delete_source": True,
        })
        steady = 0
        while True:
            first, answers, last = await poll()
            if first == last:
                steady += 1
                assert all(got == data for got in answers.values()), \
                    (first, {a: got is not None for a, got in answers.items()})
            if source not in last and not source_store.exists(block_id):
                break
            await asyncio.sleep(0.005)
        # The source's report of its DELETE comes on its next heartbeat and
        # must leave the record as it is.
        for _ in range(8):
            first, answers, last = await poll()
            assert first == last
            assert all(got == data for got in answers.values())
            await asyncio.sleep(0.05)
        assert steady > 0
        assert sorted(last) == sorted(servers[1:] + [target])
    finally:
        await c.stop()


async def test_tiering_scan_moves_cold_and_converts_ec(tmp_path):
    c = MiniCluster(
        tmp_path, n_masters=1, n_cs=3,
        cold_threshold_secs=1,
        ec_threshold_secs=1,
        intervals={"liveness": 3600, "healer": 3600, "balancer": 3600,
                   "tiering": 0.5},
    )
    try:
        await c.start()
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        data = _rand(10_000, 2)
        block_id, servers = await c.put_file("/cold-file", data, leader)
        # After ~1s the tiering scan proposes move_to_cold; CSes execute
        # MOVE_TO_COLD via heartbeat; later the EC policy conversion fires.
        holder = next(cs for cs in c.chunkservers if cs.address in servers)
        for _ in range(200):
            if holder.store.is_cold(block_id):
                break
            await asyncio.sleep(0.1)
        assert holder.store.is_cold(block_id)
        f = leader.state.files["/cold-file"]
        assert f.moved_to_cold_at_ms > 0
        for _ in range(100):
            if leader.state.files["/cold-file"].ec_data_shards == 6:
                break
            await asyncio.sleep(0.1)
        assert leader.state.files["/cold-file"].ec_data_shards == 6
        assert leader.state.files["/cold-file"].ec_parity_shards == 3
        # Data still readable from cold tier.
        assert holder.store.read(block_id) == data
    finally:
        await c.stop()


async def test_ha_masters_follower_redirect_and_failover(tmp_path):
    c = MiniCluster(tmp_path, n_masters=3, n_cs=3)
    try:
        await c.start()
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        follower = next(m for m in c.masters.values() if not m.raft.is_leader)
        with pytest.raises(RpcError) as ei:
            await c.call(follower.address, "CreateFile", {"path": "/x"})
        assert ei.value.is_not_leader
        assert ei.value.not_leader_hint == leader.address
        # Write through the leader, then fail it over.
        data = _rand(20_000, 3)
        await c.put_file("/ha-file", data, leader)
        await leader.stop()
        await c.servers[leader.address].stop()
        old = leader.address
        del c.masters[old]
        new_leader = await c.leader(timeout=15.0)
        assert new_leader.address != old
        # Metadata survived the failover.
        info = await c.call(new_leader.address, "GetFileInfo",
                            {"path": "/ha-file"})
        assert info["found"] and info["metadata"]["size"] == len(data)
    finally:
        await c.stop()


async def test_concurrent_put_sessions_cannot_interleave(tmp_path):
    """Write-session fencing (found by the live chaos tier): two clients
    racing put sessions on one path — the second CreateFile replaces the
    first writer's in-flight file, and the FIRST writer's AllocateBlock /
    CompleteFile must then be rejected as a stale session. Without the
    fence both sessions' blocks grafted onto one file (metadata size from
    one writer, block list from both) and reads returned a torn value no
    client ever wrote."""
    c = MiniCluster(tmp_path, n_masters=1, n_cs=3)
    await c.start()
    try:
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        m = leader.address
        cl = c.client

        r1 = await cl.call(m, "MasterService", "CreateFile",
                           {"path": "/race", "first_block": True})
        t1 = r1["write_token"]
        assert t1 and r1.get("block"), r1
        # Second writer races in before the first completes: replaces the
        # in-flight file with its own session.
        r2 = await cl.call(m, "MasterService", "CreateFile",
                           {"path": "/race", "first_block": True})
        t2 = r2["write_token"]
        assert t2 and t2 != t1

        # The FIRST session is now fenced off everywhere.
        with pytest.raises(RpcError, match="stale write session"):
            await cl.call(m, "MasterService", "AllocateBlock",
                          {"path": "/race", "token": t1})
        with pytest.raises(RpcError, match="stale write session"):
            await cl.call(m, "MasterService", "CompleteFile",
                          {"path": "/race", "size": 4, "etag_md5": "x",
                           "block_checksums": [], "token": t1})

        # The second session proceeds normally and owns the file alone.
        b2 = r2["block"]
        data = b"winner"
        await cl.call(b2["locations"][0], "ChunkServerService", "WriteBlock",
                      {"block_id": b2["block_id"], "data": data,
                       "next_servers": b2["locations"][1:],
                       "expected_crc32c": crc32c(data),
                       "master_term": int(r2.get("master_term") or 0)})
        await cl.call(m, "MasterService", "CompleteFile",
                      {"path": "/race", "size": len(data), "etag_md5": "e",
                       "block_checksums": [
                           {"block_id": b2["block_id"],
                            "checksum_crc32c": crc32c(data),
                            "actual_size": len(data)}],
                       "token": t2})
        info = await cl.call(m, "MasterService", "GetFileInfo",
                             {"path": "/race"})
        meta = info["metadata"]
        assert info["found"] and meta["size"] == len(data)
        assert len(meta["blocks"]) == 1  # never both sessions' blocks
        assert meta["blocks"][0]["block_id"] == b2["block_id"]
    finally:
        await c.stop()
