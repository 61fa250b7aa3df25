"""Degraded erasure-coded blocks on the read combiner's fused rounds: nine
in-process chunkservers with two stopped (the deployment of
``ec-3m9cs-rs63``), or the combiner alone over shards kept in memory. What
a round asks of whom, what it hands back, what sends a block to the
per-block fallback, and that nothing compiles after ``warm_ec``."""

import asyncio
import itertools
from collections import Counter

import grpc
import jax
import numpy as np
import pytest

from tests.test_master_service import MiniCluster
from tests.test_tpu import _confirmed_bytes, _rand, _stop_holders
from tpudfs.client.client import Client, DfsError
from tpudfs.common import telemetry
from tpudfs.common.checksum import crc32c
from tpudfs.common.erasure import encode
from tpudfs.common.resilience import BreakerBoard
from tpudfs.common.rpc import RpcError
from tpudfs.master import placement
from tpudfs.tpu import read_combiner
from tpudfs.tpu.hbm_reader import HbmReader, device_array_to_bytes
from tpudfs.tpu.read_combiner import ReadCombiner, ec_survivors, may_fuse

K, M = 6, 3
BLOCK = 64 * 1024
SLEN = -(-BLOCK // K)


# ------------------------------------------------------------ the cluster


async def _nine(tmp_path, monkeypatch, files, rotate=False):
    """Nine chunkservers and ``files`` written RS(6,3), every block's slots
    dealt to the servers in one order (the master's own order follows the
    free space the heartbeats last reported). ``rotate``: block i of the
    cluster's life has its slots dealt i places on, so two dead servers
    cost different blocks different slots."""
    dealt = itertools.count()

    def in_order(servers, n):
        picked = sorted(addr for addr, _status in servers)[:n]
        by = next(dealt) % len(picked) if rotate and picked else 0
        return picked[by:] + picked[:by]

    monkeypatch.setattr(placement, "select_servers_rack_aware", in_order)
    c = MiniCluster(tmp_path, n_masters=1, n_cs=9)
    await c.start()
    await c.wait_out_of_safe_mode(await c.leader())
    client = Client(list(c.masters), rpc_client=c.client, block_size=BLOCK,
                    local_reads=False)
    for path, data in files:
        await client.create_file(path, data, ec=(K, M))
    return c, client


async def _two_down(c, client, addrs):
    """Stop the holders ``addrs`` and let the client's blockport breakers
    know, for longer than a test runs (one failed dial opens a breaker for
    5 s; the benchmark's set-up read is what tells them on the chip)."""
    await _stop_holders(c, addrs)
    board = client.block_pool.breakers
    for addr in addrs:
        board.record_failure(addr)
        board.get(addr)._open_until += 3600
    assert all(board.is_open(a) for a in addrs)


def _count_calls(client):
    """(holder, method) of every data call the client makes from now on."""
    calls: list = []
    real = client._data_call

    async def counted(addr, method, req, **kw):
        calls.append((addr, method))
        return await real(addr, method, req, **kw)

    client._data_call = counted
    return calls


def _server(c, addr):
    return next(cs for cs in c.chunkservers if cs.address == addr)


def _rot_in_place(cs, block_id, sidecar_too=False):
    """Flip a byte of ``cs``'s shard. With the sidecar left alone the
    verified ``ReadBlock`` refuses the shard and the unverified
    ``ReadBlocks`` serves it; rewritten through the store both serve it."""
    if sidecar_too:
        raw = bytearray(cs.store.read(block_id))
        raw[10] ^= 0xFF
        cs.store.write(block_id, bytes(raw))
    else:
        p = cs.store.block_path(block_id)
        raw = bytearray(p.read_bytes())
        raw[10] ^= 0xFF
        p.write_bytes(bytes(raw))
    cs.invalidate_cached(block_id)


def _lost(block, dead):
    return tuple(i for i, a in enumerate(block["locations"]) if a in dead)


# ------------------------------------------------- one frame a holder a round


async def test_round_costs_each_holder_one_frame(tmp_path, monkeypatch):
    """A 16-block degraded file is ONE round: each live holder whose shard
    the round uses answers one ReadBlocks frame, nobody a ReadBlock; the
    counters and the spans say a round ran."""
    data = _rand(16 * BLOCK, seed=101)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/one", data)])
    try:
        meta = await client.get_file_info("/ec/one")
        dead = meta["blocks"][0]["locations"][:2]
        await _two_down(c, client, dead)
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        comb = reader._combiner(reader.devices[0])
        calls = _count_calls(client)
        telemetry.enable()
        try:
            blocks = await reader.read_file_to_device_blocks(
                "/ec/one", verify="lazy")
            assert await _confirmed_bytes(reader, blocks) == data
        finally:
            telemetry.disable()
            records = telemetry.drain()
        assert {m for _a, m in calls} == {"ReadBlocks"}
        per_holder = Counter(a for a, _m in calls)
        assert len(per_holder) == K and set(per_holder.values()) == {1}
        assert not set(per_holder) & set(dead)
        assert all(b.batch is not None for b in blocks)
        assert reader.ec_rounds == 1 and reader.ec_round_blocks == 16
        assert reader.ec_blocks == reader.ec_degraded_blocks == 16
        assert reader.ec_missing_data_shards == 32
        assert reader.ec_shard_bytes == 16 * K * SLEN
        # The replicated rounds' counters are theirs alone.
        assert comb.rounds == comb.blocks == 0
        by_name: dict = {}
        for r in records:
            by_name.setdefault(r.name, []).append(r.attrs)
        assert "ec.queued" not in by_name and "combiner.fetch" not in by_name
        assert len(by_name["combiner.queued"]) == 16
        assert by_name["ec.fetch_shards"] == [{
            "round": 1, "blocks": 16, "in_flight": 1, "holders": K,
            "bytes": 16 * K * SLEN, "fell_back": 0}]
        for name in ("ec.assemble", "ec.device_put"):
            assert [(a["round"], a["blocks"], a["degraded"])
                    for a in by_name[name]] == [(1, 16, True)]
        assert [a["round"] for a in by_name["ec.decode_dispatch"]] == [1] * 16
        assert sorted(a["block"] for a in by_name["ec.decode_dispatch"]) \
            == sorted(b["block_id"] for b in meta["blocks"])
    finally:
        await c.stop()


async def test_rounds_in_flight_stay_under_their_cap(tmp_path, monkeypatch):
    """Four rounds' worth of blocks staged at once: never more than
    ``EC_ROUNDS_IN_FLIGHT`` fetches are open, and every holder's frames
    number the rounds, not the blocks."""
    data = _rand(16 * BLOCK, seed=102)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/cap", data)])
    try:
        meta = await client.get_file_info("/ec/cap")
        await _two_down(c, client, meta["blocks"][0]["locations"][:2])
        reader = HbmReader(client, jax.devices()[:1], batch_reads=4)
        comb = reader._combiner(reader.devices[0])
        real = comb._fetch_ec
        open_now = most = 0

        async def watched(reqs, buf, fetched):
            nonlocal open_now, most
            open_now += 1
            most = max(most, open_now)
            try:
                await asyncio.sleep(0.05)
                return await real(reqs, buf, fetched)
            finally:
                open_now -= 1

        comb._fetch_ec = watched
        calls = _count_calls(client)
        blocks = await reader.read_file_to_device_blocks("/ec/cap",
                                                         verify="lazy")
        assert await _confirmed_bytes(reader, blocks) == data
        assert most == read_combiner.EC_ROUNDS_IN_FLIGHT
        assert (reader.ec_rounds, reader.ec_round_blocks) == (4, 16), \
            Counter(calls)
        assert set(Counter(calls).values()) == {4}
    finally:
        await c.stop()


# ------------------------------------------------ the combiner over memory


class _Shelf:
    """Stands in for the client under a ``ReadCombiner``: every holder
    serves, from memory, the shards put on it (``ReadBlocks`` answered the
    gRPC way, as one ``data``), and the breakers know who is dead."""

    def __init__(self, dead=()):
        self.shards: dict = {}  # (holder, block id) -> bytes
        self.frames: list = []
        self.block_pool = self
        self.breakers = BreakerBoard()
        for addr in dead:
            for _ in range(3):
                self.breakers.record_failure(addr)

    def put(self, block_id, data, locations):
        for addr, shard in zip(locations, encode(data, K, M)):
            self.shards[addr, block_id] = shard
        return {"block_id": block_id, "size": len(data),
                "original_size": len(data), "checksum_crc32c": crc32c(data),
                "ec_data_shards": K, "ec_parity_shards": M,
                "locations": list(locations)}

    async def _data_call(self, addr, method, req, **_kw):
        assert method == "ReadBlocks" and not self.breakers.is_open(addr)
        self.frames.append((addr, len(req["block_ids"])))
        got = [self.shards.get((addr, b)) for b in req["block_ids"]]
        return {"sizes": [-1 if s is None else len(s) for s in got],
                "data": b"".join(s for s in got if s is not None)}


@pytest.mark.parametrize("size", [BLOCK, 12 * 1024],
                         ids=["shards-unaligned", "shards-word-aligned"])
async def test_rounds_of_every_two_slot_loss_are_bit_exact(size):
    """One block for every loss of two slots of RS(6,3), staged together:
    the 33 that lost a data shard ride rounds whose blocks have different
    survivor sets (one decode program, the inverse an operand), each asks
    its k lowest live slots and reads back bit for bit with its CRC right;
    the 3 that lost parity only are not a round's."""
    shelf = _Shelf(dead=("dead0", "dead1"))
    live = [f"h{i}" for i in range(K + M - 2)]
    blocks, datas = [], []
    for n, (a, b) in enumerate(itertools.combinations(range(K + M), 2)):
        spare = iter(live)
        locations = ["dead0" if s == a else "dead1" if s == b
                     else next(spare) for s in range(K + M)]
        datas.append(_rand(size, seed=1000 + n))
        blocks.append(shelf.put(f"b{n}", datas[-1], locations))
    comb = ReadCombiner(shelf, jax.devices()[0], max_batch=16)
    got = await asyncio.gather(*(comb.read(b) for b in blocks))
    sets = set()
    for block, data, db in zip(blocks, datas, got):
        lost = _lost(block, ("dead0", "dead1"))
        if min(lost) >= K:
            assert db is None and not may_fuse(block, shelf.breakers)
            continue
        use = ec_survivors(block, shelf.breakers)
        assert use == tuple(s for s in range(K + M) if s not in lost)[:K]
        sets.add(use)
        assert db.batch is not None and db.source is block
        assert device_array_to_bytes(db.array, db.size) == data
        assert int(np.asarray(db.batch.crcs)[db.batch_index]) \
            == db.expected_crc == block["checksum_crc32c"]
    assert len(sets) > 10
    assert comb.ec_round_blocks == 33 and comb.ec_rounds == 3
    assert comb.ec_shard_bytes == 33 * K * -(-size // K)
    assert comb.ec_missing_data_shards == sum(
        sum(s < K for s in _lost(b, ("dead0", "dead1"))) for b in blocks)
    # 16 + 16 + 1 blocks: a holder answers one frame a round at most.
    assert max(Counter(a for a, _n in shelf.frames).values()) <= 3


class _Known:
    def __init__(self, dead):
        self.dead = dead

    def is_open(self, addr):
        return addr in self.dead


_EC = {"size": 4096, "original_size": 4096, "checksum_crc32c": 7,
       "ec_data_shards": 4, "ec_parity_shards": 2,
       "locations": ["a", "b", "c", "d", "e", "f"]}


@pytest.mark.parametrize("block, dead, use", [
    (_EC, "b", (0, 2, 3, 4)),
    (_EC, "ad", (1, 2, 4, 5)),
    (_EC, "", None),
    (_EC, "ef", None),
    (_EC, "abc", None),
    (dict(_EC, locations=["a", "", "c", "d", "e", "f"]), "", (0, 2, 3, 4)),
    (dict(_EC, checksum_crc32c=0), "b", None),
    (dict(_EC, original_size=4096 + 100), "b", None),
    (dict(_EC, original_size=0, size=0), "b", None),
], ids=["one-data-lost", "two-data-lost", "healthy", "parity-only-lost",
        "too-few-left", "slot-without-holder", "no-crc", "unaligned-tail",
        "empty"])
def test_may_fuse_ec_clause(block, dead, use):
    """An erasure-coded block rides a round when it is checksummed,
    chunk-aligned and DEGRADED as far as the breakers know, with k holders
    left; never without breakers to ask (the sweep)."""
    known = _Known(set(dead))
    assert may_fuse(block, known) is (use is not None)
    assert may_fuse(block) is False
    if block.get("checksum_crc32c") and block["original_size"] % 512 == 0 \
            and block["original_size"]:
        assert ec_survivors(block, known) == use


# ------------------------------------------- what a file's blocks may look like


async def test_file_with_a_short_unaligned_last_block(tmp_path, monkeypatch):
    """The full blocks ride a round; the last one, 1 000 bytes short of a
    chunk boundary, cannot be folded on the device as it is and reads per
    block, verified on the host."""
    data = _rand(4 * BLOCK + 5 * 512 - 1000, seed=103)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/tail", data)])
    try:
        meta = await client.get_file_info("/ec/tail")
        await _two_down(c, client, meta["blocks"][0]["locations"][:2])
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        blocks = await reader.read_file_to_device_blocks("/ec/tail",
                                                         verify="lazy")
        assert [b.batch is not None for b in blocks] == [True] * 4 + [False]
        assert blocks[-1].verified and blocks[-1].size == 5 * 512 - 1000
        assert await _confirmed_bytes(reader, blocks) == data
        assert reader.ec_round_blocks == 4 and reader.ec_blocks == 5
        assert reader.ec_degraded_blocks == 5
    finally:
        await c.stop()


async def test_file_with_degraded_and_healthy_blocks(tmp_path, monkeypatch):
    """Slots dealt differently block by block: blocks that lost a data
    shard ride rounds, each with its own survivor set; blocks that lost
    parity only need no decode and read per block (host concatenation)."""
    data = _rand(18 * BLOCK, seed=104)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/mix", data)],
                            rotate=True)
    try:
        meta = await client.get_file_info("/ec/mix")
        dead = meta["blocks"][0]["locations"][:2]
        await _two_down(c, client, dead)
        lost = [_lost(b, dead) for b in meta["blocks"]]
        degraded = [min(slots) < K for slots in lost]
        assert 2 <= degraded.count(False) <= 6 and len(set(lost)) >= 6
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        calls = _count_calls(client)
        blocks = await reader.read_file_to_device_blocks("/ec/mix",
                                                         verify="lazy")
        assert [b.batch is not None for b in blocks] == degraded
        assert await _confirmed_bytes(reader, blocks) == data
        assert reader.ec_round_blocks == degraded.count(True)
        assert reader.ec_blocks == 18
        assert reader.ec_degraded_blocks == degraded.count(True)
        assert reader.ec_missing_data_shards == sum(
            sum(s < K for s in slots) for slots in lost)
        healthy = degraded.count(False)
        assert sum(m == "ReadBlock" for _a, m in calls) == healthy * (K + M - 2)
        # 14 blocks or so in rounds of 8 + 4 + 2 at most: a few frames a
        # holder, not one a block.
        frames = Counter(a for a, m in calls if m == "ReadBlocks")
        assert max(frames.values()) <= reader.ec_rounds <= 4
    finally:
        await c.stop()


async def test_batch_reads_off_reads_per_block_as_before(tmp_path,
                                                         monkeypatch):
    data = _rand(4 * BLOCK, seed=105)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/off", data)])
    try:
        meta = await client.get_file_info("/ec/off")
        await _two_down(c, client, meta["blocks"][0]["locations"][:2])
        reader = HbmReader(client, jax.devices()[:1])
        calls = _count_calls(client)
        blocks = await reader.read_file_to_device_blocks("/ec/off",
                                                         verify="lazy")
        assert all(b.batch is None and b.pending_crc is not None
                   for b in blocks)
        assert await _confirmed_bytes(reader, blocks) == data
        assert Counter(m for _a, m in calls) == {"ReadBlock": 4 * (K + M - 2)}
        assert reader.ec_rounds == reader.ec_round_blocks == 0
        assert reader.ec_blocks == reader.ec_degraded_blocks == 4
        assert not reader._combiners
    finally:
        await c.stop()


async def test_eager_verify_reads_per_block(tmp_path, monkeypatch):
    """Rounds form under lazy verification only, as for replicated
    blocks."""
    data = _rand(2 * BLOCK, seed=106)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/eager", data)])
    try:
        meta = await client.get_file_info("/ec/eager")
        await _two_down(c, client, meta["blocks"][0]["locations"][:2])
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        blocks = await reader.read_file_to_device_blocks("/ec/eager",
                                                         verify=True)
        assert all(b.verified and b.batch is None for b in blocks)
        assert reader.ec_round_blocks == 0 and reader.ec_blocks == 2
    finally:
        await c.stop()


# --------------------------------------------------------- rot, gaps, deaths


@pytest.mark.parametrize("everywhere", [False, True],
                         ids=["sidecar-knows", "sidecar-agrees"])
async def test_rotted_survivor_fails_the_device_crc_at_confirm(
        tmp_path, monkeypatch, everywhere):
    """``ReadBlocks`` is unverified on the server: a rotted shard a round
    uses reaches the device, and the CRC32C of the RECONSTRUCTED bytes
    fails there, at ``confirm``. The re-read goes per block through the
    verified ``ReadBlock``: where the server's sidecar knows of the rot it
    refuses the shard, another survivor set decodes the block and it comes
    back right; where the sidecar agrees with the rot nothing can, and
    ``confirm`` raises rather than hand the block over."""
    data = _rand(4 * BLOCK, seed=107)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/rot", data)])
    try:
        meta = await client.get_file_info("/ec/rot")
        block = meta["blocks"][1]
        await _two_down(c, client, block["locations"][:2])
        _rot_in_place(_server(c, block["locations"][3]), block["block_id"],
                      sidecar_too=everywhere)
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        calls = _count_calls(client)
        blocks = await reader.read_file_to_device_blocks("/ec/rot",
                                                         verify="lazy")
        assert all(b.batch_pending and not b.verified for b in blocks)
        assert {m for _a, m in calls} == {"ReadBlocks"}
        if everywhere:
            with pytest.raises(DfsError, match="checksum mismatch"):
                await reader.confirm(blocks)
            assert not blocks[1].verified
            assert [b.verified for b in blocks] == [True, False, True, True]
        else:
            assert await _confirmed_bytes(reader, blocks) == data
            assert blocks[1].batch is None, "not re-read"
        assert sum(m == "ReadBlock" for _a, m in calls) == K + M - 2
    finally:
        await c.stop()


async def test_shard_a_holder_cannot_serve_sends_its_block_alone(
        tmp_path, monkeypatch):
    """One id of one frame answered -1 (the holder lost that shard): that
    block falls back per block, where the six shards left still decode it;
    the other fifteen stay in the round."""
    data = _rand(16 * BLOCK, seed=108)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/gap", data)])
    try:
        meta = await client.get_file_info("/ec/gap")
        block = meta["blocks"][5]
        await _two_down(c, client, block["locations"][:2])
        holder = _server(c, block["locations"][4])
        holder.store.block_path(block["block_id"]).unlink()
        holder.invalidate_cached(block["block_id"])
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        telemetry.enable()
        try:
            blocks = await reader.read_file_to_device_blocks("/ec/gap",
                                                             verify="lazy")
        finally:
            telemetry.disable()
            records = telemetry.drain()
        assert [b.batch is None for b in blocks] == \
            [i == 5 for i in range(16)]
        assert await _confirmed_bytes(reader, blocks) == data
        assert reader.ec_round_blocks == 15 and reader.ec_blocks == 16
        assert [r.attrs["fell_back"] for r in records
                if r.name == "ec.fetch_shards" and "round" in r.attrs] == [1]
    finally:
        await c.stop()


async def test_holder_that_dies_mid_round_sends_its_blocks_back(
        tmp_path, monkeypatch):
    """A third server stops with the round's frames on their way: its
    frame fails (UNAVAILABLE), the blocks that asked it for a shard fall
    back and decode from the six holders left, the blocks that did not
    stay in the round, and the read succeeds."""
    data = _rand(18 * BLOCK, seed=109)
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/die", data)],
                            rotate=True)
    try:
        meta = await client.get_file_info("/ec/die")
        dead = meta["blocks"][0]["locations"][:2]
        await _two_down(c, client, dead)
        # The highest live slot of block 0, which its round does not ask.
        third = meta["blocks"][0]["locations"][K + M - 1]
        board = client.block_pool.breakers
        asked = [b.get("ec_data_shards") and may_fuse(b, board)
                 and third in [b["locations"][s]
                               for s in ec_survivors(b, board)]
                 for b in meta["blocks"]]
        rides = [bool(may_fuse(b, board)) for b in meta["blocks"]]
        assert any(asked) and any(r and not a for r, a in zip(rides, asked))
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        real = client._data_call
        stopped = []

        async def dying(addr, method, req, **kw):
            if addr == third and method == "ReadBlocks" and not stopped:
                stopped.append(addr)
                await _stop_holders(c, [third])
            return await real(addr, method, req, **kw)

        client._data_call = dying
        with pytest.raises(RpcError) as refused:
            await real(dead[0], "ReadBlocks", {"block_ids": []}, timeout=5.0)
        assert refused.value.code == grpc.StatusCode.UNAVAILABLE
        blocks = await reader.read_file_to_device_blocks("/ec/die",
                                                         verify="lazy")
        assert stopped == [third] and board.is_open(third)
        assert await _confirmed_bytes(reader, blocks) == data
        in_round = [b.batch is not None for b in blocks]
        assert in_round == [r and not a for r, a in zip(rides, asked)]
    finally:
        await c.stop()


# ------------------------------------------------------------------ warm-up


async def test_after_warm_ec_a_degraded_read_compiles_nothing(
        tmp_path, monkeypatch):
    """``warm_ec`` alone, before any data exists and without knowing who
    will die, compiles every program the rounds (at every bucket up to
    ``batch_reads``), the fallback and ``confirm`` dispatch."""
    import jax.monitoring
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    compiles: list = []

    def on_event(event, _duration, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(event)

    data = _rand(15 * BLOCK, seed=110)  # rounds of 8 + 4 + 2 + 1
    c, client = await _nine(tmp_path, monkeypatch, [("/ec/warm", data)])
    try:
        reader = HbmReader(client, jax.devices()[:1], batch_reads=8)
        reader.warm_ec(K, M, BLOCK)
        meta = await client.get_file_info("/ec/warm")
        await _two_down(c, client, meta["blocks"][0]["locations"][:2])
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            blocks = await reader.read_file_to_device_blocks(
                "/ec/warm", verify="lazy")
            await reader.confirm(blocks)
            jax.block_until_ready([b.batch.words for b in blocks])
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        assert not compiles
        assert reader.ec_round_blocks == 15 and reader.ec_rounds == 4
        assert all(b.verified for b in blocks)
    finally:
        await c.stop()
