"""``closed_loop_read_hbm`` over an erasure-coded dataset with chunkservers
down: the HDFS-EC degraded read into HBM.

Set-up writes the dataset ``ec=(k, m)`` (``cfg["ec"]``), then SIGKILLs
``cfg["failed_chunkservers"]`` chunkservers by PID and leaves them dead
through the window and the check. The victims are drawn from the seed among
the holders of DATA slots of the first block of the first file, all in one
rack (slots ``j, j + racks, ...``: the rack-aware placement deals a rack's
servers to slots ``j, j + racks, j + 2 * racks``), so every block of every
file has lost a data shard and no seed yields a run with parity-only
losses. Set-up then waits until the masters have noticed (their ops gauge
``tpudfs_master_chunk_servers``; the 15 s liveness cutoff is part of
``setup_s``), so the window measures the degraded steady state. The
reader's programs are warmed BEFORE the data is written, without knowing
which servers will die: a program that has no such warm-up cannot run the
cell and fails there, soon and cleanly.

The window and its one operation are ``closed_loop_read_hbm``'s
(``read_file_to_device_blocks`` then ``confirm``). No fused round can form
(every block is erasure-coded), so ``warm_batches`` is left out.

The check: bytes on the device and ``verified`` of ``check_files`` files
(``harness.Expect.device``); the master's record (size, block sizes, each
block's CRC32C, k, m, k + m distinct slots); for ``check_replica_blocks``
blocks every SURVIVING slot, asked directly, returns exactly the shard
``reference_rs.encode`` gives for that slot. ``window.compared`` also
carries the dataset's degraded blocks, missing data shards and distinct
survivor sets, counted from the master's block lists and the dead servers.

Mix parameters: those of ``closed_loop_read_hbm`` and ``noticed_wait_s``.
"""

from __future__ import annotations

import asyncio
import os
import re
import signal
import sys
import time
import urllib.request

import numpy as np

from benchmarks import dataset, reference, reference_rs, sabotage
from benchmarks.deployments import read_replica
from benchmarks.traffic import closed_loop_read_hbm

KIND = "closed_loop_read_hbm_degraded"
#: the reader's counters of erasure-coded reads, reported as ``hbm.<name>``
EC_COUNTERS = ("ec_blocks", "ec_degraded_blocks", "ec_missing_data_shards",
               "ec_shard_bytes")
_GAUGE = re.compile(r"^tpudfs_master_chunk_servers\s+(\d+)", re.M)


def draw_victims(seed: int, locations: list[str], k: int, racks: int,
                 failed: int) -> list[str]:
    """``failed`` holders of data slots ``j, j + racks, ...`` of the block
    whose slots are ``locations``; ``j`` from the seed."""
    first = k - (failed - 1) * racks
    if first <= 0:
        raise ValueError(f"no {failed} data slots of one rack among {k} "
                         f"with {racks} racks")
    j = int(np.random.default_rng([seed, 0xDEAD]).integers(first))
    return [locations[j + i * racks] for i in range(failed)]


async def kill_chunkserver(ctx, addr: str) -> None:
    """SIGKILL by PID, as the launcher's endpoint map names it. The dead
    server leaves the list the bring-up asks for ``Stats`` (its counters
    are sums over the chunkservers that can answer)."""
    endpoints = ctx.bringup.endpoints
    for name, proc in endpoints["procs"].items():
        if name.startswith("cs") and proc["addr"] == addr:
            os.kill(proc["pid"], signal.SIGKILL)
            endpoints["chunkservers"] = [
                a for a in endpoints["chunkservers"] if a != addr]
            return
    raise KeyError(addr)


async def chunkservers_seen(ctx) -> int | None:
    """The most chunkservers any master still counts, from the masters'
    ops HTTP (``/metrics`` on the RPC port + 1000), as an operator of the
    deployment sees it; ``None`` while no master answers."""
    def gauge(addr: str) -> int | None:
        host, port = addr.rsplit(":", 1)
        try:
            with urllib.request.urlopen(
                    f"http://{host}:{int(port) + 1000}/metrics",
                    timeout=2.0) as resp:
                found = _GAUGE.search(resp.read().decode())
        except OSError:
            return None
        return int(found.group(1)) if found else None

    seen = await asyncio.gather(*(
        asyncio.to_thread(gauge, addr)
        for addr in ctx.bringup.endpoints["shards"]["shard-0"]))
    return max((n for n in seen if n is not None), default=None)


async def write_ec(ctx, client) -> float:
    """``dataset.write`` with every file erasure-coded; returns seconds."""
    file_bytes = ctx.cfg["dataset"]["file_bytes"]
    ec = tuple(ctx.cfg["ec"])
    sem = asyncio.Semaphore(dataset.WRITE_CONCURRENCY)

    async def put(i: int, path: str) -> None:
        async with sem:
            data = await asyncio.to_thread(
                reference.seeded_bytes, ctx.seed, dataset.DATA_STREAM + i,
                file_bytes)
            await client.create_file(path, data, ec=ec)

    t0 = time.perf_counter()
    await asyncio.gather(*(put(i, p) for i, p in
                           enumerate(dataset.paths_of(ctx.cfg))))
    return time.perf_counter() - t0


class Traffic(closed_loop_read_hbm.Traffic):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.k, self.m = ctx.cfg["ec"]
        self.failed = ctx.cfg["failed_chunkservers"]
        self.dead: list[str] = []
        self.noticed_wait_s = 0.0

    def counters(self) -> dict:
        out = super().counters()
        for name in EC_COUNTERS:
            value = getattr(self.reader, name, None)
            if value is not None:
                out[f"hbm.{name}"] = value
        return out

    async def prepare(self) -> None:
        from tpudfs.tpu.hbm_reader import HbmReader

        ctx, cfg = self.ctx, self.ctx.cfg
        self.client = ctx.bringup.client(
            ctx.rpc, local_reads=self.mix["local_reads"])
        self.reader = HbmReader(self.client, [ctx.device],
                                batch_reads=self.mix["batch_reads"])
        await asyncio.to_thread(self.reader.warm_ec, self.k, self.m,
                                cfg["block_bytes"])
        self.dataset_write_s = await write_ec(ctx, self.client)
        first = (await self.client.get_file_info(self.paths[0]))["blocks"][0]
        self.dead = draw_victims(ctx.seed, first["locations"], self.k,
                                 cfg["racks"], self.failed)
        for addr in self.dead:
            await kill_chunkserver(ctx, addr)
        await self.wait_noticed()
        # One file through the timed entry: connections, the metadata
        # path, the client's breakers on the dead servers, the confirm
        # fetch.
        await self.read(self.paths[0])
        await dataset.warm_per_block_path(ctx, self.client, self.reader)

    async def wait_noticed(self) -> None:
        want = self.ctx.cfg["chunkservers"] - self.failed
        t0 = time.perf_counter()
        while True:
            seen = await chunkservers_seen(self.ctx)
            self.noticed_wait_s = time.perf_counter() - t0
            if seen is not None and seen <= want:
                return
            if self.noticed_wait_s > self.mix["noticed_wait_s"]:
                raise RuntimeError(
                    f"the masters still count {seen} chunkservers "
                    f"{self.noticed_wait_s:.0f} s after {self.failed} were "
                    f"killed (want {want})")
            await asyncio.sleep(0.5)

    # ------------------------------------------------------------ the check

    async def check(self, ops, expect) -> None:
        ctx, mix = self.ctx, self.mix
        left = [entry for per in self.resident for entry in per]
        if not left:
            expect.wrong("device_blocks_missing", "the window left nothing")
            return
        take = min(mix["check_files"], len(left))
        picked = [left[j] for j in sorted(
            ctx.rng.choice(len(left), take, replace=False).tolist())]
        per_file = max(1, mix["check_replica_blocks"] // take)
        for i, held in picked:
            data = expect.data(dataset.DATA_STREAM + i, self.file_bytes)
            expect.device(held, data)
            meta = await self.metadata(expect, self.paths[i], data)
            if meta is not None:
                nblocks = len(meta["blocks"])
                await self.shards(expect, meta, data, sorted(ctx.rng.choice(
                    nblocks, min(per_file, nblocks),
                    replace=False).tolist()))
        expect.compared.update(await self.degraded_state())

    async def metadata(self, expect, path: str, data: bytes) -> dict | None:
        """The master's record of ``path`` against the reference's: size,
        block sizes, every block's CRC32C, k, m and k + m distinct slots
        (the dead servers stay listed: nothing was rebuilt)."""
        expect.compared["files"] += 1
        meta = await self.client.get_file_info(path)
        want = reference.expected_file(data, expect.block_bytes, 1)
        if meta is None:
            expect.wrong("meta_missing", path)
            return None
        blocks = meta.get("blocks") or []
        if int(meta.get("size", -1)) != want["size"] or \
                [int(b.get("size") or 0) for b in blocks] \
                != want["block_sizes"]:
            expect.wrong("meta_size_wrong",
                         f"{path}: size {meta.get('size')} blocks "
                         f"{[b.get('size') for b in blocks]} against size "
                         f"{want['size']} blocks {want['block_sizes']}")
            return None
        for b, crc in zip(blocks, want["block_crcs"]):
            expect.compared["meta_blocks"] += 1
            if int(b.get("checksum_crc32c") or -1) != crc:
                expect.wrong("meta_crc_wrong", f"{path} {b['block_id']}: "
                             f"{b.get('checksum_crc32c')} against {crc}")
            slots = b.get("locations") or []
            code = (int(b.get("ec_data_shards") or 0),
                    int(b.get("ec_parity_shards") or 0))
            if code != (self.k, self.m) or len(slots) != self.k + self.m \
                    or len({a for a in slots if a}) != len(slots):
                expect.wrong("meta_replicas_short", f"{path} "
                             f"{b['block_id']}: RS{code} slots {slots}")
        return meta

    async def shards(self, expect, meta: dict, data: bytes, picked) -> None:
        """Every surviving slot of the ``picked`` blocks, asked directly,
        returns the reference's shard for that slot."""
        view = memoryview(data)
        bb = expect.block_bytes
        for j in picked:
            block = meta["blocks"][j]
            want = reference_rs.encode(view[j * bb:(j + 1) * bb],
                                       self.k, self.m)
            reached = 0
            for slot, addr in enumerate(block["locations"]):
                if not addr or addr in self.dead:
                    continue
                expect.compared["replica_reads"] += 1
                try:
                    got = await read_replica(self.ctx.rpc, addr,
                                             block["block_id"])
                except Exception as e:
                    print(f"benchmark: slot {slot} at {addr} of "
                          f"{block['block_id']}: {e!r}", file=sys.stderr)
                    continue
                reached += 1
                if got != want[slot]:
                    expect.wrong("replica_bytes_wrong",
                                 f"slot {slot} at {addr} of "
                                 f"{block['block_id']}")
            if reached < self.k + self.m - self.failed:
                expect.wrong("meta_replicas_short",
                             f"{block['block_id']}: {reached} slots reached")

    async def degraded_state(self) -> dict:
        """What the dead servers cost the dataset, from the master's block
        lists: blocks that lost a data shard, the data shards lost, and the
        distinct sets of k survivors a reader decodes from."""
        out = {"dataset_blocks": 0, "degraded_blocks": 0,
               "missing_data_shards": 0}
        sets = set()
        for path in self.paths:
            meta = await self.client.get_file_info(path)
            for block in meta["blocks"]:
                alive = [s for s, a in enumerate(block["locations"])
                         if a and a not in self.dead]
                lost = self.k - sum(s < self.k for s in alive)
                out["dataset_blocks"] += 1
                out["degraded_blocks"] += lost > 0
                out["missing_data_shards"] += lost
                if lost:
                    sets.add(tuple(alive[:self.k]))
        out["survivor_sets"] = len(sets)
        return out


# ------------------------------------------------------------- the control


class RottenSurvivorUnverifiedRead(sabotage.Sabotage):
    """``blocks_per_file`` blocks of every file have one surviving shard
    the reconstruction uses replaced on its server (data and sidecar
    consistent, so the server serves it happily), and the timed entry hands
    blocks to the device without the CRC32C of the reconstructed bytes,
    calling them verified. Breaks "a read returns only bytes whose CRC32C
    was verified after reconstruction"."""

    def __init__(self, blocks_per_file: int = 2):
        self.blocks_per_file = blocks_per_file

    async def after_prepare(self) -> None:
        ctx, traffic = self.ctx, self.traffic
        for path in traffic.paths:
            meta = await traffic.client.get_file_info(path)
            n = len(meta["blocks"])
            for j in ctx.rng.choice(n, min(self.blocks_per_file, n),
                                    replace=False).tolist():
                block = meta["blocks"][j]
                addr = next(a for a in block["locations"]
                            if a and a not in traffic.dead)
                store = ctx.bringup.store_of(addr)
                size = len(await asyncio.to_thread(store.read,
                                                   block["block_id"]))
                wrong = np.random.default_rng([ctx.seed, 666, j]).bytes(size)
                await asyncio.to_thread(store.write, block["block_id"],
                                        wrong)
        reader = traffic.reader

        async def unverified_file(path: str) -> list:
            return sabotage._mark_verified(
                await reader.read_file_to_device_blocks(path, verify=False))

        traffic.read = unverified_file


sabotage.CONTROLS[KIND] = RottenSurvivorUnverifiedRead
