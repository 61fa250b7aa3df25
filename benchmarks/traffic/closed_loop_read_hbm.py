"""``clients`` readers, each reading whole files into HBM one after the
other (closed loop), cycling over the dataset in an order of its own drawn
from the seed. One operation: ``read_file_to_device_blocks(path,
verify=...)`` then ``confirm``; it succeeds when every block is resident
and confirmed verified. A reader keeps its last ``keep_resident`` files in
HBM and drops the one before.

Mix parameters: clients, local_reads, batch_reads, verify, keep_resident,
check_files, check_replica_blocks.
"""

from __future__ import annotations

import collections

from benchmarks import dataset, harness


class Traffic:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.mix = ctx.mix
        self.paths = dataset.paths_of(ctx.cfg)
        self.file_bytes = ctx.cfg["dataset"]["file_bytes"]
        self.client = None
        self.reader = None
        self.dataset_write_s = 0.0
        n = len(self.paths)
        #: reader c reads orders[c][0], orders[c][1], ... and wraps
        self.orders = [ctx.rng.permutation(n).tolist()
                       for _ in range(self.mix["clients"])]
        self.resident = [collections.deque(maxlen=self.mix["keep_resident"])
                         for _ in range(self.mix["clients"])]

    def counters(self) -> dict:
        out = {"combiner.rounds": 0, "combiner.blocks": 0}
        if self.reader is not None:
            for comb in self.reader._combiners.values():
                out["combiner.rounds"] += comb.rounds
                out["combiner.blocks"] += comb.blocks
        return out

    async def read(self, path: str) -> list:
        """The timed entry."""
        with self.ctx.spans.span("read_blocks_to_device"):
            held = await self.reader.read_file_to_device_blocks(
                path, verify=self.mix["verify"])
        with self.ctx.spans.span("confirm"):
            await self.reader.confirm(held)
        return held

    async def prepare(self) -> None:
        from tpudfs.tpu.hbm_reader import HbmReader

        ctx = self.ctx
        self.client = ctx.bringup.client(
            ctx.rpc, local_reads=self.mix["local_reads"])
        self.dataset_write_s = await dataset.write(ctx, self.client)
        self.reader = HbmReader(self.client, [ctx.device],
                                batch_reads=self.mix["batch_reads"])
        self.reader.warm_batches(ctx.cfg["block_bytes"] // 512)
        # One file through the timed entry: connections, metadata path,
        # the confirm fetch.
        await self.read(self.paths[0])
        await dataset.warm_per_block_path(ctx, self.client, self.reader)

    async def window(self, seconds: float, on_close):
        async def one_op(c: int, k: int):
            i = self.orders[c][k % len(self.paths)]
            held = await self.read(self.paths[i])
            if len(held) * self.ctx.cfg["block_bytes"] < self.file_bytes \
                    or not all(b.verified for b in held):
                raise RuntimeError(f"{self.paths[i]}: blocks missing or "
                                   "unverified after confirm")
            self.resident[c].append((i, held))
            return self.file_bytes, i

        return await harness.closed_loop(self.mix["clients"], seconds,
                                         one_op, on_close)

    def end_to_end(self, ops, t0: float, t1: float) -> dict:
        return {"hbm_read_GBps": harness.rate(ops, t0, t1) / 1e9,
                "read_p95_ms": harness.p95_ms(ops)}

    async def check(self, ops, expect: harness.Expect) -> None:
        """A sample, drawn from the seed, of the files the window's reads
        left in HBM."""
        left = [entry for per in self.resident for entry in per]
        await check_resident(self.ctx, self.client, left, expect)

    async def close(self) -> None:
        for per in self.resident:
            per.clear()
        if self.client is not None:
            await self.client.close()


async def check_resident(ctx, client, left: list, expect) -> None:
    """``left``: (file index, held blocks). For ``check_files`` of them:
    the bytes on the device, the master's metadata, and every named replica
    of ``check_replica_blocks`` blocks, against the reference."""
    mix = ctx.mix
    paths = dataset.paths_of(ctx.cfg)
    file_bytes = ctx.cfg["dataset"]["file_bytes"]
    if not left:
        expect.wrong("device_blocks_missing", "the window left nothing")
        return
    take = min(mix["check_files"], len(left))
    picked = [left[j] for j in sorted(
        ctx.rng.choice(len(left), take, replace=False).tolist())]
    per_file_replicas = max(1, mix["check_replica_blocks"] // take)
    for i, held in picked:
        data = expect.data(dataset.DATA_STREAM + i, file_bytes)
        expect.device(held, data)
        meta = await expect.metadata(client, paths[i], data)
        if meta is not None:
            nblocks = len(meta["blocks"])
            await expect.replicas(meta, data, sorted(ctx.rng.choice(
                nblocks, min(per_file_replicas, nblocks),
                replace=False).tolist()))
