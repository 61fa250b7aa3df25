"""One caller sweeping the whole dataset into HBM, epoch after epoch, each
epoch in an order drawn from the seed: ``sweep_paths_to_device(paths)``
then ``confirm``. One operation is one epoch; the window closes at the end
of the epoch in flight when ``--seconds`` have passed, so the rate is all
the bytes over all the time with no part-epoch left out. At most
``keep_epochs`` epochs are resident: the one before is dropped when the
next is complete.

Mix parameters: local_reads, batch_reads, keep_epochs, check_files,
check_replica_blocks.
"""

from __future__ import annotations

import collections
import sys
import time

from benchmarks import dataset, harness
from benchmarks.spans import CURRENT_OP
from benchmarks.traffic.closed_loop_read_hbm import check_resident


class Traffic:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.mix = ctx.mix
        self.paths = dataset.paths_of(ctx.cfg)
        self.file_bytes = ctx.cfg["dataset"]["file_bytes"]
        self.client = None
        self.reader = None
        self.dataset_write_s = 0.0
        self.resident = collections.deque(maxlen=self.mix["keep_epochs"])

    def counters(self) -> dict:
        return {"sweep.blocks":
                self.reader.sweep_blocks if self.reader else 0}

    async def sweep(self, order: list[int]) -> list:
        """The timed entry."""
        with self.ctx.spans.span("sweep_to_device"):
            held = await self.reader.sweep_paths_to_device(
                [self.paths[i] for i in order])
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
        # One file through the timed entry: the local-store probes, the
        # metadata path, the pump's first rounds.
        await self.sweep([0])
        await dataset.warm_per_block_path(ctx, self.client, self.reader)

    async def window(self, seconds: float, on_close):
        ops: list[harness.Op] = []
        n = len(self.paths)
        per_block = self.ctx.cfg["block_bytes"]
        t0 = time.perf_counter()
        epoch = 0
        closed = False
        while True:
            start = time.perf_counter()
            if start >= t0 + seconds:
                break
            CURRENT_OP.set(epoch)
            order = self.ctx.rng.permutation(n).tolist()
            held = None
            try:
                held = await self.sweep(order)
                if len(held) * per_block < n * self.file_bytes \
                        or not all(b.verified for b in held):
                    raise RuntimeError("epoch: blocks missing or "
                                       "unverified after confirm")
                self.resident.append((order, held))
                ops.append(harness.Op(start, time.perf_counter(), True,
                                      n * self.file_bytes, (epoch, order)))
            except Exception as e:
                print(f"benchmark: epoch failed: {e!r}", file=sys.stderr)
                ops.append(harness.Op(start, time.perf_counter(), False, 0,
                                      (epoch, None)))
            del held
            epoch += 1
            if on_close is not None and not closed \
                    and time.perf_counter() >= t0 + seconds:
                closed = True
                await on_close()
        return ops, t0, max(time.perf_counter(), t0 + seconds)

    def end_to_end(self, ops, t0: float, t1: float) -> dict:
        return {"hbm_read_GBps": harness.rate(ops, t0, t1) / 1e9}

    async def check(self, ops, expect: harness.Expect) -> None:
        """A sample, drawn from the seed, of the files of the last epoch
        the window left in HBM."""
        left = []
        if self.resident:
            order, held = self.resident[-1]
            per_file = self.file_bytes // self.ctx.cfg["block_bytes"]
            left = [(i, held[k * per_file:(k + 1) * per_file])
                    for k, i in enumerate(order)]
        await check_resident(self.ctx, self.client, left, expect)

    async def close(self) -> None:
        self.resident.clear()
        if self.client is not None:
            await self.client.close()
