"""``clients`` writers, each putting one fresh file of ``file_bytes`` after
the other (closed loop) for the whole window: the upstream ``dfs_cli
benchmark stress-write`` shape. One operation: ``Client.create_file``; it
succeeds when the put is acknowledged. The bytes of put number g are those
of stream ``PUT_STREAM + g % payloads`` of the seed.

Mix parameters: clients, file_bytes, payloads, check_puts, batch_reads.
"""

from __future__ import annotations

import asyncio
import sys

from benchmarks import harness, reference

PUT_STREAM = 1000


class Traffic:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.mix = ctx.mix
        self.client = None
        self.payloads: list[bytes] = []

    def counters(self) -> dict:
        return {}

    def path(self, c: int, k: int) -> str:
        return f"/bench/w/c{c:02d}/f{k:07d}"

    def stream(self, c: int, k: int) -> int:
        return PUT_STREAM + (k * self.mix["clients"] + c) \
            % self.mix["payloads"]

    async def put(self, path: str, data: bytes) -> None:
        """The timed entry."""
        await self.client.create_file(path, data)

    async def prepare(self) -> None:
        ctx = self.ctx
        self.client = ctx.bringup.client(ctx.rpc, local_reads=False)
        self.payloads = await asyncio.to_thread(lambda: [
            reference.seeded_bytes(ctx.seed, PUT_STREAM + j,
                                   self.mix["file_bytes"])
            for j in range(self.mix["payloads"])])
        # One put per writer through the timed entry: connections to every
        # master and chunkserver, the write group's first round.
        await asyncio.gather(*(
            self.put(f"/bench/warm/c{c:02d}", self.payloads[0])
            for c in range(self.mix["clients"])))

    async def window(self, seconds: float, on_close):
        async def one_op(c: int, k: int):
            stream = self.stream(c, k)
            path = self.path(c, k)
            with self.ctx.spans.span("create_file"):
                await self.put(path, self.payloads[stream - PUT_STREAM])
            return self.mix["file_bytes"], (path, stream)

        return await harness.closed_loop(self.mix["clients"], seconds,
                                         one_op, on_close)

    def end_to_end(self, ops, t0: float, t1: float) -> dict:
        return {"write_MBps": harness.rate(ops, t0, t1) / 1e6,
                "write_p95_ms": harness.p95_ms(ops)}

    async def check(self, ops, expect: harness.Expect) -> None:
        """``check_puts`` acknowledged puts drawn from the seed: the
        master's metadata, every named replica asked directly, and the file
        read back into HBM (device-verified) and compared byte for byte."""
        from tpudfs.tpu.hbm_reader import HbmReader

        ctx = self.ctx
        done = [o for o in ops if o.ok]
        if not done:
            expect.wrong("meta_missing", "no put was acknowledged")
            return
        take = min(self.mix["check_puts"], len(done))
        picked = [done[j] for j in sorted(
            ctx.rng.choice(len(done), take, replace=False).tolist())]
        reader = HbmReader(self.client, [ctx.device],
                           batch_reads=self.mix["batch_reads"])

        async def one(op) -> None:
            path, stream = op.what[1]
            data = expect.data(stream, self.mix["file_bytes"])
            meta = await expect.metadata(self.client, path, data)
            if meta is None:
                return
            await expect.replicas(meta, data, range(len(meta["blocks"])))
            try:
                with ctx.spans.span("check_read_back"):
                    held = await reader.read_file_to_device_blocks(
                        path, verify="lazy")
                    await reader.confirm(held)
            except Exception as e:
                print(f"benchmark: read-back of {path}: {e!r}",
                      file=sys.stderr)
                held = []
            expect.device(held, data)

        for lo in range(0, take, 16):
            await asyncio.gather(*(one(op) for op in picked[lo:lo + 16]))

    async def close(self) -> None:
        if self.client is not None:
            await self.client.close()
