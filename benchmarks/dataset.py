"""The read cells' dataset: ``files`` files of ``file_bytes`` each, bytes
of stream ``DATA_STREAM + i`` of the seed, written through
``Client.create_file`` in set-up (3x replicated, the program's own flush
policy) because every run starts from an empty cluster."""

from __future__ import annotations

import asyncio
import time

from benchmarks import reference

DATA_STREAM = 100
WRITE_CONCURRENCY = 8


def paths_of(cfg: dict) -> list[str]:
    return [f"/bench/data/f{i:04d}" for i in range(cfg["dataset"]["files"])]


async def write(ctx, client) -> float:
    """Returns the seconds the write took (a set-up reading: every read
    number stands on the directory under the cluster)."""
    file_bytes = ctx.cfg["dataset"]["file_bytes"]
    sem = asyncio.Semaphore(WRITE_CONCURRENCY)

    async def put(i: int, path: str) -> None:
        async with sem:
            data = await asyncio.to_thread(
                reference.seeded_bytes, ctx.seed, DATA_STREAM + i,
                file_bytes)
            await client.create_file(path, data)

    t0 = time.perf_counter()
    await asyncio.gather(*(put(i, p)
                           for i, p in enumerate(paths_of(ctx.cfg))))
    return time.perf_counter() - t0


async def warm_per_block_path(ctx, client, reader) -> None:
    """The per-block path a fused round falls back to when the pump or the
    combiner cannot serve a block (moved by the master's balancer since
    the metadata was read, cold tier, short read): its whole-block CRC
    program and ``confirm``'s stacked fetch at every bucket up to one
    file's blocks. It runs about once in fifteen 40 s windows (my chip
    runs, PR 24), so without this one window in fifteen compiles."""
    meta = await client.get_file_info(paths_of(ctx.cfg)[0])
    one = await reader.read_block_to_device(
        meta["blocks"][0], ctx.device, verify="lazy", safe_local=True)
    n = 1
    while n <= len(meta["blocks"]):
        reader.warm_confirm(one, n)
        n <<= 1
    await reader.confirm([one])
