"""Faults planted underneath the timed path, for the control and the tests
only: ``run.py`` never passes one. Each shows that the comparison which
decides ``correct`` can fail.

Controls (each breaks one guarantee the configuration states, as a
tempting later change would):

- ``SilentRotUnverifiedRead`` (read cells): blocks of every file are
  replaced on all their replicas — data and sidecar consistent, so only the
  end-to-end check against the master's recorded CRC32C can tell — and the
  timed entry hands bytes to the device without that check, calling them
  verified. Breaks "a read returns only bytes whose CRC32C was verified".
- ``AckBeforeThirdReplica`` (write cells): one named replica of every
  acknowledged put is gone when the window closes. Breaks "acknowledged
  only after 3 distinct chunkservers hold the block durably".

Faults of the timed path (tests): ``AnswerAltered``, ``HalfLeftOut``,
``StateUnchanged``, ``ExchangeLeftOut``. ``BlockGoneFromOneReplica`` is no
fault of the timed path: the program recovers, and ``correct`` stays true.
"""

from __future__ import annotations

import asyncio


class Sabotage:
    def attach(self, ctx, traffic) -> None:
        self.ctx = ctx
        self.traffic = traffic

    async def after_prepare(self) -> None:
        pass

    async def after_window(self, ops) -> None:
        pass


def _mark_verified(held: list) -> list:
    for b in held:
        b.verified = True
        b.pending_crc = None
        b.batch_pending = False
    return held


class SilentRotUnverifiedRead(Sabotage):
    """``blocks_per_file`` blocks of every dataset file replaced on every
    replica; reads go to the device unverified."""

    def __init__(self, blocks_per_file: int = 2):
        self.blocks_per_file = blocks_per_file

    async def after_prepare(self) -> None:
        import jax
        import numpy as np

        from benchmarks import dataset

        ctx, traffic = self.ctx, self.traffic
        for path in dataset.paths_of(ctx.cfg):
            meta = await traffic.client.get_file_info(path)
            n = len(meta["blocks"])
            for j in ctx.rng.choice(n, min(self.blocks_per_file, n),
                                    replace=False).tolist():
                block = meta["blocks"][j]
                size = int(block["size"])
                wrong = np.random.default_rng([ctx.seed, 666, j]).bytes(size)
                for addr in {a for a in block["locations"] if a}:
                    await asyncio.to_thread(
                        ctx.bringup.store_of(addr).write,
                        block["block_id"], wrong)
        reader = traffic.reader
        device = ctx.device

        async def unverified_file(path: str) -> list:
            return _mark_verified(await reader.read_file_to_device_blocks(
                path, verify=False))

        async def unverified_sweep(order: list[int]) -> list:
            """A plain reader in the pump's place: pread, device_put."""
            from tpudfs.tpu.crc32c_pallas import bytes_to_words
            from tpudfs.tpu.hbm_reader import DeviceBlock

            held = []
            for i in order:
                meta = await traffic.client.get_file_info(traffic.paths[i])
                for block in meta["blocks"]:
                    addr = next(a for a in block["locations"] if a)
                    data = await asyncio.to_thread(
                        ctx.bringup.store_of(addr).read, block["block_id"])
                    held.append(DeviceBlock(
                        block["block_id"],
                        jax.device_put(bytes_to_words(data), device),
                        len(data), True))
            return held

        if hasattr(traffic, "sweep"):
            traffic.sweep = unverified_sweep
        else:
            traffic.read = unverified_file


class AckBeforeThirdReplica(Sabotage):
    async def after_window(self, ops) -> None:
        ctx, traffic = self.ctx, self.traffic
        for op in ops:
            if not op.ok:
                continue
            meta = await traffic.client.get_file_info(op.what[1][0])
            for block in meta["blocks"]:
                addr = sorted(a for a in block["locations"] if a)[0]
                await asyncio.to_thread(
                    ctx.bringup.store_of(addr).delete, block["block_id"])


class BlockGoneFromOneReplica(Sabotage):
    """One block of every file out of reach at its first replica for as
    long as the window lasts, as when the master's balancer moves a block
    after the reader took the metadata: the fused round cannot serve it
    and falls back to the per-block path. Back in place for the check."""

    async def after_prepare(self) -> None:
        from benchmarks import dataset

        self.moved = []
        for path in dataset.paths_of(self.ctx.cfg):
            meta = await self.traffic.client.get_file_info(path)
            block = meta["blocks"][0]
            addr = next(a for a in block["locations"] if a)
            file = self.ctx.bringup.store_of(addr).block_path(
                block["block_id"])
            file.rename(file.with_suffix(".gone"))
            self.moved.append(file)

    async def after_window(self, ops) -> None:
        for file in self.moved:
            file.with_suffix(".gone").rename(file)


class AnswerAltered(Sabotage):
    """A word altered where the answer is produced: in the blocks a read
    leaves on the device, or in the bytes a put hands to the client."""

    def attach(self, ctx, traffic) -> None:
        super().attach(ctx, traffic)
        if hasattr(traffic, "put"):
            inner_put = traffic.put

            async def put(path: str, data: bytes) -> None:
                await inner_put(path, bytes([data[0] ^ 1]) + data[1:])

            traffic.put = put
            return
        name = "sweep" if hasattr(traffic, "sweep") else "read"
        inner = getattr(traffic, name)

        per_file = ctx.cfg["dataset"]["file_bytes"] // ctx.cfg["block_bytes"]

        async def altered(arg) -> list:
            held = await inner(arg)
            for b in held[::per_file]:  # one block of every file
                b.array = b.array.at[0, 0].add(1)
            return held

        setattr(traffic, name, altered)


class HalfLeftOut(Sabotage):
    """Half of the blocks of every read left out."""

    def attach(self, ctx, traffic) -> None:
        super().attach(ctx, traffic)
        name = "sweep" if hasattr(traffic, "sweep") else "read"
        inner = getattr(traffic, name)

        async def half(arg) -> list:
            held = await inner(arg)
            return held[: len(held) // 2]

        setattr(traffic, name, half)


class StateUnchanged(Sabotage):
    """A put that acknowledges and leaves the cluster as it was."""

    async def after_prepare(self) -> None:
        async def put(path: str, data: bytes) -> None:
            await asyncio.sleep(0.001)

        self.traffic.put = put


class ExchangeLeftOut(Sabotage):
    """The exchange between chips left out of the replicate program: every
    device keeps its own batch in all R replica groups, so a member
    persists its own blocks under its ring predecessors' ids."""

    async def after_prepare(self) -> None:
        import jax
        import jax.numpy as jnp

        replicator = self.ctx.bringup.group.replicator
        inner = replicator.replicate
        R = replicator.replication

        def replicate(words, crcs):
            replicas, ok, acks = inner(words, crcs)
            n = len(self.ctx.devices)
            C = words.shape[0] // n
            own = words.reshape(n, 1, C, words.shape[1])
            same = jnp.broadcast_to(own, (n, R, C, words.shape[1]))
            return (jax.device_put(same.reshape(replicas.shape),
                                   replicas.sharding), ok, acks)

        replicator.replicate = replicate


CONTROLS = {
    "closed_loop_read_hbm": SilentRotUnverifiedRead,
    "epoch_sweep_hbm": SilentRotUnverifiedRead,
    "closed_loop_put": AckBeforeThirdReplica,
}
