"""``clients`` restorers (a resume is one caller: 1), each restoring the
newest published checkpoint of the deployment into HBM one time after the
other (closed loop, back to back). One operation:

    CheckpointManager(client, base, num_shards=n, ec=None,
                      reader=HbmReader(client, [chip], batch_reads=16)
                      ).restore(step=None, device=chip)

then ``jax.block_until_ready`` on every tensor: find the newest published
step, read its manifest, restore all shards in parallel, hand over the typed
tensors. It succeeds when every tensor of the configuration's table is
there with its dtype and shape. A restorer keeps its last ``keep_resident``
trees in HBM and drops the one before, as a job drops its state of before.
The op's bytes are the shard files' payload.

Set-up writes the dataset through the program's own ``save`` (host arrays
of the seed, ``reference_ckpt``), leaves a newer step staged and
uncommitted (``assumed.torn_step``: other bytes, no manifest), warms the
reader's fused rounds, the gathers and assemblies
(``CheckpointManager.warm_restore``), the per-block path a round falls back
to, and runs one restore through the timed entry.

The check: of the last tree held, ``check_tensors`` tensors drawn from the
seed plus the smallest, the largest and one bf16 and one f32 that straddle
a block boundary, bit for bit against the reference (D2H, viewed as uint8;
dtype and shape equal) under the existing counts: ``device_blocks_missing``
a tensor absent or of another dtype or shape, ``device_bytes_wrong`` its
bits, ``device_blocks_unverified`` a shard whose ``confirm`` did not return
with every block verified before the tree was handed over. Then the newest
published manifest is the configuration's step and names the reference's
shard paths, and ``harness.Expect.metadata`` + ``replicas`` hold the shard
files to the reference's own payloads.

Mix parameters: clients, local_reads, batch_reads, keep_resident,
check_tensors, check_replica_blocks.
"""

from __future__ import annotations

import asyncio
import collections
import json
import time

import numpy as np

from benchmarks import harness, reference_ckpt, sabotage

KIND = "closed_loop_restore_hbm"
#: manager stats reported as ``ckpt.<name>``
CKPT_COUNTERS = ("tensor_bytes_device", "tensor_bytes_host_bounce",
                 "restored_shards")


class Traffic:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.mix = ctx.mix
        cfg = ctx.cfg
        self.base = cfg["dataset"]["base"]
        self.step = cfg["dataset"]["published_step"]
        self.num_shards = cfg["assumed"]["num_shards"]
        self.table = reference_ckpt.table(cfg)
        self.shards = reference_ckpt.deal(cfg)
        self.payloads = [reference_ckpt.layout(cfg, s)[1]
                         for s in range(self.num_shards)]
        self.payload_bytes = sum(self.payloads)
        self.blocks = sum(max(1, -(-n // cfg["block_bytes"]))
                          for n in self.payloads)
        self.client = None
        self.dataset_write_s = 0.0
        #: (trees, [(blocks confirmed, of them unverified)]) of the last
        #: restores, newest last
        self.resident = collections.deque(maxlen=self.mix["keep_resident"])
        #: counters of the restores that ended, and the manager and reader
        #: of the one in flight
        self.totals = dict.fromkeys(
            ("combiner.rounds", "combiner.blocks",
             *(f"ckpt.{name}" for name in CKPT_COUNTERS)), 0)
        self.live = None

    # ------------------------------------------------------------ counters

    @staticmethod
    def _of(mgr, reader) -> dict:
        out = {f"ckpt.{name}": mgr.stats.get(name, 0)
               for name in CKPT_COUNTERS}
        combiners = reader._combiners.values()
        out["combiner.rounds"] = sum(c.rounds for c in combiners)
        out["combiner.blocks"] = sum(c.blocks for c in combiners)
        return out

    def counters(self) -> dict:
        out = dict(self.totals)
        if self.live is not None:
            for key, value in self._of(*self.live).items():
                out[key] += value
        return out

    # ------------------------------------------------------ the timed entry

    def manager(self, confirms: list | None = None):
        """A manager on a reader of its own, as a resuming process builds
        them; ``confirms`` collects what each ``confirm`` resolved."""
        from tpudfs.tpu.checkpoint import CheckpointManager
        from tpudfs.tpu.hbm_reader import HbmReader

        reader = HbmReader(self.client, [self.ctx.device],
                           batch_reads=self.mix["batch_reads"])
        if confirms is not None:
            inner = reader.confirm

            async def confirm(blocks, **kw):
                await inner(blocks, **kw)
                confirms.append((len(blocks),
                                 sum(not b.verified for b in blocks)))

            reader.confirm = confirm
        return CheckpointManager(
            self.client, self.base, num_shards=self.num_shards,
            ec=self.ctx.cfg["assumed"]["ec"], reader=reader), reader

    async def restore(self) -> tuple[dict, list]:
        """The timed entry: ``(trees, confirms)``."""
        import jax

        confirms: list = []
        self.live = self.manager(confirms)
        try:
            with self.ctx.spans.span("restore"):
                trees = await self.live[0].restore(step=None,
                                                   device=self.ctx.device)
            with self.ctx.spans.span("tensors_ready"):
                jax.block_until_ready(trees)
        finally:
            for key, value in self._of(*self.live).items():
                self.totals[key] += value
            self.live = None
        return trees, confirms

    def whole(self, trees: dict) -> str | None:
        """What a tree lacks of the table, or None when every tensor is
        there with its dtype and shape."""
        for shard, names in enumerate(self.shards):
            for name in names:
                got = trees.get(shard, {}).get(name)
                dtype, shape = self.table[name]
                if got is None:
                    return f"shard {shard} lacks {name}"
                if str(got.dtype) != dtype or tuple(got.shape) != shape:
                    return (f"{name}: {got.dtype}{tuple(got.shape)} "
                            f"against {dtype}{shape}")
        return None

    # -------------------------------------------------------------- set-up

    def host_tree(self, shard: int, step: str) -> dict:
        import jax.numpy as jnp

        out = {}
        for name in self.shards[shard]:
            dtype, shape, data = reference_ckpt.tensor(
                self.ctx.seed, self.ctx.cfg, name, step)
            out[name] = np.frombuffer(data, dtype=jnp.dtype(dtype)) \
                .reshape(shape)
        return out

    async def write_dataset(self) -> float:
        """The published step through ``save`` and, beside it (a file's
        blocks are written one after the other, so a fifth stream costs
        little), the torn step's shards through ``save_shard`` alone."""
        assumed = self.ctx.cfg["assumed"]
        mgr, _reader = self.manager()
        t0 = time.perf_counter()
        wanted = [(s, "published") for s in range(self.num_shards)] \
            + [(s, "torn") for s in assumed["torn_shards"]]
        trees = await asyncio.gather(*(
            asyncio.to_thread(self.host_tree, s, step) for s, step in wanted))
        await asyncio.gather(
            mgr.save(self.step, dict(enumerate(trees[:self.num_shards]))),
            *(mgr.save_shard(assumed["torn_step"], s, tree)
              for (s, _step), tree in zip(wanted[self.num_shards:],
                                          trees[self.num_shards:])))
        return time.perf_counter() - t0

    async def prepare(self) -> None:
        ctx = self.ctx
        self.client = ctx.bringup.client(
            ctx.rpc, local_reads=self.mix["local_reads"])
        self.dataset_write_s = await self.write_dataset()
        mgr, reader = self.manager()
        await asyncio.to_thread(reader.warm_batches,
                                ctx.cfg["block_bytes"] // 512)
        await mgr.warm_restore(ctx.device)
        await self.warm_per_block_path(reader)
        # One restore through the timed entry: connections, the metadata
        # path, the shapes only this deployment's short last blocks have.
        trees, _confirms = await self.restore()
        lacks = self.whole(trees)
        if lacks:
            raise RuntimeError(f"warm-up restore: {lacks}")

    async def warm_per_block_path(self, reader) -> None:
        """``dataset.warm_per_block_path`` for a shard file: the whole-block
        CRC program of a block that fell out of its fused round, and
        ``confirm``'s stacked fetch at every bucket up to a shard's
        blocks."""
        path = reference_ckpt.shard_path(self.base, self.step, 0)
        meta = await self.client.get_file_info(path)
        one = await reader.read_block_to_device(
            meta["blocks"][0], self.ctx.device, verify="lazy",
            safe_local=True)
        n = 1
        while n <= len(meta["blocks"]):
            reader.warm_confirm(one, n)
            n <<= 1
        await reader.confirm([one])

    # -------------------------------------------------------------- window

    async def window(self, seconds: float, on_close):
        async def one_op(c: int, k: int):
            trees, confirms = await self.restore()
            lacks = self.whole(trees)
            if lacks:
                raise RuntimeError(f"restore {k}: {lacks}")
            self.resident.append((trees, confirms))
            return self.payload_bytes, k

        return await harness.closed_loop(self.mix["clients"], seconds,
                                         one_op, on_close)

    def end_to_end(self, ops, t0: float, t1: float) -> dict:
        return {"hbm_read_GBps": harness.rate(ops, t0, t1) / 1e9,
                "read_p95_ms": harness.p95_ms(ops)}

    # --------------------------------------------------------------- check

    def sample(self) -> list[str]:
        """``check_tensors`` names from the seed, the smallest, the largest
        and one bf16 and one f32 that straddle a block boundary."""
        names = sorted(self.table)
        size = {n: reference_ckpt.nbytes(*self.table[n]) for n in names}
        picked = {names[i] for i in self.ctx.rng.choice(
            len(names), min(self.mix["check_tensors"], len(names)),
            replace=False).tolist()}
        picked.add(min(names, key=lambda n: (size[n], n)))
        picked.add(max(names, key=lambda n: (size[n], n)))
        for dtype in ("bfloat16", "float32"):
            picked.update(reference_ckpt.straddlers(self.ctx.cfg, dtype)[:1])
        return sorted(picked)

    def device_tensors(self, trees: dict, expect: harness.Expect) -> None:
        shard_of = {name: shard for shard, names in enumerate(self.shards)
                    for name in names}
        expect.compared["device_tensors"] = 0
        for name in self.sample():
            expect.compared["device_tensors"] += 1
            dtype, shape, data = reference_ckpt.tensor(
                self.ctx.seed, self.ctx.cfg, name)
            got = trees.get(shard_of[name], {}).get(name)
            if got is None or str(got.dtype) != dtype \
                    or tuple(got.shape) != shape:
                expect.wrong("device_blocks_missing",
                             f"{name}: {None if got is None else got.dtype}"
                             f" against {dtype}{shape}")
                continue
            bits = np.asarray(got).reshape(-1).view(np.uint8)
            if not np.array_equal(bits, np.frombuffer(data, np.uint8)):
                expect.wrong("device_bytes_wrong", name)

    async def check(self, ops, expect: harness.Expect) -> None:
        if not self.resident:
            expect.wrong("device_blocks_missing", "the window left nothing")
            return
        trees, confirms = self.resident[-1]
        # Every block of every shard went through a confirm that left it
        # verified, before the tree was handed over.
        if sum(n for n, _bad in confirms) != self.blocks \
                or any(bad for _n, bad in confirms):
            expect.wrong("device_blocks_unverified",
                         f"confirms {confirms} against {self.blocks} blocks "
                         f"of {self.num_shards} shards")
        self.device_tensors(trees, expect)
        await self.published(expect)
        per_shard = max(1, self.mix["check_replica_blocks"]
                        // self.num_shards)
        for shard in range(self.num_shards):
            data = await asyncio.to_thread(
                reference_ckpt.shard_payload, self.ctx.seed, self.ctx.cfg,
                shard)
            meta = await expect.metadata(
                self.client,
                reference_ckpt.shard_path(self.base, self.step, shard), data)
            if meta is not None:
                nblocks = len(meta["blocks"])
                await expect.replicas(meta, data, sorted(self.ctx.rng.choice(
                    nblocks, min(per_shard, nblocks),
                    replace=False).tolist()))

    async def published(self, expect: harness.Expect) -> None:
        """The newest published manifest is the configuration's step and
        names the reference's shard files; the staged step has none."""
        prefix = reference_ckpt.manifest_path(self.base, 0)[:-16]
        listed = sorted(path for path, _meta in
                        await self.client.list_files_with_meta(prefix,
                                                               meta=False))
        want = reference_ckpt.manifest_path(self.base, self.step)
        if not listed or listed[-1] != want:
            expect.wrong("meta_missing",
                         f"published manifests {listed} against {want}")
            return
        manifest = json.loads(await self.client.get_file(want))
        paths = [s.get("path") for s in manifest.get("shards", [])]
        if manifest.get("step") != self.step or paths != [
                reference_ckpt.shard_path(self.base, self.step, s)
                for s in range(self.num_shards)]:
            expect.wrong("meta_missing",
                         f"manifest step {manifest.get('step')} paths "
                         f"{paths}")

    async def close(self) -> None:
        self.resident.clear()
        if self.client is not None:
            await self.client.close()


# ------------------------------------------------------------- the control


class RotUnderLargestUnverifiedRestore(sabotage.Sabotage):
    """One block of one shard file replaced under every replica (data and
    sidecar consistent, so every server serves it happily), and the restore
    reads without the on-device CRC32C, calling the blocks verified, in the
    manner of ``SilentRotUnverifiedRead``. The block lies under the largest
    tensor, which the check always compares: one rotten block in 1 344 is
    otherwise under a sampled tensor in a run out of five. Breaks "no
    tensor is handed over before every block under it was CRC32C-verified
    on the device"."""

    async def after_prepare(self) -> None:
        ctx, traffic = self.ctx, self.traffic
        bb = ctx.cfg["block_bytes"]
        size = {n: reference_ckpt.nbytes(*traffic.table[n])
                for n in traffic.table}
        largest = max(size, key=lambda n: (size[n], n))
        shard = next(s for s, names in enumerate(traffic.shards)
                     if largest in names)
        offset = next(off for name, off, _size in
                      reference_ckpt.layout(ctx.cfg, shard)[0]
                      if name == largest)
        under = range(offset // bb, (offset + size[largest] - 1) // bb + 1)
        j = under[int(ctx.rng.integers(len(under)))]
        meta = await traffic.client.get_file_info(
            reference_ckpt.shard_path(traffic.base, traffic.step, shard))
        block = meta["blocks"][j]
        wrong = np.random.default_rng([ctx.seed, 666, j]).bytes(
            int(block["size"]))
        for addr in {a for a in block["locations"] if a}:
            await asyncio.to_thread(ctx.bringup.store_of(addr).write,
                                    block["block_id"], wrong)
        inner = traffic.manager

        def unverified_manager(confirms=None):
            mgr, reader = inner(confirms)
            read = reader.read_file_to_device_blocks

            async def unverified(path, verify=True, **kw):
                return sabotage._mark_verified(
                    await read(path, verify=False, **kw))

            reader.read_file_to_device_blocks = unverified
            return mgr, reader

        traffic.manager = unverified_manager


sabotage.CONTROLS[KIND] = RotUnderLargestUnverifiedRestore
