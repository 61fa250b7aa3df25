"""``clients`` restorers (a resume is one caller: 1), each restoring the
newest published checkpoint of the deployment onto the cell's four chips
under ANOTHER layout than it was saved in, one time after the other
(closed loop, back to back). One operation:

    CheckpointManager(client, base, num_shards=n, ec=None,
                      reader=HbmReader(client, chips, batch_reads=16)
                      ).restore(step=None, target=layout B)

then ``jax.block_until_ready`` on every array: find the newest published
step, read its manifest, plan, read every planned block onto the chip the
plan names, one ``confirm`` and the combined CRC a shard file, one
chip-to-chip move, one assembly a chip, hand over ``{name: jax.Array}``
sharded over the mesh. It succeeds when every global tensor of the host's
share is there with its dtype, host shape and sharding. A restorer keeps
its last ``keep_resident`` results on the chips. The op's bytes are the
host share's UNIQUE saved bytes (``reference_reshard.unique_bytes``):
bytes that land on several chips count once.

Layout B (``target`` of the configuration): a mesh of the cell's chips in
order, row-major over ``target.mesh``'s axes, a ``PartitionSpec`` a
parameter (its optimizer state follows it), the host's index range of the
stacked experts. Set-up first builds it and the save's pieces, so a program
without restore under another layout fails at once; then it writes the
dataset through the program's own ``save`` (each rank's pieces of the
stacked experts, rank 0's dense part once; ``reference_reshard``), leaves a
newer step staged (``assumed.torn_step``), warms the combiners of every
chip, the plan's gathers, move and assemblies
(``CheckpointManager.warm_restore(target=...)``), the per-block path a
round falls back to on every chip, and runs one restore through the timed
entry.

The check: of the last result held, ``check_tensors`` tensors drawn from
the seed plus one of each kind (a row split, a column split, a 2-chip
duplicate and a replicated tensor, each in bf16 and f32, and ``step``),
EVERY chip's shard bit for bit against the reference's slice for that chip
of the cell's mesh (D2H, viewed as uint8; dtype and shape equal), under the
existing counts: ``device_blocks_missing`` a tensor or a shard absent or of
another dtype or shape, ``device_bytes_wrong`` its bits,
``device_blocks_unverified`` a block not verified by a ``confirm`` before
the result was handed over. Then the newest published manifest is the
configuration's step and names the reference's shard paths, and
``harness.Expect.metadata`` + ``replicas`` hold the shard files to the
reference's own payloads.

Mix parameters: clients, local_reads, batch_reads, keep_resident,
check_tensors, check_replica_blocks.
"""

from __future__ import annotations

import asyncio
import collections
import time

import numpy as np

from benchmarks import harness, reference_ckpt, reference_reshard as ref
from benchmarks import sabotage
from benchmarks.traffic import closed_loop_restore_hbm as restore_kind

KIND = "closed_loop_reshard_restore_hbm"
#: manager stats reported as ``ckpt.<name>``
CKPT_COUNTERS = restore_kind.CKPT_COUNTERS + (
    "reshard_unique_bytes", "reshard_h2d_bytes", "reshard_ici_bytes",
    "reshard_pieces")


class Traffic(restore_kind.Traffic):
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.mix = ctx.mix
        cfg = ctx.cfg
        self.base = cfg["dataset"]["base"]
        self.step = cfg["dataset"]["published_step"]
        self.table = ref.table(cfg)
        self.num_shards = len(ref.shards(cfg))
        self.payloads = [ref.layout(cfg, s)[1]
                         for s in range(self.num_shards)]
        self.payload_bytes = ref.unique_bytes(cfg)
        self.blocks = sum(max(1, -(-n // cfg["block_bytes"]))
                          for n in self.payloads)
        self.client = None
        self.dataset_write_s = 0.0
        self.target = None
        self.resident = collections.deque(maxlen=self.mix["keep_resident"])
        self.totals = dict.fromkeys(
            ("combiner.rounds", "combiner.blocks",
             *(f"ckpt.{name}" for name in CKPT_COUNTERS)), 0)
        self.live = None

    @staticmethod
    def _of(mgr, reader) -> dict:
        out = {f"ckpt.{name}": mgr.stats.get(name, 0)
               for name in CKPT_COUNTERS}
        combiners = reader._combiners.values()
        out["combiner.rounds"] = sum(c.rounds for c in combiners)
        out["combiner.blocks"] = sum(c.blocks for c in combiners)
        return out

    # ------------------------------------------------------ the timed entry

    def make_target(self, devices: list):
        """Layout B on ``devices``, row-major over the mesh's axes."""
        from jax.sharding import Mesh, PartitionSpec as P

        from tpudfs.tpu.ckpt_reshard import Target

        axes = self.ctx.cfg["target"]["mesh"]
        mesh = Mesh(np.array(devices).reshape(tuple(axes.values())),
                    tuple(axes))
        specs = {name: P(*ref.spec_of(self.ctx.cfg, name))
                 for name in self.table if ref.spec_of(self.ctx.cfg, name)}
        index = {name: rng for name, (_d, gshape, rng) in self.table.items()
                 if tuple(b - a for a, b in rng) != gshape}
        return Target(mesh, specs, index)

    def manager(self, confirms: list | None = None):
        from tpudfs.tpu.checkpoint import CheckpointManager
        from tpudfs.tpu.hbm_reader import HbmReader

        reader = HbmReader(self.client, self.ctx.devices,
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
        """The timed entry: ``(arrays, confirms)``."""
        import jax

        confirms: list = []
        self.live = self.manager(confirms)
        try:
            with self.ctx.spans.span("restore"):
                arrays = await self.live[0].restore(step=None,
                                                    target=self.target)
            with self.ctx.spans.span("tensors_ready"):
                jax.block_until_ready(arrays)
        finally:
            for key, value in self._of(*self.live).items():
                self.totals[key] += value
            self.live = None
        return arrays, confirms

    def whole(self, arrays: dict) -> str | None:
        """What a result lacks of the host's share, or None when every
        tensor is there with its dtype, host shape and four shards."""
        for name, entry in self.table.items():
            got = arrays.get(name)
            if got is None:
                return f"lacks {name}"
            shape = ref.host_shape(entry)
            if str(got.dtype) != entry[0] or tuple(got.shape) != shape \
                    or len(got.addressable_shards) != len(self.ctx.devices):
                return (f"{name}: {got.dtype}{tuple(got.shape)} on "
                        f"{len(got.addressable_shards)} chips against "
                        f"{entry[0]}{shape}")
        return None

    # -------------------------------------------------------------- set-up

    def host_tree(self, shard: int, step: str) -> tuple[dict, dict]:
        """One shard file's arrays and their pieces, as its rank saves."""
        import jax.numpy as jnp

        from tpudfs.tpu.checkpoint import Piece

        cfg = self.ctx.cfg
        rank, names = ref.shards(cfg)[shard]
        tree, pieces = {}, {}
        for name in names:
            start, shape = ref.rank_pieces(cfg, rank)[name]
            dtype = self.table[name][0]
            tree[name] = np.frombuffer(
                ref.piece_bytes(self.ctx.seed, cfg, name, rank, step),
                dtype=jnp.dtype(dtype)).reshape(shape)
            pieces[name] = Piece(self.table[name][1], start)
        return tree, pieces

    async def write_dataset(self) -> float:
        """The published step through ``save`` with the pieces and, beside
        it, the torn step's shards through ``save_shard`` alone."""
        assumed = self.ctx.cfg["assumed"]
        mgr, _reader = self.manager()
        t0 = time.perf_counter()
        wanted = [(s, "published") for s in range(self.num_shards)] \
            + [(s, "torn") for s in assumed["torn_shards"]]
        made = await asyncio.gather(*(
            asyncio.to_thread(self.host_tree, s, step) for s, step in wanted))
        n = self.num_shards
        await asyncio.gather(
            mgr.save(self.step, {s: made[s][0] for s in range(n)},
                     pieces={s: made[s][1] for s in range(n)}),
            *(mgr.save_shard(assumed["torn_step"], s, tree, pieces=pieces)
              for (s, _step), (tree, pieces) in zip(wanted[n:], made[n:])))
        return time.perf_counter() - t0

    async def prepare(self) -> None:
        ctx = self.ctx
        # The restore's target before anything is written: a program that
        # cannot restore under another layout stops here.
        self.target = self.make_target(ctx.devices)
        self.client = ctx.bringup.client(
            ctx.rpc, local_reads=self.mix["local_reads"])
        self.dataset_write_s = await self.write_dataset()
        mgr, reader = self.manager()
        await asyncio.to_thread(reader.warm_batches,
                                ctx.cfg["block_bytes"] // 512)
        await mgr.warm_restore(target=self.target)
        await self.warm_per_block_path(reader)
        arrays, _confirms = await self.restore()
        lacks = self.whole(arrays)
        if lacks:
            raise RuntimeError(f"warm-up restore: {lacks}")

    async def warm_per_block_path(self, reader) -> None:
        """The base kind's, on every chip: a block that falls out of its
        round is read and checked on the chip the plan gave it."""
        from benchmarks import reference_ckpt

        meta = await self.client.get_file_info(
            reference_ckpt.shard_path(self.base, self.step, 0))
        for device in self.ctx.devices:
            one = await reader.read_block_to_device(
                meta["blocks"][0], device, verify="lazy", safe_local=True)
            await reader.confirm([one])
        n = 1
        while n <= max(-(-p // self.ctx.cfg["block_bytes"])
                       for p in self.payloads):
            reader.warm_confirm(one, n)
            n <<= 1

    # -------------------------------------------------------------- window

    async def window(self, seconds: float, on_close):
        async def one_op(c: int, k: int):
            arrays, confirms = await self.restore()
            lacks = self.whole(arrays)
            if lacks:
                raise RuntimeError(f"restore {k}: {lacks}")
            self.resident.append((arrays, confirms))
            return self.payload_bytes, k

        return await harness.closed_loop(self.mix["clients"], seconds,
                                         one_op, on_close)

    # --------------------------------------------------------------- check

    def sample(self) -> list[str]:
        """``check_tensors`` names from the seed, plus a row split, a
        column split, a 2-chip duplicate (row and column halves) and a
        replicated tensor in bf16 and f32, and ``step``."""
        cfg = self.ctx.cfg
        names = sorted(self.table)
        picked = {names[i] for i in self.ctx.rng.choice(
            len(names), min(self.mix["check_tensors"], len(names)),
            replace=False).tolist()}
        kinds = {}
        for name in names:
            spec = tuple(ref.spec_of(cfg, name))
            kind = (ref.is_expert(cfg, name), spec)
            kinds.setdefault((kind, self.table[name][0]), name)
        picked.update(kinds.values())
        return sorted(picked)

    def device_shards(self, arrays: dict, expect: harness.Expect) -> None:
        expect.compared["device_tensors"] = 0
        expect.compared["device_shards"] = 0
        for name in self.sample():
            expect.compared["device_tensors"] += 1
            got = arrays.get(name)
            dtype = self.table[name][0]
            if got is None or str(got.dtype) != dtype:
                expect.wrong("device_blocks_missing",
                             f"{name}: {None if got is None else got.dtype}"
                             f" against {dtype}")
                continue
            held = {s.device: s.data for s in got.addressable_shards}
            want = ref.device_shards(self.ctx.seed, self.ctx.cfg, name)
            for chip, device in enumerate(self.ctx.devices):
                expect.compared["device_shards"] += 1
                _dt, shape, data = want[chip]
                part = held.get(device)
                if part is None or tuple(part.shape) != shape:
                    expect.wrong("device_blocks_missing",
                                 f"{name} chip {chip}: "
                                 f"{None if part is None else part.shape} "
                                 f"against {shape}")
                    continue
                bits = np.asarray(part).reshape(-1).view(np.uint8)
                if not np.array_equal(bits, np.frombuffer(data, np.uint8)):
                    expect.wrong("device_bytes_wrong", f"{name} chip {chip}")

    async def check(self, ops, expect: harness.Expect) -> None:
        if not self.resident:
            expect.wrong("device_blocks_missing", "the window left nothing")
            return
        arrays, confirms = self.resident[-1]
        if sum(n for n, _bad in confirms) != self.blocks \
                or any(bad for _n, bad in confirms):
            expect.wrong("device_blocks_unverified",
                         f"confirms {confirms} against {self.blocks} blocks "
                         f"of {self.num_shards} shard files")
        await asyncio.to_thread(self.device_shards, arrays, expect)
        await self.published(expect)
        per_shard = max(1, self.mix["check_replica_blocks"]
                        // self.num_shards)
        for shard in range(self.num_shards):
            data = await asyncio.to_thread(
                ref.shard_payload, self.ctx.seed, self.ctx.cfg, shard)
            meta = await expect.metadata(
                self.client, reference_ckpt.shard_path(
                    self.base, self.step, shard), data)
            if meta is not None:
                nblocks = len(meta["blocks"])
                await expect.replicas(meta, data, sorted(self.ctx.rng.choice(
                    nblocks, min(per_shard, nblocks),
                    replace=False).tolist()))


# ------------------------------------------------------------- the control


class SwappedModelHalves(sabotage.Sabotage):
    """The restore is handed a mesh whose ``model`` halves are exchanged
    (chips (e, 0) and (e, 1) trade places), warmed like the real one: each
    chip holds its neighbour's half of every split tensor, a result that
    is whole, typed and sharded, and wrong. Breaks "every device shard is
    bit-identical to the slice the target sharding gives its chip"."""

    async def after_prepare(self) -> None:
        traffic = self.traffic
        axes = self.ctx.cfg["target"]["mesh"]
        grid = np.array(self.ctx.devices, dtype=object).reshape(
            tuple(axes.values()))
        swapped = np.flip(grid, axis=list(axes).index("model"))
        traffic.target = traffic.make_target(list(swapped.flat))
        mgr, _reader = traffic.manager()
        await mgr.warm_restore(target=traffic.target)
        await traffic.restore()


sabotage.CONTROLS[KIND] = SwappedModelHalves
