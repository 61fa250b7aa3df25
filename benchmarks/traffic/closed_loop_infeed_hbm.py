"""One consumer (a training step's input side), closed loop: it takes the
next batch from the program's Grain pipeline as soon as the last one's
digest is dispatched. The pipeline is the program's own, nothing of it is
assembled here:

    source = DfsWdsSource(masters, shards, client_kwargs=...)
    ds = make_dataset(source, decode=decode, batch_size=B,
                      shuffle_seed=seed, num_epochs=None)
    batches = device_iterator(ds, devices=[chip])

``decode`` is ``wds.decode_sample(dtype="uint8")`` with the sample's key kept
beside it (a job that logs sample ids), so a batch is ``(B, L) uint8``,
``(B,) int32`` labels and ``(B,) int32`` keys, and every record can be held
to the reference for the key it came with. The source has its own
``Client`` (``local_reads`` as the mix says: the trainer is not a storage
host) on the loop thread the program gives it; the prefetch is Grain's
``ReadOptions`` default, the hand-off to the device the program's.

One operation is one batch: from the consumer's ``next()`` to the batch's
digest ready on the device (a second thread waits for it, so the consumer
does not). Its bytes are the payload, records and labels (no tar framing,
no keys). The step on the device is one jitted program a batch,
``infeed_digest``: the reference's two ``uint32`` of each of the B rows, in
integers, the first read of the batch on the chip. Digests, labels and keys
of every batch stay on the device until the window closes; the last
``keep_resident`` batches stay whole.

Set-up writes the dataset through ``wds.write_wds_shards`` (one call a
shard, side by side) from the reference's samples, builds the source (the
tar-header walk), and takes ``warm_batches`` batches through the timed
entry: the batch's and the digest's shapes, every connection, and a
pipeline that is full when the window opens, as it is when it closes.
Then it freezes the heap (``gc.collect(); gc.freeze()``), as
docs/operations.md "A trainer's heap" tells the process that owns the
pipeline to: unfrozen, each full pass of the collector over what set-up
built stalled every fetch in flight.

The check, limit 0 each, under the harness's count names: every record
delivered since the pipeline started (ALL of them, D2H of digests, labels
and keys) has the reference's digest and label for its key
(``device_bytes_wrong``); every batch is ``(B, L) uint8``, and every epoch
that completed handed over each key exactly once
(``device_blocks_missing``); no two epochs in the same order
(``device_blocks_unverified``: the order is not what the configuration
says); the resident batches byte for byte after D2H
(``device_bytes_wrong``); and ``check_shards`` shard files, drawn from the
seed, against the reference's tar bytes through ``harness.Expect.metadata``
+ ``replicas``.

Mix parameters: local_reads, warm_batches, keep_resident, check_shards,
check_replica_blocks.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import gc
import itertools
import queue
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import harness, reference_wds, sabotage
from benchmarks.spans import CURRENT_OP
# ``HANDOFF_DEPTH`` came with the supported pipeline: a program without it
# cannot run this kind, and says so here, at import.
from tpudfs.tpu.grain_infeed import (HANDOFF_DEPTH,  # noqa: F401
                                     device_iterator, make_dataset)
from tpudfs.tpu.wds import DfsWdsSource, decode_sample, write_wds_shards

KIND = "closed_loop_infeed_hbm"


def decode(sample: dict):
    x, y = decode_sample(sample, dtype="uint8")
    return x, y, np.int32(int(sample["__key__"]))


@jax.jit
def infeed_digest(x):
    """``reference_wds.digest`` of every row of ``(B, L) uint8``, from the
    bytes: word ``j`` is ``sum(b[4j + k] << 8k)``, so ``(j + 1) * w[j]`` is
    the wrapping sum of ``b[i] * (((i >> 2) + 1) << 8 (i & 3))``."""
    i = jax.lax.iota(jnp.uint32, x.shape[1])
    shift = (i & 3) << 3
    weight = ((i >> 2) + 1) << shift
    b = x.astype(jnp.uint32)
    total = jnp.sum(b * weight[None, :], axis=1, dtype=jnp.uint32)
    fold = jax.lax.reduce(b << shift[None, :], np.uint32(0),
                          jax.lax.bitwise_xor, (1,))
    return jnp.stack([total, fold], axis=1)


class Traffic:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.mix = ctx.mix
        ds = ctx.cfg["dataset"]
        self.batch = ds["batch_size"]
        self.record_bytes = ds["record_bytes"]
        self.samples = reference_wds.samples(ctx.cfg)
        self.paths = [reference_wds.shard_path(ctx.cfg, s)
                      for s in range(ds["shards"])]
        #: records and labels of one batch
        self.payload_bytes = self.batch * (self.record_bytes + 4)
        self.client = None
        self.source = None
        self.batches = None
        self.dataset_write_s = 0.0
        self.index_s = 0.0
        #: per batch since the pipeline started, on the device:
        #: (shape and dtype as they should be, labels, keys, digests)
        self.delivered: list = []
        self.resident = collections.deque(maxlen=self.mix["keep_resident"])

    def counters(self) -> dict:
        """``DfsSourceBase.stats()`` as ``infeed.<name>`` (the harness asks
        after ``prepare``)."""
        return {f"infeed.{name}": value
                for name, value in self.source.stats().items()}

    # ------------------------------------------------------ the timed entry

    def take(self):
        """The timed entry: the pipeline's next batch, on the device."""
        with self.ctx.spans.span("next_batch"):
            return next(self.batches)

    def step(self, batch):
        """The step: the digest's dispatch; the batch is kept."""
        x, y, key = batch
        with self.ctx.spans.span("digest_dispatch"):
            digests = infeed_digest(x)
        self.delivered.append((
            x.shape == (self.batch, self.record_bytes)
            and x.dtype == jnp.uint8, y, key, digests))
        self.resident.append(batch)
        return digests

    # -------------------------------------------------------------- set-up

    async def write_dataset(self) -> float:
        ctx = self.ctx
        prefix = ctx.cfg["dataset"]["prefix"]

        async def put(shard: int) -> list[str]:
            samples = await asyncio.to_thread(
                list, reference_wds.shard_samples(ctx.seed, ctx.cfg, shard))
            return await write_wds_shards(
                self.client, f"{prefix}-{shard:02d}", samples,
                shard_size_bytes=1 << 40)

        t0 = time.perf_counter()
        written = await asyncio.gather(*(put(s)
                                         for s in range(len(self.paths))))
        if [p for paths in written for p in paths] != self.paths:
            raise RuntimeError(f"shards written as {written}, the "
                               f"reference says {self.paths}")
        return time.perf_counter() - t0

    def pipeline(self) -> None:
        """The program's entry points, in a worker thread (the index walk
        blocks on the source's own loop)."""
        ctx = self.ctx
        t0 = time.perf_counter()
        self.source = DfsWdsSource(
            self.client.master_addrs, self.paths,
            client_kwargs={"config_addrs": self.client.config_addrs,
                           "block_size": ctx.cfg["block_bytes"],
                           "local_reads": self.mix["local_reads"]})
        self.index_s = time.perf_counter() - t0
        if len(self.source) != self.samples:
            raise RuntimeError(f"the index holds {len(self.source)} "
                               f"samples of {self.samples}")
        ds = make_dataset(self.source, decode=decode, batch_size=self.batch,
                          shuffle_seed=ctx.seed, num_epochs=None)
        self.batches = device_iterator(ds, devices=[ctx.device])
        t1 = time.perf_counter()
        for _ in range(self.mix["warm_batches"]):
            jax.block_until_ready(self.step(self.take()))
        # Set-up readings the line has no place for.
        print(f"benchmark: dataset write {self.dataset_write_s:.2f} s, index "
              f"walk {self.index_s:.2f} s, {self.mix['warm_batches']} warm "
              f"batches {time.perf_counter() - t1:.2f} s", file=sys.stderr)

    async def prepare(self) -> None:
        ctx = self.ctx
        self.client = ctx.bringup.client(
            ctx.rpc, local_reads=self.mix["local_reads"])
        self.dataset_write_s = await self.write_dataset()
        await asyncio.to_thread(self.pipeline)
        # The operator's guidance for the process that owns the pipeline
        # (docs/operations.md "A trainer's heap"), followed as a trainer
        # would: what set-up left on the heap (JAX, Grain, the index: ~1M
        # objects) is old and stays, so the collector's generation-2 passes
        # in the window walk what the window allocated and no more.
        # Unfrozen, each pass held the GIL 56-69 ms, twice a window, and
        # every fetch in flight with it (PERF.md section 6, PR 33).
        gc.collect()
        gc.freeze()

    # -------------------------------------------------------------- window

    def consume(self, deadline: float, ops: list) -> None:
        """The consumer's thread; a second one stamps each operation's end
        when its digest is ready, so the consumer never waits for the
        device."""
        ready: queue.Queue = queue.Queue()

        def stamp() -> None:
            while (item := ready.get()) is not None:
                start, op_id, digests = item
                jax.block_until_ready(digests)
                ops.append(harness.Op(start, time.perf_counter(), True,
                                      self.payload_bytes, (op_id, None)))

        stamper = threading.Thread(target=stamp, name="bench-digest-ready")
        stamper.start()
        try:
            for op_id in itertools.count():
                if time.perf_counter() >= deadline:
                    break
                CURRENT_OP.set(op_id)
                start = time.perf_counter()
                try:
                    digests = self.step(self.take())
                except Exception as e:  # counted; the pipeline is over
                    print(f"benchmark: operation failed: {e!r}",
                          file=sys.stderr)
                    ops.append(harness.Op(start, time.perf_counter(), False,
                                          0, (op_id, None)))
                    break
                ready.put((start, op_id, digests))
        finally:
            ready.put(None)
            stamper.join()

    async def window(self, seconds: float, on_close):
        ops: list[harness.Op] = []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        consumer = asyncio.create_task(
            asyncio.to_thread(self.consume, deadline, ops))
        await asyncio.sleep(max(0.0, deadline - time.perf_counter()))
        if on_close is not None:
            await on_close()
        await consumer
        return ops, t0, deadline

    def end_to_end(self, ops, t0: float, t1: float) -> dict:
        return {"hbm_read_GBps": harness.rate(ops, t0, t1) / 1e9,
                "read_p95_ms": harness.p95_ms(ops)}

    # --------------------------------------------------------------- check

    def records(self, expect: harness.Expect) -> None:
        """Every record delivered: digest and label against its key's, the
        batches' shapes, the epochs."""
        seed, cfg = self.ctx.seed, self.ctx.cfg
        n = self.samples
        misshapen = sum(not ok for ok, _y, _k, _d in self.delivered)
        if misshapen:
            expect.wrong("device_blocks_missing",
                         f"{misshapen} batches not ({self.batch}, "
                         f"{self.record_bytes}) uint8", misshapen)
        labels = np.concatenate([np.asarray(y).reshape(-1)
                                 for _ok, y, _k, _d in self.delivered])
        keys = np.concatenate([np.asarray(k).reshape(-1)
                               for _ok, _y, k, _d in self.delivered])
        got = np.concatenate([np.asarray(d).reshape(-1, 2)
                              for _ok, _y, _k, d in self.delivered])
        expect.compared["records"] = len(keys)
        known = (keys >= 0) & (keys < n)
        at = np.where(known, keys, 0)
        bad = ~known | (labels != reference_wds.labels(seed, cfg)[at]) \
            | (got != reference_wds.digests(seed, cfg)[at]).any(axis=1)
        if bad.any():
            first = int(np.flatnonzero(bad)[0])
            expect.wrong("device_bytes_wrong",
                         f"{int(bad.sum())} of {len(keys)} records; the "
                         f"first is delivery {first}, key {int(keys[first])}",
                         int(bad.sum()))
        epochs = [keys[e * n:(e + 1) * n] for e in range(len(keys) // n)]
        expect.compared["epochs"] = len(epochs)
        for e, epoch in enumerate(epochs):
            faults = reference_wds.epoch_faults(epoch, n)
            if faults:
                expect.wrong("device_blocks_missing",
                             f"epoch {e}: {faults} keys not exactly once",
                             faults)
        repeats = reference_wds.same_order(epochs)
        if repeats:
            expect.wrong("device_blocks_unverified",
                         f"{repeats} epochs in an earlier epoch's order",
                         repeats)

    def resident_bytes(self, expect: harness.Expect) -> None:
        """The batches left whole on the device, byte for byte."""
        seed, cfg = self.ctx.seed, self.ctx.cfg
        expect.compared["resident_records"] = 0
        for x, _y, key in self.resident:
            rows, keys = np.asarray(x), np.asarray(key).reshape(-1)
            expect.compared["resident_records"] += len(keys)
            wrong = sum(
                not 0 <= k < self.samples
                or row.tobytes() != reference_wds.image(seed, cfg, int(k))
                for row, k in zip(rows, keys))
            if wrong or len(rows) != len(keys):
                expect.wrong("device_bytes_wrong",
                             f"{wrong} rows of a resident batch",
                             max(wrong, 1))

    async def check(self, ops, expect: harness.Expect) -> None:
        ctx = self.ctx
        if not self.delivered or not self.resident:
            expect.wrong("device_blocks_missing", "the window left nothing")
            return
        await asyncio.to_thread(self.records, expect)
        await asyncio.to_thread(self.resident_bytes, expect)
        shards = sorted(ctx.rng.choice(
            len(self.paths), min(self.mix["check_shards"], len(self.paths)),
            replace=False).tolist())
        per_shard = max(1, self.mix["check_replica_blocks"] // len(shards))
        for shard in shards:
            data = await asyncio.to_thread(
                reference_wds.shard_tar, ctx.seed, ctx.cfg, shard)
            meta = await expect.metadata(self.client, self.paths[shard],
                                         data)
            if meta is not None:
                nblocks = len(meta["blocks"])
                await expect.replicas(meta, data, sorted(ctx.rng.choice(
                    nblocks, min(per_shard, nblocks),
                    replace=False).tolist()))

    async def close(self) -> None:
        gc.unfreeze()
        self.resident.clear()
        self.delivered.clear()
        if self.batches is not None:
            # A consumer that died inside ``next`` still holds the generator.
            with contextlib.suppress(ValueError):
                self.batches.close()
        if self.source is not None:
            await asyncio.to_thread(self.source.close)
        if self.client is not None:
            await self.client.close()


# ------------------------------------------------------------- the control


class RowsExchangedAfterFetch(sabotage.Sabotage):
    """The first two records of every batch exchange their bytes after the
    fetch, on the device, while labels and keys stay where they were: what
    a batch assembled by a wrong index would look like. No server, no CRC
    and nothing in the program can tell (each row is a record that was
    written); the reference's digest for the key a row came with does.
    Breaks "a sample's bytes, key and label belong together"."""

    async def after_prepare(self) -> None:
        traffic = self.traffic

        @jax.jit
        def exchange(x):
            return x.at[:2].set(x[1::-1])

        # Compiled here, in set-up: the window itself compiles nothing.
        jax.block_until_ready(exchange(traffic.resident[-1][0]))
        inner = traffic.take

        def take():
            x, y, key = inner()
            return exchange(x), y, key

        traffic.take = take


sabotage.CONTROLS[KIND] = RowsExchangedAfterFetch
