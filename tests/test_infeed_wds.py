"""The supported Grain pipeline over WebDataset shards, held to the plain
reference (``benchmarks/reference_wds.py``) at a small size on the CPU:
``DfsWdsSource`` -> ``make_dataset(decode=...)`` -> ``device_iterator``.

3 shards x 40 samples of 10 000 B in 64 KiB blocks (a sample is 11 776 B of
tar, so about one record in six straddles two blocks), 1 master + 3
chunkservers in this process. All Grain work runs in a worker thread: the
cluster serves on the test's own event loop, which must stay free.
"""

from __future__ import annotations

import asyncio
import contextlib
import threading

import jax
import numpy as np
import pytest

from benchmarks import reference_wds
from tests.test_master_service import MiniCluster
from tpudfs.client.client import Client, OverloadedError
from tpudfs.tpu import grain_infeed as gi
from tpudfs.tpu.wds import DfsWdsSource, decode_sample, write_wds_shards

pytest.importorskip("grain")

SEED = 2**31 + 4242
CFG = {"dataset": {"prefix": "/wds/train", "shards": 3,
                   "samples_per_shard": 40, "record_bytes": 10_000,
                   "classes": 1000}}
N = reference_wds.samples(CFG)
L = CFG["dataset"]["record_bytes"]
BLOCK = 64 * 1024


def infeed_threads() -> list[str]:
    """Live threads of the pipeline: the source's client loop, the
    ``device_iterator``'s thread, Grain's prefetch pool."""
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith(("tpudfs-", "grain-")))


@pytest.fixture(autouse=True)
def no_infeed_thread_outlives_its_test():
    """A test that leaves one behind would load every test after it in its
    worker (the wall-clock tests of ROADMAP D12 among them)."""
    yield
    assert infeed_threads() == []


def decode(sample: dict):
    """``decode_sample`` with the key kept, as a job that logs sample ids."""
    x, y = decode_sample(sample, dtype="uint8")
    return x, y, np.int32(int(sample["__key__"]))


@contextlib.asynccontextmanager
async def dataset_on_cluster(tmp_path):
    """``(cluster, client, shard paths)``: the reference's samples written
    through ``write_wds_shards``, one call a shard."""
    c = MiniCluster(tmp_path, n_masters=1, n_cs=3)
    await c.start()
    try:
        await c.wait_out_of_safe_mode(await c.leader())
        client = Client(list(c.masters), rpc_client=c.client,
                        block_size=BLOCK)
        shards = []
        for shard in range(CFG["dataset"]["shards"]):
            shards += await write_wds_shards(
                client, f"{CFG['dataset']['prefix']}-{shard:02d}",
                reference_wds.shard_samples(SEED, CFG, shard),
                shard_size_bytes=1 << 30)
        yield c, client, shards
    finally:
        await c.stop()


async def in_thread(c, shards, fn, **source_kw):
    """``fn(source)`` in a worker thread, the source closed behind it."""
    def run():
        source = DfsWdsSource(list(c.masters), shards, **source_kw)
        try:
            return fn(source)
        finally:
            source.close()

    return await asyncio.to_thread(run)


def epochs_of(source, seed: int, batch: int = 8, epochs: int = 2) -> list:
    ds = gi.make_dataset(source, batch_size=batch, shuffle_seed=seed,
                         num_epochs=epochs, decode=decode)
    return [tuple(np.asarray(leaf) for leaf in b) for b in ds]


async def test_the_reference_tar_is_what_write_wds_shards_put(tmp_path):
    async with dataset_on_cluster(tmp_path) as (_c, client, shards):
        assert shards == [reference_wds.shard_path(CFG, s) for s in range(3)]
        for shard, path in enumerate(shards):
            want = reference_wds.shard_tar(SEED, CFG, shard)
            assert len(want) == -(-(40 * 11_776 + 1024) // 10_240) * 10_240
            assert await client.get_file(path) == want


async def test_two_epochs_each_exactly_once_in_an_order_of_the_seed(tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            assert len(source) == N
            return (epochs_of(source, 7), epochs_of(source, 7),
                    epochs_of(source, 8))

        first, again, other = await in_thread(c, shards, run)
    keys = np.concatenate([b[2] for b in first])
    assert len(keys) == 2 * N  # 8 divides 120: nothing dropped
    e0, e1 = keys[:N], keys[N:]
    assert reference_wds.epoch_faults(e0, N) == 0
    assert reference_wds.epoch_faults(e1, N) == 0
    assert reference_wds.same_order([e0, e1]) == 0  # reseeded each epoch
    assert not np.array_equal(e0, np.arange(N))  # and shuffled at all
    assert np.array_equal(keys, np.concatenate([b[2] for b in again]))
    assert not np.array_equal(keys, np.concatenate([b[2] for b in other]))
    # The reference's own yardstick tells a lost and a doubled key.
    assert reference_wds.epoch_faults(np.r_[e0[:-1], e0[0]], N) == 2
    assert reference_wds.same_order([e0, e1, e0]) == 1


async def test_every_record_keeps_its_bytes_key_and_label(tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        batches = await in_thread(
            c, shards, lambda source: epochs_of(source, 11, batch=16,
                                                epochs=1))
    assert len(batches) == N // 16  # 120 = 7 x 16 + 8: the rest dropped
    labels = reference_wds.labels(SEED, CFG)
    digests = reference_wds.digests(SEED, CFG)
    for x, y, key in batches:
        assert x.shape == (16, L) and x.dtype == np.uint8
        assert y.shape == (16,) and y.dtype == np.int32
        assert np.array_equal(y, labels[key])
        assert np.array_equal(reference_wds.digest(x), digests[key])
        for row, k in zip(x, key):
            assert row.tobytes() == reference_wds.image(SEED, CFG, int(k))


async def test_batches_cross_the_epoch_end(tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        batches = await in_thread(
            c, shards, lambda source: epochs_of(source, 3, batch=32))
    keys = np.concatenate([b[2] for b in batches])
    assert len(batches) == 2 * N // 32 and len(keys) == 224
    assert reference_wds.epoch_faults(keys[:N], N) == 0
    # Batch 3 holds the last 24 keys of epoch 0 and the first 8 of epoch 1.
    assert len(set(keys[N:].tolist())) == 224 - N


async def test_prefetch_is_on_and_the_gate_holds(tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            assert source.stats() == {
                "records": 0, "bytes": 0, "hop_ns": 0, "loop_ns": 0,
                "range_reads": 0, "range_frames": 0,
                "max_in_flight": 1,  # the metadata fetch of the index
                "sheds": 0, "governor_level": 0}
            epochs_of(source, 5, epochs=1)
            return source.stats()

        stats = await in_thread(c, shards, run)
    assert stats["records"] == N
    # A sample's read runs from its .img data to the end of its .cls data.
    assert N * (10_240 + 512) < stats["bytes"] <= N * (10_240 + 512 + 3)
    # What the client issued for the records (the index walk's reads are
    # the ``infeed.index`` span's): some straddle a block, none spans 3.
    assert N < stats["range_reads"] <= 2 * N
    assert stats["range_frames"] == 0  # a record is ReadBlocks of its own
    assert stats["hop_ns"] > 0 and stats["loop_ns"] > 0
    assert 1 < stats["max_in_flight"] <= 16
    assert stats["sheds"] == 0 and stats["governor_level"] == 0


async def test_a_shed_fetch_on_the_wds_source_steps_the_governor(tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            client = source._client_loop().client
            client.hedge_delay = 0.05
            inner = client.read_meta_range
            shed = iter([True])

            async def sheds_once(meta, offset, length):
                if next(shed, False):
                    raise OverloadedError("shed by cs: Overloaded|0.1|limit")
                return await inner(meta, offset, length)

            client.read_meta_range = sheds_once
            sample = source[17]
            return sample, source.stats(), client.hedge_delay

        sample, stats, hedge = await in_thread(c, shards, run)
    assert sample["__key__"] == reference_wds.name(17)
    assert sample["img"] == reference_wds.image(SEED, CFG, 17)
    assert stats["sheds"] == 1 and stats["governor_level"] == 1
    assert hedge is None  # the ladder's first rung: hedges off
    assert stats["records"] == 1


async def test_range_reads_counts_every_read_block_the_client_sent(tmp_path):
    """Counted where a read is issued, not from the layout: a replica that
    fails costs a second ``ReadBlock``, and ``stats()`` says so."""
    import grpc

    from tpudfs.common.rpc import RpcError

    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            client = source._client_loop().client
            assert client.local_read_blocks == 0  # every read is a ReadBlock
            source[0]  # sample 0 lies inside block 0
            whole = source.stats()["range_reads"]
            inner, failed = client._data_call, []

            async def first_replica_down(addr, method, req, **kw):
                if method == "ReadBlock" and not failed:
                    failed.append(addr)
                    raise RpcError(grpc.StatusCode.UNAVAILABLE, "replica down")
                return await inner(addr, method, req, **kw)

            client._data_call = first_replica_down
            sample = source[0]
            return sample, whole, source.stats(), len(failed), source

        sample, whole, stats, failed, source = await in_thread(
            c, shards, run, client_kwargs={"local_reads": False})
    assert sample["img"] == reference_wds.image(SEED, CFG, 0)
    assert whole == 1 and failed == 1
    assert stats["range_reads"] == 3 and stats["records"] == 2
    assert source.stats() == stats  # a closed source still says it


async def test_a_fetch_splits_into_the_hop_and_the_time_on_the_loop(
        tmp_path):
    """``hop_ns``: a fetch's hand-off from its thread to its first step on
    the client's loop; ``loop_ns``: from there to the read's return. A
    read held 50 ms on the loop shows in the second; a loop kept busy
    50 ms before the fetch's first step shows in the first."""
    import time

    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            cl = source._client_loop()
            client = cl.client
            inner = client.read_meta_range

            async def slow(meta, offset, length):
                await asyncio.sleep(0.05)
                return await inner(meta, offset, length)

            client.read_meta_range = slow
            source[3]
            slow_read = source.stats()
            client.read_meta_range = inner
            cl._loop.call_soon_threadsafe(time.sleep, 0.05)  # the loop busy
            source[4]
            return slow_read, source.stats()

        slow_read, busy_loop = await in_thread(c, shards, run)
    assert slow_read["records"] == 1 and slow_read["loop_ns"] >= 50e6
    assert busy_loop["records"] == 2
    assert busy_loop["hop_ns"] - slow_read["hop_ns"] >= 40e6


async def test_range_frames_counts_the_read_blocks_frames_sent(tmp_path):
    """Frames are counted where they are sent, apart from ``range_reads``:
    the index walk's are left out of both."""
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            cl = source._client_loop()
            before = source.stats()
            meta = source._metas[shards[0]]
            ids = [b["block_id"] for b in meta["blocks"][:2]]
            addr = meta["blocks"][0]["locations"][0]
            resp = cl.run(cl.client._data_call(
                addr, "ReadBlocks", {"block_ids": ids}, timeout=30.0))
            return before, resp, source.stats()

        before, resp, after = await in_thread(
            c, shards, run, client_kwargs={"local_reads": False})
    assert before["range_frames"] == 0 and after["range_frames"] == 1
    assert after["range_reads"] == before["range_reads"]
    assert len(resp["sizes"]) == 2


async def test_the_tenant_reaches_the_sources_client(tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        tenant = await in_thread(
            c, shards, lambda source: source._client_loop().client.tenant,
            tenant="trainer-7")
    assert tenant == "trainer-7"


async def test_device_iterator_lands_batches_and_stops_with_its_caller(
        tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            ds = gi.make_dataset(source, batch_size=8, shuffle_seed=9,
                                 num_epochs=None, decode=decode)
            taken = []
            batches = gi.device_iterator(ds, devices=jax.devices()[:1])
            for batch in batches:
                taken.append(batch)
                if len(taken) == 20:  # past the first epoch's 15 batches
                    break
            batches.close()
            return taken, [t.name for t in threading.enumerate()]

        taken, threads = await in_thread(c, shards, run)
    assert "tpudfs-infeed-put" not in threads  # stopped with the generator
    assert all(isinstance(leaf, jax.Array) and leaf.is_fully_addressable
               for batch in taken for leaf in batch)
    keys = np.concatenate([np.asarray(b[2]) for b in taken])
    assert reference_wds.epoch_faults(keys[:N], N) == 0
    labels = reference_wds.labels(SEED, CFG)
    assert all(np.array_equal(np.asarray(b[1]), labels[np.asarray(b[2])])
               for b in taken)


async def test_a_failing_fetch_reaches_the_consumer_of_device_iterator(
        tmp_path):
    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def run(source):
            def broken(sample):
                raise ValueError("no decode")

            ds = gi.make_dataset(source, batch_size=8, decode=broken)
            with pytest.raises(ValueError, match="no decode"):
                next(gi.device_iterator(ds))

        await in_thread(c, shards, run)
