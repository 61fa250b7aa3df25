"""TPU data-plane layer on the virtual 8-device CPU mesh: kernel bit-exactness,
ICI chain replication with on-device verification, HBM reader against a live
cluster, infeed, and the driver graft entry points."""

import asyncio
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_master_service import MiniCluster
from tpudfs.client.client import Client, DfsError
from tpudfs.common.checksum import crc32c_chunks
from tpudfs.common.erasure import decode, encode
from tpudfs.tpu.crc32c_pallas import (
    bytes_to_words,
    crc32c_chunks_device,
    crc32c_chunks_jax,
)
from tpudfs.tpu.hbm_reader import HbmReader, device_array_to_bytes
from tpudfs.tpu.ici_replication import IciReplicator, make_mesh, replicated_write_step
from tpudfs.tpu.infeed import DfsInfeed
from tpudfs.tpu.rs_pallas import rs_encode_jax


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


# ---------------------------------------------------------------- kernels


@pytest.mark.parametrize("n", [512, 4096, 100_000, 1 << 20])
def test_crc_kernel_bit_exact(n):
    data = _rand(n, seed=n)
    want = crc32c_chunks(data + b"\x00" * (-n % 512))  # padded layout
    np.testing.assert_array_equal(crc32c_chunks_jax(data, use_pallas=False), want)
    np.testing.assert_array_equal(crc32c_chunks_jax(data, use_pallas=True), want)


@pytest.mark.parametrize("k,m", [(4, 2), (6, 3)])
def test_rs_kernel_bit_exact(k, m):
    data = _rand(100_000, seed=1)
    want = encode(data, k, m)
    assert rs_encode_jax(data, k, m, use_pallas=False) == want
    assert rs_encode_jax(data, k, m, use_pallas=True) == want
    # Device parities decode with the host decoder after losses.
    shards: list[bytes | None] = list(rs_encode_jax(data, k, m))
    shards[0] = None
    shards[k] = None
    assert decode(shards, k, m, len(data)) == data


# ------------------------------------------------------------ ICI chain


def test_ici_chain_replication_layout():
    mesh = make_mesh(jax.devices()[:4])
    rep = IciReplicator(mesh, replication=3)
    chunks_per_host = 2
    data = _rand(4 * chunks_per_host * 512, seed=2)
    words = jnp.asarray(bytes_to_words(data))
    crcs = jnp.asarray(crc32c_chunks(data).astype(np.uint32))
    sharding = rep.sharding()
    words = jax.device_put(words, sharding)
    crcs = jax.device_put(crcs, sharding)
    replicas, ok, acks = rep.replicate(words, crcs)
    assert int(acks) == 4 and bool(jnp.all(ok))
    # Chain layout: host i holds shard groups of hosts i, i-1, i-2.
    rep_np = np.asarray(replicas).reshape(4, 3, chunks_per_host, 128)
    src = np.asarray(words).reshape(4, chunks_per_host, 128)
    for host in range(4):
        for r in range(3):
            np.testing.assert_array_equal(
                rep_np[host, r], src[(host - r) % 4],
                err_msg=f"host {host} replica {r}",
            )


def test_pod_mesh_2d_chain_and_ec_ride_ici_axis():
    """Multi-host pod layout: a (dcn, ici) 2-D mesh where the replication
    chain and the EC scatter/degraded gather ride the LAST (ici) axis and
    the dcn axis carries independent data-parallel write groups — DCN
    never moves block bytes (reference multi-host scaling via NCCL/MPI,
    re-expressed as mesh axes)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudfs.tpu.ici_replication import (
        EcShardGather, EcShardScatter, IciReplicator,
    )

    devs = jax.devices()[:8]
    n_dcn, n_ici = 2, 4
    mesh = Mesh(np.array(devs).reshape(n_dcn, n_ici), ("dcn", "ici"))
    C = 2  # chunks per host
    rng = np.random.default_rng(33)
    blocks = [rng.integers(0, 256, C * 512, dtype=np.uint8).tobytes()
              for _ in range(8)]
    data = b"".join(blocks)
    words = jnp.asarray(bytes_to_words(data))
    crcs = jnp.asarray(crc32c_chunks(data).astype(np.uint32))
    sharding = NamedSharding(mesh, P(("dcn", "ici")))
    words = jax.device_put(words, sharding)
    crcs = jax.device_put(crcs, sharding)

    # 3x chain per dcn row: host (a, b) must hold rows (a, b-r % n_ici) —
    # the chain never crosses the dcn axis.
    rep = IciReplicator(mesh, replication=3, axis="ici")
    replicas, ok, acks = rep.replicate(words, crcs)
    assert int(acks) == 8 and bool(jnp.all(ok))
    rep_np = np.asarray(replicas).reshape(n_dcn, n_ici, 3, C, 128)
    src = np.asarray(words).reshape(n_dcn, n_ici, C, 128)
    for a in range(n_dcn):
        for b in range(n_ici):
            for r in range(3):
                np.testing.assert_array_equal(
                    rep_np[a, b, r], src[a, (b - r) % n_ici],
                    err_msg=f"group {a} host {b} replica {r}",
                )

    # EC(2,2) scatter + degraded gather per row; ring position 1 of EVERY
    # dcn group serves garbage and each host still reconstructs its data.
    k, m = 2, 2
    scatter = EcShardScatter(mesh, k, m, axis="ici")
    shards, ec_ok, ec_acks = scatter.scatter(words)
    assert int(ec_acks) == 8 and bool(np.asarray(ec_ok).all())
    broken = np.asarray(shards).copy().reshape(n_dcn, n_ici, k + m, -1, 128)
    broken[:, 1] = 0xCD
    gather = EcShardGather(mesh, k, m, axis="ici")
    recon = np.asarray(gather.gather(
        jax.device_put(jnp.asarray(broken.reshape(shards.shape)), sharding),
        failed=1,
    ))
    per = -(-(C * 512) // k)
    shard_len_b = -(-per // 512) * 512
    recon = recon.reshape(8, k, -1)
    for i in range(8):
        got = b"".join(
            recon[i, r].astype("<u4").tobytes()[:shard_len_b]
            for r in range(k)
        )[:C * 512]
        assert got == blocks[i], f"host {i} degraded reconstruction"


def test_pod_mesh_size1_ring_axis_rejected():
    """A multi-device mesh whose ring axis has size 1 must raise, not
    silently produce zero redundancy (self-ppermute 'replicas') or decode
    a codeword entirely from the 'failed' device's shards."""
    from jax.sharding import Mesh

    from tpudfs.tpu.ici_replication import (
        EcShardGather, EcShardScatter, IciReplicator,
    )

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(4, 1), ("dcn", "ici"))
    with pytest.raises(ValueError):
        IciReplicator(mesh, replication=3, axis="ici")
    with pytest.raises(ValueError):
        EcShardScatter(mesh, 2, 1, axis="ici")
    with pytest.raises(ValueError):
        EcShardGather(mesh, 2, 1, axis="ici")
    # And the ring axis must be the LAST mesh axis.
    mesh2 = Mesh(np.array(devs).reshape(2, 2), ("ici", "dcn"))
    with pytest.raises(ValueError):
        IciReplicator(mesh2, replication=2, axis="ici")


def test_ici_chain_detects_corruption():
    mesh = make_mesh(jax.devices()[:4])
    rep = IciReplicator(mesh, replication=3)
    data = _rand(4 * 512, seed=3)
    words = bytes_to_words(data)
    crcs = crc32c_chunks(data).astype(np.uint32)
    crcs[1] ^= 0xDEADBEEF  # poison host 1's expected checksum
    sharding = rep.sharding()
    w = jax.device_put(jnp.asarray(words), sharding)
    c = jax.device_put(jnp.asarray(crcs), sharding)
    replicas, ok, acks = rep.replicate(w, c)
    ok_np = np.asarray(ok)
    # Hosts 1, 2, 3 receive host 1's poisoned group along the chain.
    assert int(acks) == 1
    assert ok_np.tolist() == [True, False, False, False]


def test_replicated_write_step_with_parity():
    mesh = make_mesh(jax.devices()[:8])
    step = replicated_write_step(mesh, replication=3, ec=(6, 3))
    chunks_per_host = 6
    data = _rand(8 * chunks_per_host * 512, seed=4)
    words = jnp.asarray(bytes_to_words(data))
    crcs = jnp.asarray(crc32c_chunks(data).astype(np.uint32))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P("hosts"))
    out = step(jax.device_put(words, sharding), jax.device_put(crcs, sharding))
    assert int(out["acks"]) == 8
    # Per-host parity matches the host encoder applied to that host's bytes.
    host0 = data[: chunks_per_host * 512]
    expect = encode(host0, 6, 3)[6:]
    parity = np.asarray(out["parity"])[:3]
    got = [parity[i].tobytes() for i in range(3)]
    assert got == expect


# ------------------------------------------------------- reader + infeed


async def _cluster_with_files(tmp_path, files):
    c = MiniCluster(tmp_path, n_masters=1, n_cs=3)
    await c.start()
    leader = await c.leader()
    await c.wait_out_of_safe_mode(leader)
    client = Client(list(c.masters), rpc_client=c.client, block_size=64 * 1024)
    for path, data in files:
        await client.create_file(path, data)
    return c, client


async def test_hbm_reader_blocks_and_verify(tmp_path):
    data = _rand(200_000, seed=5)
    c, client = await _cluster_with_files(tmp_path, [("/t/a", data)])
    try:
        reader = HbmReader(client, jax.devices())
        blocks = await reader.read_file_to_device_blocks("/t/a")
        assert len(blocks) == 4  # 64KiB blocks
        assert all(b.verified for b in blocks)
        joined = b"".join(
            device_array_to_bytes(b.array, b.size) for b in blocks
        )
        assert joined == data
        # Blocks land round-robin on distinct devices.
        devs = [b.array.devices().pop() for b in blocks]
        assert len(set(devs)) == min(4, len(jax.devices()))
    finally:
        await c.stop()


async def test_hbm_reader_detects_tamper(tmp_path):
    data = _rand(4096, seed=6)
    c, client = await _cluster_with_files(tmp_path, [("/t/bad", data)])
    try:
        # Tamper with every replica AND its sidecar so the chunkservers serve
        # the corrupt bytes happily — only the end-to-end device check trips.
        meta = await client.get_file_info("/t/bad")
        bid = meta["blocks"][0]["block_id"]
        for cs in c.chunkservers:
            if cs.store.exists(bid):
                raw = bytearray(cs.store.read(bid))
                raw[100] ^= 0xFF
                cs.store.write(bid, bytes(raw))
                cs.invalidate_cached(bid)
        reader = HbmReader(client, jax.devices())
        with pytest.raises(DfsError) as ei:
            await reader.read_file_to_device_blocks("/t/bad")
        assert "on-device checksum mismatch" in str(ei.value)
    finally:
        await c.stop()


async def test_hbm_reader_sharded_array(tmp_path):
    data = _rand(8 * 64 * 1024, seed=7)  # exactly 8 blocks of 64KiB
    c, client = await _cluster_with_files(tmp_path, [("/t/sharded", data)])
    try:
        reader = HbmReader(client, jax.devices())
        arr = await reader.read_file_sharded("/t/sharded")
        assert arr.shape == (8 * 128, 128)  # 8 blocks x 128 chunks
        assert len(arr.sharding.device_set) == 8
        np.testing.assert_array_equal(
            np.asarray(arr).reshape(-1), bytes_to_words(data).reshape(-1)
        )
        # The sharded array is directly consumable by a jitted global op
        # (modular uint32 sum: x64 is disabled on the test platform).
        total = jax.jit(lambda x: jnp.sum(x, dtype=jnp.uint32))(arr)
        want = np.sum(bytes_to_words(data), dtype=np.uint32)
        assert int(total) == int(want)
    finally:
        await c.stop()


async def test_hbm_reader_sharded_more_blocks_than_devices(tmp_path):
    """16 blocks on 8 devices must come back in FILE order, not interleaved."""
    data = _rand(16 * 64 * 1024, seed=8)
    c, client = await _cluster_with_files(tmp_path, [("/t/many", data)])
    try:
        reader = HbmReader(client, jax.devices())
        arr = await reader.read_file_sharded("/t/many")
        np.testing.assert_array_equal(
            np.asarray(arr).reshape(-1), bytes_to_words(data).reshape(-1)
        )
    finally:
        await c.stop()


async def test_infeed_missing_file_raises(tmp_path):
    """A failed prefetch must raise to the consumer, never hang it."""
    c, client = await _cluster_with_files(tmp_path, [])
    try:
        infeed = DfsInfeed(client, ["/no/such/file"], jax.devices())

        async def consume():
            async for _ in infeed.__aiter__():
                pass

        with pytest.raises(DfsError):
            await asyncio.wait_for(consume(), timeout=30)
    finally:
        await c.stop()


async def test_infeed_stream(tmp_path):
    files = [(f"/in/f{i}", _rand(64 * 1024, seed=10 + i)) for i in range(3)]
    c, client = await _cluster_with_files(tmp_path, files)
    try:
        infeed = DfsInfeed(client, [p for p, _ in files], jax.devices(),
                           prefetch=2)
        seen = []
        async for path, blocks in infeed.__aiter__():
            seen.append(path)
            assert all(b.verified for b in blocks)
            joined = b"".join(
                device_array_to_bytes(b.array, b.size) for b in blocks
            )
            assert joined == dict(files)[path]
        assert seen == [p for p, _ in files]
    finally:
        await c.stop()


# ------------------------------------------------------------ graft entry


def test_graft_entry_single_chip():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert bool(out["crc_ok"])
    assert out["parity"].shape[0] == 3


def test_graft_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


# ------------------------------------------------------------ grain infeed


async def test_grain_infeed_training_batches(tmp_path):
    """North-star JAX/Grain infeed: DFS files -> grain source -> shuffled
    batches -> device arrays consumed by a jitted training step. All grain
    work runs in a worker thread so the cluster's event loop stays free to
    serve the RPCs grain's fetches issue."""
    record = 1024
    files = [
        (f"/train/shard{i}", _rand(16 * record + 100, seed=40 + i))
        for i in range(3)
    ]
    c, _client = await _cluster_with_files(tmp_path, files)
    try:
        from tpudfs.tpu import grain_infeed as gi

        def consume():
            source = gi.DfsRecordSource(
                list(c.masters), [p for p, _ in files], record
            )
            try:
                assert len(source) == 48  # 16 per file, 100-byte tails dropped
                # Record bytes come back exactly as written.
                assert np.asarray(source[0]).tobytes() == files[0][1][:record]
                ds = gi.make_dataset(
                    source, batch_size=8, shuffle_seed=0,
                    shard_by_process=True,
                )
                return list(gi.device_iterator(ds))
            finally:
                source.close()

        batches = await asyncio.to_thread(consume)
        assert len(batches) == 6
        assert batches[0].shape == (8, record)
        assert all(isinstance(b, jax.Array) for b in batches)

        # A jitted training step consumes the device-resident batches.
        @jax.jit
        def train_step(w, x):
            x = x.astype(jnp.float32) / 255.0
            return w + x.mean()

        w = jnp.zeros(())
        for b in batches:
            w = train_step(w, b)
        assert np.isfinite(float(w))

        # Shuffling actually permuted records across the epoch.
        flat = np.concatenate([np.asarray(b) for b in batches])
        ordered = np.stack([
            np.frombuffer(files[i][1][j * record:(j + 1) * record], np.uint8)
            for i in range(3) for j in range(16)
        ])
        assert not np.array_equal(flat, ordered)
        assert sorted(map(bytes, flat)) == sorted(map(bytes, ordered))
    finally:
        await c.stop()


async def test_grain_infeed_sharded_batches(tmp_path):
    """device_iterator with a mesh shards each batch over the device axis
    (data-parallel infeed layout)."""
    record = 512
    files = [("/train/one", _rand(32 * record, seed=50))]
    c, _client = await _cluster_with_files(tmp_path, files)
    try:
        from tpudfs.tpu import grain_infeed as gi

        mesh = make_mesh(jax.devices())

        def consume():
            source = gi.DfsRecordSource(
                list(c.masters), ["/train/one"], record
            )
            try:
                ds = gi.make_dataset(
                    source, batch_size=8, shard_by_process=False
                )
                return list(gi.device_iterator(ds, mesh=mesh))
            finally:
                source.close()

        batches = await asyncio.to_thread(consume)
        assert len(batches) == 4
        for b in batches:
            assert b.shape == (8, record)
            assert len(b.sharding.device_set) == len(jax.devices())
    finally:
        await c.stop()


# ------------------------------------------------- device CRC fold + lazy


@pytest.mark.parametrize("n", [512, 64 * 1024, 1 << 20])
def test_block_crc_device_matches_host(n):
    from tpudfs.common.checksum import crc32c
    from tpudfs.tpu.crc32c_pallas import block_crc_device

    data = _rand(n, seed=n % 97)
    got = int(np.asarray(block_crc_device(jnp.asarray(bytes_to_words(data)))))
    assert got == crc32c(data)


async def test_hbm_reader_lazy_verify_and_confirm(tmp_path):
    data = _rand(4 * 64 * 1024, seed=11)  # chunk-multiple blocks
    c, client = await _cluster_with_files(tmp_path, [("/t/lazy", data)])
    try:
        reader = HbmReader(client, jax.devices())
        blocks = await reader.read_file_to_device_blocks("/t/lazy", verify="lazy")
        assert all(not b.verified and b.pending_crc is not None for b in blocks)
        await reader.confirm(blocks)
        assert all(b.verified and b.pending_crc is None for b in blocks)
        assert b"".join(
            device_array_to_bytes(b.array, b.size) for b in blocks
        ) == data
        await reader.confirm(blocks)  # idempotent, no pending flags left
    finally:
        await c.stop()


async def test_hbm_reader_lazy_confirm_detects_tamper(tmp_path):
    data = _rand(64 * 1024, seed=12)
    c, client = await _cluster_with_files(tmp_path, [("/t/lazybad", data)])
    try:
        meta = await client.get_file_info("/t/lazybad")
        bid = meta["blocks"][0]["block_id"]
        for cs in c.chunkservers:
            if cs.store.exists(bid):
                raw = bytearray(cs.store.read(bid))
                raw[4000] ^= 0x10
                cs.store.write(bid, bytes(raw))
                cs.invalidate_cached(bid)
        reader = HbmReader(client, jax.devices())
        blocks = await reader.read_file_to_device_blocks("/t/lazybad", verify="lazy")
        with pytest.raises(DfsError) as ei:
            await reader.confirm(blocks)
        assert bid in str(ei.value)
    finally:
        await c.stop()


async def test_hbm_reader_lazy_tail_block_raises_eagerly(tmp_path):
    # Non-chunk-multiple tail blocks cannot defer to confirm() (the device
    # fold runs on the padded stream) — lazy mode must verify them eagerly
    # and raise AT READ TIME on corruption.
    data = _rand(64 * 1024 + 300, seed=13)
    c, client = await _cluster_with_files(tmp_path, [("/t/tail", data)])
    try:
        reader = HbmReader(client, jax.devices())
        blocks = await reader.read_file_to_device_blocks("/t/tail", verify="lazy")
        tail = [b for b in blocks if b.size % 512 != 0]
        assert tail and all(b.verified and b.pending_crc is None for b in tail)
        meta = await client.get_file_info("/t/tail")
        bid = meta["blocks"][-1]["block_id"]
        for cs in c.chunkservers:
            if cs.store.exists(bid):
                raw = bytearray(cs.store.read(bid))
                raw[-1] ^= 0x01
                cs.store.write(bid, bytes(raw))
                cs.invalidate_cached(bid)
        with pytest.raises(DfsError):
            await reader.read_file_to_device_blocks("/t/tail", verify="lazy")
    finally:
        await c.stop()


def test_block_crc_device_empty():
    from tpudfs.tpu.crc32c_pallas import block_crc_device

    assert int(np.asarray(
        block_crc_device(jnp.zeros((0, 128), jnp.uint32))
    )) == 0


# ------------------------------------------------- EC shard scatter (ICI)


def test_ec_shard_scatter_layout_and_reconstruction():
    from tpudfs.tpu.ici_replication import EcShardScatter

    k, m = 2, 1
    n = len(jax.devices())
    mesh = make_mesh(jax.devices())
    scatter = EcShardScatter(mesh, k, m)
    C = 8  # chunks per host (4 KiB blocks)
    rng = np.random.default_rng(21)
    blocks = [rng.integers(0, 256, C * 512, dtype=np.uint8).tobytes()
              for _ in range(n)]
    words = np.concatenate([bytes_to_words(b) for b in blocks])
    arr = jax.device_put(
        jnp.asarray(words),
        jax.NamedSharding(mesh, jax.sharding.PartitionSpec("hosts")),
    )
    shards, ok, acks = scatter.scatter(arr)
    assert int(acks) == n and bool(np.asarray(ok).all())

    # Device d's group row j holds shard j of host (d - j) % n; gathering
    # the k data shards of host i from devices (i+j) % n reconstructs it.
    out = np.asarray(shards).reshape(n, k + m, -1, 128)
    per = -(-(C * 512) // k)
    shard_len_b = -(-per // 512) * 512
    for i in range(n):
        got = b""
        for j in range(k):
            dev = (i + j) % n
            got += out[dev, j].astype("<u4").tobytes()[:shard_len_b]
        assert got[:C * 512] == blocks[i], f"host {i} reconstruction"

    # Parity shards really are RS parity: decode with the host codec after
    # dropping a data shard.
    from tpudfs.common.erasure import decode as ec_decode
    for i in range(min(n, 3)):
        all_shards: list[bytes | None] = []
        for j in range(k + m):
            dev = (i + j) % n
            all_shards.append(out[dev, j].astype("<u4").tobytes()[:shard_len_b])
        all_shards[0] = None  # lose a data shard
        assert ec_decode(all_shards, k, m, C * 512) == blocks[i]


# ------------------------------------------------- on-device RS decode


@pytest.mark.parametrize("k,m,missing", [
    (4, 2, (0,)),          # one data shard lost
    (6, 3, (1, 4)),        # two data shards lost
    (6, 3, (0, 5, 7)),     # two data + one parity lost
    (6, 3, (6, 7, 8)),     # only parity lost (identity decode)
])
def test_rs_decode_device_bit_exact(k, m, missing):
    from tpudfs.tpu.rs_pallas import pad_shard_len, rs_decode_device

    data = _rand(50_000, seed=11)
    shards = encode(data, k, m)
    slen = len(shards[0])
    present = tuple(i for i in range(k + m) if i not in missing)
    use = present[:k]
    padded = pad_shard_len(slen)
    stack = np.zeros((k, padded), dtype=np.uint8)
    for r, idx in enumerate(use):
        stack[r, :slen] = np.frombuffer(shards[idx], dtype=np.uint8)
    for use_pallas in (False, True):
        out = np.asarray(rs_decode_device(
            jnp.asarray(stack), k, m, use, use_pallas=use_pallas
        ))
        got = b"".join(out[i, :slen].tobytes() for i in range(k))[:len(data)]
        assert got == data, f"use_pallas={use_pallas}"


async def test_hbm_reader_ec_degraded_reconstructs_on_device(tmp_path):
    """Degraded EC read through HbmReader: kill two shard holders, the
    reader uploads the k survivors and reconstructs with the Pallas GF
    matmul, and the on-device block CRC fold verifies the result."""
    from tests.test_master_service import MiniCluster

    c = MiniCluster(tmp_path, n_masters=1, n_cs=6)
    await c.start()
    leader = await c.leader()
    await c.wait_out_of_safe_mode(leader)
    client = Client(list(c.masters), rpc_client=c.client,
                    block_size=1 << 20, local_reads=False)
    try:
        data = _rand(192 * 512, seed=12)  # chunk-multiple: device fold path
        await client.create_file("/ec/dev", data, ec=(4, 2))
        meta = await client.get_file_info("/ec/dev")
        block = meta["blocks"][0]
        for cs in list(c.chunkservers):
            if cs.address in block["locations"][:2]:
                await cs.stop()
        reader = HbmReader(client, jax.devices())
        blocks = await reader.read_file_to_device_blocks("/ec/dev")
        assert len(blocks) == 1 and blocks[0].verified
        assert device_array_to_bytes(blocks[0].array, blocks[0].size) == data
    finally:
        await c.stop()


async def test_hbm_reader_ec_degraded_detects_corrupt_shard(tmp_path):
    """A corrupted surviving shard must fail the end-to-end device CRC of
    the reconstruction, not silently decode to garbage."""
    from tests.test_master_service import MiniCluster

    c = MiniCluster(tmp_path, n_masters=1, n_cs=6)
    await c.start()
    leader = await c.leader()
    await c.wait_out_of_safe_mode(leader)
    client = Client(list(c.masters), rpc_client=c.client,
                    block_size=1 << 20, local_reads=False)
    try:
        data = _rand(64 * 512, seed=13)
        await client.create_file("/ec/bad", data, ec=(4, 2))
        meta = await client.get_file_info("/ec/bad")
        block = meta["blocks"][0]
        bid = block["block_id"]
        # Kill one data-shard holder (degraded) and corrupt another data
        # shard in place, sidecar included, so the store serves it happily.
        victims = 0
        for cs in list(c.chunkservers):
            if cs.address == block["locations"][0]:
                await cs.stop()
        for cs in list(c.chunkservers):
            if cs.address == block["locations"][1] and cs.store.exists(bid):
                raw = bytearray(cs.store.read(bid))
                raw[10] ^= 0xFF
                cs.store.write(bid, bytes(raw))
                cs.invalidate_cached(bid)
                victims += 1
        assert victims == 1
        reader = HbmReader(client, jax.devices())
        with pytest.raises(DfsError) as ei:
            await reader.read_file_to_device_blocks("/ec/bad")
        assert "checksum mismatch" in str(ei.value)
    finally:
        await c.stop()


# ------------------------------ the degraded read's one decode program


def _loss_patterns(k, m):
    """Every loss of two slots, and a sample of losses of three where the
    code can bear them."""
    import itertools

    n = k + m
    lost = list(itertools.combinations(range(n), 2))
    if m >= 3:
        lost += [(0, 1, 2), (0, k - 1, k), (1, k, n - 1), (k, k + 1, n - 1),
                 (2, 3, n - 1)]
    return lost


@pytest.mark.parametrize("k,m,size", [
    (6, 3, 65536),   # shards of 10 923 bytes: every shard but one is shifted
    (6, 3, 3840),    # shards of 640 bytes: a multiple of 128, word-aligned
    (6, 3, 5000),    # not a chunk multiple: the grid's tail is zero padding
    (4, 2, 50_000),
    (3, 2, 7777),
])
def test_rs_decode_block_one_program_for_every_loss(k, m, size):
    """The degraded read's decode program against the plain reference AND
    the host codec, bit for bit, for every failure pattern, with ONE
    compile per (k, shard length, block size): the inverse is an operand."""
    from benchmarks import reference_rs
    from tpudfs.common.erasure import reconstruct
    from tpudfs.tpu.rs_pallas import (
        decode_matrix,
        rs_decode_block,
        survivors_to_words,
    )

    data = _rand(size, seed=size)
    shards = encode(data, k, m)
    slen = len(shards[0])
    want = bytes_to_words(data)
    compiled = rs_decode_block._cache_size()
    seen = set()
    for lost in _loss_patterns(k, m):
        have = [None if i in lost else s for i, s in enumerate(shards)]
        use = tuple(i for i in range(k + m) if i not in lost)[:k]
        seen.add(use)
        assert reference_rs.decode(have, k, m, size) == data
        assert b"".join(reconstruct(have, k, m)[:k])[:size] == data
        got = np.asarray(rs_decode_block(
            jnp.asarray(survivors_to_words([shards[i] for i in use], slen)),
            jnp.asarray(decode_matrix(k, m, use)),
            slen=slen, size=size))
        np.testing.assert_array_equal(got, want, err_msg=f"lost {lost}")
    assert len(seen) > 3
    assert rs_decode_block._cache_size() == compiled + 1


async def _nine_with_rs63_file(tmp_path, path, data):
    c = MiniCluster(tmp_path, n_masters=1, n_cs=9)
    await c.start()
    leader = await c.leader()
    await c.wait_out_of_safe_mode(leader)
    client = Client(list(c.masters), rpc_client=c.client,
                    block_size=64 * 1024, local_reads=False)
    await client.create_file(path, data, ec=(6, 3))
    return c, client


async def _stop_holders(c, addrs):
    for cs, hb in zip(list(c.chunkservers), c.heartbeats):
        if cs.address in addrs:
            hb.stop()
            await cs.stop()


async def test_hbm_reader_rs63_two_of_nine_down(tmp_path):
    """A multi-block RS(6,3) file on nine servers, two holders of data
    shards stopped: every block is reconstructed on the device by the one
    warmed program and verified there; counters and spans say what ran."""
    from tpudfs.common import telemetry
    from tpudfs.tpu.rs_pallas import rs_decode_block

    data = _rand(5 * 64 * 1024, seed=21)  # five blocks
    c, client = await _nine_with_rs63_file(tmp_path, "/ec/nine", data)
    try:
        meta = await client.get_file_info("/ec/nine")
        assert [len(b["locations"]) for b in meta["blocks"]] == [9] * 5
        await _stop_holders(c, meta["blocks"][0]["locations"][:2])
        reader = HbmReader(client, jax.devices()[:1])
        reader.warm_ec(6, 3, 64 * 1024)
        compiled = rs_decode_block._cache_size()
        telemetry.enable()
        try:
            blocks = await reader.read_file_to_device_blocks(
                "/ec/nine", verify="lazy")
            await reader.confirm(blocks)
        finally:
            records = telemetry.drain()
            telemetry.disable()
        assert rs_decode_block._cache_size() == compiled
        assert len(blocks) == 5 and all(b.verified for b in blocks)
        assert b"".join(device_array_to_bytes(b.array, b.size)
                        for b in blocks) == data
        assert reader.ec_blocks == 5
        # The stopped servers may hold parity slots of later blocks.
        assert 1 <= reader.ec_degraded_blocks <= 5
        assert reader.ec_degraded_blocks <= reader.ec_missing_data_shards \
            <= 2 * reader.ec_degraded_blocks
        slen = -(-64 * 1024 // 6)
        assert reader.ec_shard_bytes == 5 * 7 * slen
        whole = [r for r in records if r.name == "hbm.read_file"]
        assert len(whole) == 1
        by_name = {}
        for r in records:
            if r.name.startswith("ec."):
                assert r.parent_id == whole[0].span_id, r
                by_name.setdefault(r.name, []).append(r)
        assert set(by_name) == {"ec.queued", "ec.fetch_shards", "ec.assemble",
                                "ec.device_put", "ec.decode_dispatch"}
        assert len(by_name["ec.fetch_shards"]) == len(by_name["ec.queued"]) \
            == 5
        assert len(by_name["ec.decode_dispatch"]) == reader.ec_degraded_blocks
        assert len(by_name["ec.assemble"]) == len(by_name["ec.device_put"]) == 5
        assert sum(r.attrs["degraded"] for r in by_name["ec.assemble"]) \
            == reader.ec_degraded_blocks
        assert all(r.attrs["present"] == 7 for r in by_name["ec.fetch_shards"])
        assert sum(r.attrs["missing_data"]
                   for r in by_name["ec.fetch_shards"]) \
            == reader.ec_missing_data_shards
        # Once the breakers know the two are down they are left alone.
        assert sum(client.block_pool.breakers.is_open(a)
                   for a in meta["blocks"][0]["locations"]) == 2
    finally:
        await c.stop()


async def test_hbm_reader_rs63_degraded_detects_corrupt_survivor(tmp_path):
    """Nine servers, two down, and a surviving shard the reconstruction
    uses rotted in place (sidecar consistent): the CRC32C of the
    RECONSTRUCTED bytes fails on the device, at confirm."""
    data = _rand(2 * 64 * 1024, seed=22)
    c, client = await _nine_with_rs63_file(tmp_path, "/ec/rot", data)
    try:
        meta = await client.get_file_info("/ec/rot")
        block = meta["blocks"][0]
        bid = block["block_id"]
        await _stop_holders(c, block["locations"][:2])
        for cs in c.chunkservers:
            if cs.address == block["locations"][2]:
                raw = bytearray(cs.store.read(bid))
                raw[10] ^= 0xFF
                cs.store.write(bid, bytes(raw))
                cs.invalidate_cached(bid)
        reader = HbmReader(client, jax.devices()[:1])
        blocks = await reader.read_file_to_device_blocks(
            "/ec/rot", verify="lazy")
        assert not blocks[0].verified and blocks[0].pending_crc is not None
        with pytest.raises(DfsError, match="checksum mismatch"):
            await reader.confirm(blocks)
        assert reader.ec_degraded_blocks >= 1
    finally:
        await c.stop()


# ---------------------------------------- corrupt-local-replica failover


async def _corrupt_first_replica(c, client, path):
    """Bit-rot the FIRST location's replica IN PLACE (sidecar untouched)
    so the unverified short-circuit pread returns rot while the verified
    path excludes this replica and the others stay healthy."""
    meta = await client.get_file_info(path)
    block = meta["blocks"][0]
    bid = block["block_id"]
    for cs in c.chunkservers:
        if cs.address == block["locations"][0]:
            p = cs.store.block_path(bid)
            raw = bytearray(p.read_bytes())
            raw[42] ^= 0xFF
            p.write_bytes(bytes(raw))
            cs.invalidate_cached(bid)
            return
    raise AssertionError("first replica holder not found")


async def test_hbm_reader_retries_corrupt_local_replica_eager(tmp_path):
    data = _rand(16 * 512, seed=14)
    c, client = await _cluster_with_files(tmp_path, [("/cl/a", data)])
    try:
        await _corrupt_first_replica(c, client, "/cl/a")
        reader = HbmReader(client, jax.devices()[:1])
        blocks = await reader.read_file_to_device_blocks("/cl/a", verify=True)
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size) for b in blocks)
        assert got == data
    finally:
        await c.stop()


async def test_hbm_reader_retries_corrupt_local_replica_lazy(tmp_path):
    data = _rand(16 * 512, seed=15)
    c, client = await _cluster_with_files(tmp_path, [("/cl/b", data)])
    try:
        await _corrupt_first_replica(c, client, "/cl/b")
        reader = HbmReader(client, jax.devices()[:1])
        blocks = await reader.read_file_to_device_blocks("/cl/b",
                                                         verify="lazy")
        await reader.confirm(blocks)  # retry path resolves the rot
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size) for b in blocks)
        assert got == data
    finally:
        await c.stop()


# ------------------------------------------ sweep over cached metadata


async def _sweep_cached_meta(client, reader, path):
    """One sweep of ``path`` from metadata fetched beforehand, with the
    master's metadata call forbidden while it runs."""
    meta = await client.get_file_info(path)

    async def no_master(_path):
        raise AssertionError("a sweep over cached metadata asked the master")

    real, client.get_file_info = client.get_file_info, no_master
    try:
        return await reader.sweep_metas_to_device([meta])
    finally:
        client.get_file_info = real


async def test_sweep_metas_roundtrip(tmp_path):
    """Cached-meta sweep: after one normal read primes the local-store
    probes, sweep_metas_to_device returns blocks verified on arrival with
    no master round-trip, bit-identical to the file."""
    data = _rand(6 * 64 * 1024, seed=30)
    c, client = await _cluster_with_files(tmp_path, [("/wf/a", data)])
    try:
        client.local_reads = True
        reader = HbmReader(client, jax.devices()[:1])
        prime = await reader.read_file_to_device_blocks("/wf/a",
                                                        verify="lazy")
        await reader.confirm(prime)
        before = client.local_read_blocks
        blocks = await _sweep_cached_meta(client, reader, "/wf/a")
        assert all(b.verified for b in blocks)
        assert reader.sweep_blocks == len(blocks) == 6
        got = b"".join(device_array_to_bytes(b.array, b.size) for b in blocks)
        assert got == data
        # the pump preads the replicas itself: no client._read_local (no
        # counter bump), no master, no chunkserver RPC
        assert client.local_read_blocks == before
    finally:
        await c.stop()


async def test_sweep_metas_rot_failover(tmp_path):
    """Bit-rot under the sweep fails the pump's CRC check and resolves
    through the per-block fallback."""
    data = _rand(16 * 512, seed=31)
    c, client = await _cluster_with_files(tmp_path, [("/wf/b", data)])
    try:
        client.local_reads = True
        reader = HbmReader(client, jax.devices()[:1])
        prime = await reader.read_file_to_device_blocks("/wf/b",
                                                        verify="lazy")
        await reader.confirm(prime)
        await _corrupt_first_replica(c, client, "/wf/b")
        blocks = await _sweep_cached_meta(client, reader, "/wf/b")
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size) for b in blocks)
        assert got == data
    finally:
        await c.stop()


async def test_sweep_metas_tail_rot_failover(tmp_path):
    """A NON-512-aligned (tail) block never rides the pump; rot in its
    colocated replica must fall back through the general path's retry
    instead of failing the sweep."""
    data = _rand(5 * 512 + 100, seed=32)  # single unaligned block
    c, client = await _cluster_with_files(tmp_path, [("/wf/c", data)])
    try:
        client.local_reads = True
        reader = HbmReader(client, jax.devices()[:1])
        prime = await reader.read_file_to_device_blocks("/wf/c",
                                                        verify="lazy")
        await reader.confirm(prime)
        await _corrupt_first_replica(c, client, "/wf/c")
        blocks = await _sweep_cached_meta(client, reader, "/wf/c")
        assert all(b.verified for b in blocks) and not reader.sweep_blocks
        got = b"".join(device_array_to_bytes(b.array, b.size) for b in blocks)
        assert got == data
    finally:
        await c.stop()


# ------------------------------------------- sharded metadata plane → HBM


async def test_hbm_reader_across_shards(tmp_path):
    """The TPU reader rides the full sharded metadata plane: files whose
    keys live on DIFFERENT range shards (REDIRECT protocol, per-shard
    masters) all land in device memory verified — P5 on top of P3
    (SURVEY.md §2.6)."""
    from tests.test_cross_shard import ShardedCluster

    c = await ShardedCluster(tmp_path).start()
    try:
        client = c.client
        files = {}
        for seed, path in ((41, "/a/left.bin"), (42, "/z/right.bin")):
            data = _rand(24 * 512, seed=seed)
            await client.create_file(path, data)
            files[path] = data
        assert c.master_of("/a/left.bin") is not c.master_of("/z/right.bin")
        reader = HbmReader(client, jax.devices()[:2])
        for path, data in files.items():
            blocks = await reader.read_file_to_device_blocks(path,
                                                             verify="lazy")
            await reader.confirm(blocks)
            assert all(b.verified for b in blocks)
            got = b"".join(
                device_array_to_bytes(b.array, b.size) for b in blocks
            )
            assert got == data
    finally:
        await c.stop()


# ---------------------------------------- pod-level degraded EC gather


@pytest.mark.parametrize("k,m", [(4, 2), (6, 3)])
def test_gf_matmul_runtime_bit_exact(k, m):
    """The runtime-coefficient GF matmul matches the host codec for both
    encode (parity rows) and decode (inverse) matrices."""
    from tpudfs.common.erasure import _gf_matmul, encode_matrix
    from tpudfs.tpu.rs_pallas import decode_matrix, gf_matmul_runtime

    rng = np.random.default_rng(50)
    shards = rng.integers(0, 256, (k, 1024), dtype=np.uint8)
    words = jnp.asarray(
        np.ascontiguousarray(shards).reshape(k, -1, 4).view("<u4")[..., 0]
        .reshape(k, -1)
    )
    for mat in (encode_matrix(k, m)[k:],
                decode_matrix(k, m, tuple(range(1, k + 1)))):
        want = _gf_matmul(np.asarray(mat), shards)
        got_words = np.asarray(gf_matmul_runtime(jnp.asarray(mat), words))
        got = got_words.astype("<u4").tobytes()
        assert got == want.tobytes()


@pytest.mark.parametrize("k,m", [(2, 1), (2, 2)])
def test_ec_gather_reconstructs_around_failed_device(k, m):
    """Scatter → lose a device → gather: every host's data shards come
    back bit-exact with reconstruction running entirely on the mesh."""
    from tpudfs.tpu.ici_replication import EcShardGather, EcShardScatter

    n = len(jax.devices())
    mesh = make_mesh(jax.devices())
    scatter = EcShardScatter(mesh, k, m)
    gather = EcShardGather(mesh, k, m)
    C = 8  # chunks per host
    rng = np.random.default_rng(51)
    blocks = [rng.integers(0, 256, C * 512, dtype=np.uint8).tobytes()
              for _ in range(n)]
    words = np.concatenate([bytes_to_words(b) for b in blocks])
    arr = jax.device_put(
        jnp.asarray(words),
        jax.NamedSharding(mesh, jax.sharding.PartitionSpec("hosts")),
    )
    shards, ok, acks = scatter.scatter(arr)
    assert int(acks) == n

    def check(reconstructed):
        out = np.asarray(reconstructed).reshape(n, k, -1)
        per = -(-(C * 512) // k)
        shard_len_b = -(-per // 512) * 512
        for i in range(n):
            got = b"".join(
                out[i, r].astype("<u4").tobytes()[:shard_len_b]
                for r in range(k)
            )[:C * 512]
            assert got == blocks[i], f"host {i}"

    # Healthy gather (identity decode everywhere).
    check(gather.gather(shards, failed=None))
    # Garbage a device's whole shard group, reconstruct around it. The
    # same compiled program serves every failure index (runtime matrices).
    host_shards = np.asarray(shards).copy().reshape(n, k + m, -1, 128)
    for failed in range(min(n, 3)):
        broken = host_shards.copy()
        broken[failed] = 0xAB
        barr = jax.device_put(
            jnp.asarray(broken.reshape(np.asarray(shards).shape)),
            jax.NamedSharding(mesh, jax.sharding.PartitionSpec("hosts")),
        )
        check(gather.gather(barr, failed=failed))


def test_ec_gather_rejects_small_mesh():
    """A mesh smaller than k+m puts multiple shards of one codeword on a
    single device — one failure would exceed the one-excluded-shard
    repair, so construction must refuse (same guard as the scatter)."""
    from tpudfs.tpu.ici_replication import EcShardGather

    mesh = make_mesh(jax.devices()[:2])
    with pytest.raises(ValueError):
        EcShardGather(mesh, 2, 1)


# ------------------------------------------------- fused read path (r3)


@pytest.mark.parametrize("nblocks", [1, 3, 8])
def test_batch_block_crc_device_bit_exact(nblocks):
    from tpudfs.common.checksum import crc32c
    from tpudfs.tpu.crc32c_pallas import batch_block_crc_device

    cpb = 16
    datas = [_rand(cpb * 512, seed=40 + i) for i in range(nblocks)]
    words = jnp.asarray(bytes_to_words(b"".join(datas)))
    got = np.asarray(batch_block_crc_device(words, nblocks))
    assert [int(x) for x in got] == [crc32c(d) for d in datas]


async def _batched_reader(client, host_verify):
    client.local_reads = True  # conftest defaults TPUDFS_LOCAL_READS=0
    reader = HbmReader(client, jax.devices()[:1], batch_reads=8)
    comb = reader._combiner(reader.devices[0])
    comb.host_verify = host_verify
    return reader, comb


@pytest.mark.parametrize("host_verify", [True, False])
async def test_fused_read_roundtrip(tmp_path, host_verify):
    """Fused rounds (native multi-pread -> one device_put -> one CRC) are
    bit-exact and actually used, in both verify placements: on-host
    (CPU-fallback twin, CRC inside the native read) and on-device
    (batched fold resolved at confirm)."""
    from tpudfs.common import telemetry

    data = _rand(6 * 64 * 1024, seed=50)
    c, client = await _cluster_with_files(tmp_path, [("/fu/a", data)])
    try:
        reader, comb = await _batched_reader(client, host_verify)
        # Prime the local-store probes (first read may race the probe).
        prime = await reader.read_file_to_device_blocks("/fu/a",
                                                        verify="lazy")
        await reader.confirm(prime)
        telemetry.enable()
        try:
            blocks = await reader.read_file_to_device_blocks("/fu/a",
                                                             verify="lazy")
        finally:
            telemetry.disable()
            fetches = [r.attrs for r in telemetry.drain()
                       if r.name == "combiner.fetch"]
        assert comb.blocks >= 1, "combiner never engaged"
        assert fetches and all(f["in_flight"] == 1 for f in fetches), \
            "the local disk is one source"
        await reader.confirm(blocks)
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in blocks)
        assert got == data
        await reader.confirm(blocks)  # idempotent
    finally:
        await c.stop()


async def test_fused_read_buffer_pool_reuse(tmp_path):
    """Round buffers recycle across rounds (bounded pool) and reuse is
    bit-exact — a recycled buffer must never leak a previous round's
    bytes into a later read (device_put copies on CPU; accelerators gate
    release on transfer completion)."""
    d1 = _rand(4 * 64 * 1024, seed=53)
    d2 = _rand(4 * 64 * 1024, seed=54)
    c, client = await _cluster_with_files(
        tmp_path, [("/fu/p1", d1), ("/fu/p2", d2)])
    try:
        reader, comb = await _batched_reader(client, True)
        for want, path in [(d1, "/fu/p1"), (d2, "/fu/p2")] * 3:
            blocks = await reader.read_file_to_device_blocks(path,
                                                             verify="lazy")
            await reader.confirm(blocks)
            got = b"".join(device_array_to_bytes(b.array, b.size)
                           for b in blocks)
            assert got == want
        assert comb.blocks >= 6, "combiner never engaged"
        pooled = sum(len(v) for v in comb._buf_pool.values())
        assert 1 <= pooled <= comb._POOL_PER_SHAPE * len(comb._buf_pool), \
            comb._buf_pool
    finally:
        await c.stop()


async def test_fused_read_held_blocks_survive_buffer_recycle(tmp_path):
    """Device blocks from round 1 are HELD while round 2 refills the
    recycled host buffer, then read back — catches any backend where
    device_put aliases (rather than copies) the pooled numpy buffer (a
    pool-reuse test that holds no device array across a reuse would pass
    zero-copy aliasing)."""
    d1 = _rand(4 * 64 * 1024, seed=57)
    d2 = _rand(4 * 64 * 1024, seed=58)
    c, client = await _cluster_with_files(
        tmp_path, [("/fu/h1", d1), ("/fu/h2", d2)])
    try:
        reader, comb = await _batched_reader(client, True)
        held = await reader.read_file_to_device_blocks("/fu/h1",
                                                       verify="lazy")
        await reader.confirm(held)
        # Round 2+ recycles round 1's pooled buffer and overwrites it.
        for _ in range(3):
            blocks = await reader.read_file_to_device_blocks("/fu/h2",
                                                             verify="lazy")
            await reader.confirm(blocks)
        assert comb.blocks >= 4, "combiner never engaged"
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in held)
        assert got == d1, "recycled host buffer leaked into held blocks"
    finally:
        await c.stop()


@pytest.mark.parametrize("host_verify", [True, False])
async def test_fused_read_buffer_recycle_rounds_out_of_order(tmp_path,
                                                             host_verify):
    """The held-blocks check above with rounds that END out of order: the
    first round's frame stays open while four later rounds (another
    origin) recycle the pool, then it is handed off last. No buffer is
    handed out again while a round still owns it, each returns to the pool
    only with every transfer out of it complete, and every block held
    across the recycling reads back exactly."""
    data = _rand(16 * 64 * 1024, seed=59)
    c, client = await _cluster_with_files(tmp_path, [("/fu/ooo", data)])
    try:
        reader, comb, addrs = _remote_reader(client, host_verify, origins=2)
        await client.get_file_info("/fu/ooo")
        gate = _FetchGate(comb, held={addrs[0]})
        out: dict = {}  # data pointer of a buffer a round owns -> its reqs
        get_buf, put_buf = comb._get_buf, comb._put_buf
        upload_round = comb._upload_round
        recycled = []

        def tracked_get(nrows):
            buf = get_buf(nrows)
            assert buf.ctypes.data not in out, "buffer handed out twice"
            out[buf.ctypes.data] = None
            return buf

        async def tracked_upload(reqs, rows, *rest):
            out[rows.ctypes.data] = reqs
            await upload_round(reqs, rows, *rest)

        def tracked_put(buf):
            if buf is not None:
                words = [r.fut.result().batch.words
                         for r in out.pop(buf.ctypes.data)]
                assert len(words) == 2 and all(w.is_ready() for w in words), \
                    "buffer pooled before its transfer completed"
                recycled.append(buf.ctypes.data)
            put_buf(buf)

        async def settled(addr):
            # A round of the other origin ends only once the rounds before
            # it are uploaded and their buffers pooled, so the round after
            # it finds a pooled buffer whatever the host's timing.
            if addr != addrs[0] and not gate.release.is_set():
                before = gate.issued.count(addr) - 1
                await _until(lambda: comb.rounds >= before,
                             "the earlier rounds are uploaded")

        gate.before = settled

        comb._get_buf, comb._put_buf = tracked_get, tracked_put
        comb._upload_round = tracked_upload
        read = asyncio.create_task(reader.read_file_to_device_blocks(
            "/fu/ooo", verify="lazy"))
        await _until(lambda: comb.blocks == 8, "the other origin is done")
        assert gate.active[addrs[0]] == 1
        assert len(recycled) == 4 and len(set(recycled)) == 2, \
            "the other origin's rounds did not recycle the pool"
        gate.release.set()
        held = await read
        assert await _confirmed_bytes(reader, held) == data
        assert comb.blocks == 16 and not out
    finally:
        await c.stop()


def test_combiner_pool_buffers_defeat_zero_copy_aliasing():
    """PJRT's CPU client zero-copy-aliases 64-byte-aligned host buffers
    (measured on this image) — an aliased device array references pooled
    memory forever, so a recycled buffer would corrupt held blocks.
    host_buffers defends by (a) misaligning every buffer to ptr%64==4 and
    (b) probing that exact allocation pattern once, recycling nothing if a
    future jaxlib aliases anyway."""
    from tpudfs.tpu import host_buffers

    dev = jax.devices("cpu")[0]
    assert host_buffers.may_recycle(dev) is True
    buf = host_buffers.alloc(dev, 256 << 10)
    assert buf.ctypes.data % 64 == 4, "pool buffer not misaligned"
    # The probe is live, not vacuous: mutating the misaligned source must
    # leave the device copy intact (the aligned twin aliases on this
    # jaxlib, which is exactly why alloc misaligns).
    assert host_buffers._device_put_copies(dev) is True


async def test_fused_read_host_verify_falls_back_on_rot(tmp_path):
    """Host-verified fused reads route a corrupt local replica to the
    general path, which excludes it and recovers from a healthy one."""
    data = _rand(4 * 64 * 1024, seed=51)
    c, client = await _cluster_with_files(tmp_path, [("/fu/rot", data)])
    try:
        reader, comb = await _batched_reader(client, True)
        prime = await reader.read_file_to_device_blocks("/fu/rot",
                                                        verify="lazy")
        await reader.confirm(prime)
        await _corrupt_first_replica(c, client, "/fu/rot")
        blocks = await reader.read_file_to_device_blocks("/fu/rot",
                                                         verify="lazy")
        await reader.confirm(blocks)
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in blocks)
        assert got == data
    finally:
        await c.stop()


async def test_fused_read_device_verify_confirm_recovers_rot(tmp_path):
    """Device-verified fused reads surface rot at confirm(), whose retry
    re-reads through the host-verified path and repairs the block."""
    data = _rand(4 * 64 * 1024, seed=52)
    c, client = await _cluster_with_files(tmp_path, [("/fu/rot2", data)])
    try:
        reader, comb = await _batched_reader(client, False)
        prime = await reader.read_file_to_device_blocks("/fu/rot2",
                                                        verify="lazy")
        await reader.confirm(prime)
        await _corrupt_first_replica(c, client, "/fu/rot2")
        blocks = await reader.read_file_to_device_blocks("/fu/rot2",
                                                         verify="lazy")
        assert any(b.batch_pending for b in blocks)
        await reader.confirm(blocks)
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in blocks)
        assert got == data
    finally:
        await c.stop()


async def test_fused_read_mixed_block_sizes(tmp_path):
    """A non-chunk-aligned tail block takes the per-block path while the
    aligned blocks fuse; the file still reassembles bit-exactly."""
    data = _rand(2 * 64 * 1024 + 777, seed=53)
    c, client = await _cluster_with_files(tmp_path, [("/fu/mix", data)])
    try:
        reader, comb = await _batched_reader(client, True)
        prime = await reader.read_file_to_device_blocks("/fu/mix",
                                                        verify="lazy")
        await reader.confirm(prime)
        blocks = await reader.sweep_metas_to_device(
            [await client.get_file_info("/fu/mix")], reader.devices[0])
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in blocks)
        assert got == data
    finally:
        await c.stop()


async def test_fused_read_sync_arrays_no_slices(tmp_path):
    """sync_arrays of a fused block exposes batch-level arrays (no
    per-block slice dispatch); materializing .array afterwards still
    yields the block's own words."""
    data = _rand(4 * 64 * 1024, seed=54)
    c, client = await _cluster_with_files(tmp_path, [("/fu/sync", data)])
    try:
        reader, comb = await _batched_reader(client, True)
        prime = await reader.read_file_to_device_blocks("/fu/sync",
                                                        verify="lazy")
        await reader.confirm(prime)
        blocks = await reader.read_file_to_device_blocks("/fu/sync",
                                                         verify="lazy")
        fused = [b for b in blocks if b.batch is not None]
        assert fused
        for b in fused:
            for arr in b.sync_arrays:
                assert arr.shape[0] >= b.batch.cpb  # batch-level, not slice
        jax.block_until_ready([x for b in blocks for x in b.sync_arrays])
        await reader.confirm(blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in blocks)
        assert got == data
    finally:
        await c.stop()


def test_ec_full_geometry_nine_device_mesh():
    """RS(6,3) at its FULL k+m=9 shard-per-device geometry — scatter,
    healthy gather, and degraded gather around a garbage device — runs in
    a dedicated 12-virtual-device subprocess (the session's own mesh is
    capped at 8; VERDICT r2 item 4)."""
    import pathlib
    import subprocess
    import sys

    child = pathlib.Path(__file__).with_name("ec_full_geometry_child.py")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, str(child)], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, (
        f"child failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-2000:]}"
    )
    assert "OK" in proc.stdout


@pytest.mark.parametrize("host_verify", [True, False])
async def test_fused_read_remote_rounds(tmp_path, host_verify):
    """A NON-colocated client (short-circuit off) still gets fused rounds:
    blocks group per origin chunkserver and ship as one ReadBlocks frame,
    bit-exact in both verify placements."""
    data = _rand(6 * 64 * 1024, seed=60)
    c, client = await _cluster_with_files(tmp_path, [("/rf/a", data)])
    try:
        client.local_reads = False
        reader = HbmReader(client, jax.devices()[:1], batch_reads=8)
        comb = reader._combiner(reader.devices[0])
        comb.host_verify = host_verify
        blocks = await reader.read_file_to_device_blocks("/rf/a",
                                                         verify="lazy")
        assert comb.blocks >= 1, "remote fused rounds never engaged"
        await reader.confirm(blocks)
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in blocks)
        assert got == data
    finally:
        await c.stop()


async def test_fused_read_remote_corrupt_slot_falls_back(tmp_path):
    """A corrupt replica behind the remote fused round (server-side verify
    marks the slot -1) falls back to the per-block path, which fails over
    to a healthy replica."""
    data = _rand(4 * 64 * 1024, seed=61)
    c, client = await _cluster_with_files(tmp_path, [("/rf/rot", data)])
    try:
        client.local_reads = False
        await _corrupt_first_replica(c, client, "/rf/rot")
        reader = HbmReader(client, jax.devices()[:1], batch_reads=8)
        blocks = await reader.read_file_to_device_blocks("/rf/rot",
                                                         verify="lazy")
        await reader.confirm(blocks)
        assert all(b.verified for b in blocks)
        got = b"".join(device_array_to_bytes(b.array, b.size)
                       for b in blocks)
        assert got == data
    finally:
        await c.stop()


# ------------------------------- one round in flight per origin (PR 26)


def _remote_reader(client, host_verify, origins):
    """A NON-colocated reader whose metadata names block i's replicas
    rotated by ``i % origins``, so its rounds (2 blocks each) spread over
    that many origin chunkservers: every chunkserver of the 3x cluster
    holds every block. Returns (reader, combiner, the origins in order)."""
    client.local_reads = False
    real = client.get_file_info
    addrs: list = []

    async def spread(path):
        meta = await real(path)
        blocks = []
        for i, b in enumerate(meta["blocks"]):
            locs = sorted(b["locations"])
            assert len(locs) == 3
            addrs[:] = locs[:origins]
            k = i % origins
            blocks.append(dict(b, locations=locs[k:] + locs[:k]))
        return dict(meta, blocks=blocks)

    client.get_file_info = spread
    reader = HbmReader(client, jax.devices()[:1], batch_reads=2)
    comb = reader._combiner(reader.devices[0])
    comb.host_verify = host_verify
    return reader, comb, addrs


class _FetchGate:
    """Stands in front of ``ReadCombiner._fetch_remote``: counts the rounds
    in flight per origin, and holds the rounds to the ``held`` origins open
    until ``release`` is set."""

    def __init__(self, comb, held=()):
        self.real = comb._fetch_remote
        self.held = held
        self.release = asyncio.Event()
        self.active: dict = {}
        self.most: dict = {}
        self.issued: list = []
        self.before = None  # async hook(addr), run before a round's fetch
        comb._fetch_remote = self

    async def __call__(self, reqs, buf):
        addr = reqs[0].addr
        self.issued.append(addr)
        self.active[addr] = self.active.get(addr, 0) + 1
        self.most[addr] = max(self.most.get(addr, 0), self.active[addr])
        try:
            if self.before is not None:
                await self.before(addr)
            if addr in self.held:
                await self.release.wait()
            return await self.real(reqs, buf)
        finally:
            self.active[addr] -= 1


async def _until(cond, what):
    for _ in range(1000):
        if cond():
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"timed out waiting until {what}")


async def _confirmed_bytes(reader, blocks):
    await reader.confirm(blocks)
    assert all(b.verified for b in blocks)
    return b"".join(device_array_to_bytes(b.array, b.size) for b in blocks)


@pytest.mark.parametrize("host_verify", [True, False])
async def test_fused_read_rounds_overlap_across_origins(tmp_path,
                                                        host_verify):
    """With blocks on two origins and one origin's frame held open, the
    other origin's rounds are issued and served meanwhile; no origin ever
    has two rounds in flight."""
    from tpudfs.common import telemetry

    data = _rand(8 * 64 * 1024, seed=62)
    c, client = await _cluster_with_files(tmp_path, [("/rf/two", data)])
    try:
        reader, comb, addrs = _remote_reader(client, host_verify, origins=2)
        await client.get_file_info("/rf/two")
        slow, fast = addrs
        gate = _FetchGate(comb, held={slow})
        telemetry.enable()
        try:
            read = asyncio.create_task(reader.read_file_to_device_blocks(
                "/rf/two", verify="lazy"))
            # Both of the fast origin's rounds are served while the slow
            # origin's first frame is still open, and its second round
            # waits for the first.
            await _until(lambda: comb.blocks == 4, "the fast origin is done")
            assert gate.active[slow] == 1 and gate.issued.count(slow) == 1
            gate.release.set()
            blocks = await read
        finally:
            telemetry.disable()
            records = telemetry.drain()
        assert await _confirmed_bytes(reader, blocks) == data
        assert comb.blocks == 8
        assert gate.most == {slow: 1, fast: 1}
        depth = {r.attrs["round"]: (r.attrs["origin"], r.attrs["in_flight"])
                 for r in records if r.name == "combiner.fetch"}
        assert depth == {1: (slow, 1), 2: (fast, 2), 3: (fast, 2),
                         4: (slow, 1)}
    finally:
        await c.stop()


@pytest.mark.parametrize("host_verify", [True, False])
async def test_fused_read_one_origin_goes_a_round_at_a_time(tmp_path,
                                                            host_verify):
    """Traffic with one source behaves as before the read stage kept
    several rounds in flight: a round at a time, nothing overlapped."""
    from tpudfs.common import telemetry

    data = _rand(8 * 64 * 1024, seed=63)
    c, client = await _cluster_with_files(tmp_path, [("/rf/one", data)])
    try:
        reader, comb, addrs = _remote_reader(client, host_verify, origins=1)
        gate = _FetchGate(comb)
        telemetry.enable()
        try:
            blocks = await reader.read_file_to_device_blocks("/rf/one",
                                                             verify="lazy")
        finally:
            telemetry.disable()
            depth = [r.attrs["in_flight"] for r in telemetry.drain()
                     if r.name == "combiner.fetch"]
        assert await _confirmed_bytes(reader, blocks) == data
        assert comb.rounds == 4 and comb.blocks == 8
        assert depth == [1, 1, 1, 1]
        assert gate.most == {addrs[0]: 1}
    finally:
        await c.stop()


@pytest.mark.parametrize("host_verify", [True, False])
@pytest.mark.parametrize("failure", ["rpc_error", "blowup"])
async def test_fused_read_failed_frame_frees_its_origin(tmp_path, failure,
                                                        host_verify):
    """A frame that fails on one origin (an RpcError the fetch absorbs, or
    anything else the round does) falls its blocks back to the per-block
    path while the other origin's rounds complete, and frees its origin
    for the next round."""
    import grpc

    from tpudfs.common.rpc import RpcError

    data = _rand(8 * 64 * 1024, seed=64)
    c, client = await _cluster_with_files(tmp_path, [("/rf/bad", data)])
    try:
        reader, comb, addrs = _remote_reader(client, host_verify, origins=2)
        await client.get_file_info("/rf/bad")
        bad, good = addrs
        real = client._data_call
        frames = []

        async def data_call(addr, method, req, **kw):
            if addr == bad and method == "ReadBlocks":
                frames.append(len(req["block_ids"]))
                # Fail only once the good origin has been served: the bad
                # one must not have held it back.
                await _until(lambda: comb.blocks == 4,
                             "the good origin is done")
                if failure == "rpc_error":
                    raise RpcError(grpc.StatusCode.UNAVAILABLE, "injected")
                raise OSError("injected")
            return await real(addr, method, req, **kw)

        client._data_call = data_call
        blocks = await reader.read_file_to_device_blocks("/rf/bad",
                                                         verify="lazy")
        assert await _confirmed_bytes(reader, blocks) == data
        assert frames == [2, 2], "the bad origin was not freed"
        assert comb.blocks == 4
        assert [b.batch is not None for b in blocks] == [False, True] * 4
    finally:
        await c.stop()


@pytest.mark.parametrize("frames_fail", [False, True],
                         ids=["fused", "failed_frame"])
async def test_read_blocks_frames_are_counted_where_sent(tmp_path,
                                                         frames_fail):
    """``Client.read_blocks_frames`` / ``read_blocks_slots`` count every
    ``ReadBlocks`` frame the client sends, whether it is answered or not,
    and a block that falls back after a failed frame is counted where its
    ``ReadBlock`` is sent (``read_block_calls``), apart from the frames."""
    import grpc

    from tpudfs.common.rpc import RpcError

    data = _rand(8 * 64 * 1024, seed=68)
    c, client = await _cluster_with_files(tmp_path, [("/rf/cnt", data)])
    try:
        reader, comb, _ = _remote_reader(client, False, origins=1)
        await client.get_file_info("/rf/cnt")
        if frames_fail:
            real = client.block_pool.call

            async def call(rpc, addr, service, method, req, **kw):
                if method == "ReadBlocks":
                    raise RpcError(grpc.StatusCode.UNAVAILABLE, "injected")
                return await real(rpc, addr, service, method, req, **kw)

            client.block_pool.call = call
        before = (client.read_blocks_frames, client.read_blocks_slots,
                  client.read_block_calls)
        blocks = await reader.read_file_to_device_blocks("/rf/cnt",
                                                         verify="lazy")
        assert await _confirmed_bytes(reader, blocks) == data
        frames, slots, calls = (
            client.read_blocks_frames - before[0],
            client.read_blocks_slots - before[1],
            client.read_block_calls - before[2])
        assert (frames, slots) == (4, 8)  # 4 rounds of 2 blocks
        if frames_fail:
            assert comb.blocks == 0 and calls >= 8
        else:
            assert comb.blocks == 8 and calls == 0
    finally:
        await c.stop()


async def test_fused_read_rounds_land_in_place_through_a_blockport(tmp_path):
    """Nothing stubbed between the combiner and the socket: a full
    16-block round, and a round with one slot the origin cannot serve
    (its replica is gone: the slot comes back short, with no bytes),
    travel as real ReadBlocks frames. The kernel puts the good slots
    straight into the round buffer (all but what one recv brings along
    with the header), on both sides of the gap; the short slot falls back
    per block; the upload stage gets the same bytes as ever."""
    from tpudfs.common import blocknet, telemetry

    full = _rand(16 * 64 * 1024, seed=66)
    gap = _rand(16 * 64 * 1024, seed=67)
    c, client = await _cluster_with_files(
        tmp_path, [("/rf/full", full), ("/rf/gap", gap)])
    try:
        _, _, addrs = _remote_reader(client, False, origins=1)
        reader = HbmReader(client, jax.devices()[:1], batch_reads=16)
        comb = reader._combiner(reader.devices[0])
        block = (await client.get_file_info("/rf/gap"))["blocks"][5]
        origin = next(cs for cs in c.chunkservers
                      if cs.address == addrs[0])
        origin.store.block_path(block["block_id"]).unlink()
        origin.invalidate_cached(block["block_id"])

        async def read(path):
            telemetry.enable()
            try:
                blocks = await reader.read_file_to_device_blocks(
                    path, verify="lazy")
            finally:
                telemetry.disable()
                received = [r.attrs for r in telemetry.drain()
                            if r.name == "blockport.recv_payload"]
            return blocks, received

        for path, data, short in (("/rf/full", full, None),
                                  ("/rf/gap", gap, 5)):
            before = comb.blocks
            blocks, received = await read(path)
            frames = [a for a in received if a["method"] == "ReadBlocks"]
            good = 16 - (short is not None)
            assert comb.blocks - before == good
            assert [f["bytes"] for f in frames] == [good * 65536], \
                "not one frame of the good slots"
            direct = frames[0]["direct"]
            assert good * 65536 - blocknet._RX_BUF <= direct <= good * 65536
            # Every payload byte is in a span (the fall-back's own
            # ReadBlock, refused by the origin and served by the next
            # replica, too), and what landed in place is a part of it.
            assert sum(a["bytes"] for a in received) == 16 * 65536
            assert all(0 <= a["direct"] <= a["bytes"] for a in received)
            assert [b.batch is None for b in blocks] == \
                [i == short for i in range(16)]
            assert await _confirmed_bytes(reader, blocks) == data
    finally:
        await c.stop()


@pytest.mark.parametrize("host_verify", [True, False])
async def test_fused_read_cancel_with_rounds_in_flight(tmp_path,
                                                       host_verify):
    """Cancelling the read stage with a round open to each of two origins
    fails out every request (in flight and still pending), returns the
    rounds' buffers, ends the upload stage, and leaves a stage the next
    read restarts."""
    data = _rand(8 * 64 * 1024, seed=65)
    c, client = await _cluster_with_files(tmp_path, [("/rf/stop", data)])
    try:
        reader, comb, addrs = _remote_reader(client, host_verify, origins=2)
        meta = await client.get_file_info("/rf/stop")
        gate = _FetchGate(comb, held=set(addrs))
        reads = [asyncio.create_task(reader.read_block_to_device(
            b, reader.devices[0], verify="lazy")) for b in meta["blocks"]]
        await _until(lambda: sum(gate.active.values()) == 2,
                     "a round is open to each origin")
        assert len(comb._pending) == 4
        upload = comb._upload_task
        comb._read_task.cancel()
        done, pending = await asyncio.wait(reads, timeout=10)
        assert not pending, "a request was left waiting"
        assert all(isinstance(t.exception(), RuntimeError) for t in done)
        await asyncio.wait_for(upload, 10)
        assert comb._read_task is None and not comb._pending
        assert sum(gate.active.values()) == 0
        assert sum(len(v) for v in comb._buf_pool.values()) == 2
        assert comb.blocks == 0
        gate.release.set()
        blocks = await reader.read_file_to_device_blocks("/rf/stop",
                                                         verify="lazy")
        assert await _confirmed_bytes(reader, blocks) == data
        assert comb.blocks == 8
    finally:
        await c.stop()


def test_graft_dryrun_full_geometry_nine_devices():
    """dryrun at >= 9 devices runs the flagship one-RS(6,3)-shard-per-
    device geometry (self-provisioned bootstrap mesh; the session's own
    mesh caps at 8, so this exercises the driver branch end-to-end)."""
    import __graft_entry__ as g

    g.dryrun_multichip(9)


# ------------------------------------------------ native sweep pump (r5)


async def test_sweep_pump_roundtrip(tmp_path):
    """The native sweep pump serves whole file sets bit-exactly: producer
    thread drives fused pread+CRC, Python only device_puts rounds. Tail
    (non-512-aligned) blocks and files fall back per block."""
    files = [(f"/sw/f{i}", _rand(3 * 64 * 1024, seed=60 + i))
             for i in range(5)]
    files.append(("/sw/tail", _rand(64 * 1024 + 700, seed=70)))
    c, client = await _cluster_with_files(tmp_path, files)
    try:
        client.local_reads = True
        reader = HbmReader(client, jax.devices()[:1], batch_reads=8)
        blocks = await reader.sweep_paths_to_device(
            [p for p, _ in files], round_blocks=4, ring=2)
        assert all(b is not None and b.verified for b in blocks)
        await reader.confirm(blocks)
        it = iter(blocks)
        for path, data in files:
            meta = await client.get_file_info(path)
            got = b"".join(
                device_array_to_bytes(next(it).array, b["size"])
                for b in meta["blocks"])
            assert got == data, path
    finally:
        await c.stop()


@pytest.mark.parametrize("by", ["paths", "metas"])
async def test_sweep_pump_span_and_totals_say_the_team_ran(tmp_path, by):
    """``hbm.sweep`` carries how many producers the pump started and how
    many of its rounds were produced when asked for; the reader keeps the
    running totals beside ``sweep_blocks``."""
    from tpudfs.common import telemetry

    files = [(f"/sw/t{i}", _rand(3 * 64 * 1024, seed=75 + i))
             for i in range(3)]
    c, client = await _cluster_with_files(tmp_path, files)
    try:
        client.local_reads = True
        reader = HbmReader(client, jax.devices()[:1], batch_reads=8)
        paths = [p for p, _ in files]
        metas = [await client.get_file_info(p) for p in paths]
        seen = []
        for _ in range(2):
            telemetry.enable()
            try:
                if by == "paths":
                    blocks = await reader.sweep_paths_to_device(
                        paths, round_blocks=4, ring=2)
                else:
                    blocks = await reader.sweep_metas_to_device(
                        metas, round_blocks=4, ring=2)
            finally:
                telemetry.disable()
                records = telemetry.drain()
            assert len(blocks) == 9 and all(b.verified for b in blocks)
            (attrs,) = [r.attrs for r in records if r.name == "hbm.sweep"]
            assert attrs["blocks"] == 9 and attrs["rounds"] == 3
            assert 1 <= attrs["producers"] <= 4  # round_blocks
            assert 0 <= attrs["rounds_ready"] <= 3
            seen.append(attrs["rounds_ready"])
        assert reader.sweep_blocks == 18 and reader.sweep_rounds == 6
        assert reader.sweep_rounds_ready == sum(seen)
    finally:
        await c.stop()


async def test_sweep_pump_corruption_falls_back_and_recovers(tmp_path):
    """A corrupt local replica fails the pump's CRC check for that slot
    only; the per-block fallback excludes it and serves verified bytes
    from a healthy replica."""
    data = _rand(4 * 64 * 1024, seed=80)
    c, client = await _cluster_with_files(tmp_path, [("/sw/rot", data)])
    try:
        client.local_reads = True
        reader = HbmReader(client, jax.devices()[:1], batch_reads=8)
        prime = await reader.sweep_paths_to_device(["/sw/rot"])
        await reader.confirm(prime)
        await _corrupt_first_replica(c, client, "/sw/rot")
        blocks = await reader.sweep_paths_to_device(["/sw/rot"])
        await reader.confirm(blocks)
        meta = await client.get_file_info("/sw/rot")
        got = b"".join(
            device_array_to_bytes(b.array, m["size"])
            for b, m in zip(blocks, meta["blocks"]))
        assert got == data
    finally:
        await c.stop()


# ------------------------------- one rule each, one fallback, arrows one way


def test_read_path_leaves_import_nothing_above_them():
    """device_block, host_buffers and read_combiner load without the module
    above them: no import of hbm_reader, at the top or inside a function
    that loading runs."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import tpudfs.tpu.device_block, tpudfs.tpu.host_buffers\n"
        "import tpudfs.tpu.read_combiner\n"
        "assert 'tpudfs.tpu.hbm_reader' not in sys.modules, 'cycle'\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("block, fuses", [
    ({"size": 4096, "checksum_crc32c": 7, "ec_data_shards": 6}, False),
    ({"size": 4096, "checksum_crc32c": 0}, False),
    ({"size": 0, "checksum_crc32c": 7}, False),
    ({"size": 4096 + 100, "checksum_crc32c": 7}, False),
    ({"size": 4096, "checksum_crc32c": 7}, True),
], ids=["ec", "no-crc", "empty", "unaligned-tail", "aligned"])
def test_may_fuse(block, fuses):
    from tpudfs.tpu.read_combiner import chunk_aligned, may_fuse

    assert may_fuse(block) is fuses
    assert chunk_aligned(block["size"]) is (block["size"] % 512 == 0)


def _without_native_library(monkeypatch):
    from tpudfs.common import native

    monkeypatch.setattr(native, "get_lib", lambda: None)


async def test_sweep_without_pump_serves_every_block_per_block(
        tmp_path, monkeypatch):
    """No native library: every entry of the sweep is a fallback entry, and
    the per-block path returns them verified, in (file, block) order."""
    from tpudfs.common import telemetry

    files = [(f"/np/f{i}", _rand(3 * 64 * 1024, seed=90 + i))
             for i in range(3)]
    c, client = await _cluster_with_files(tmp_path, files)
    try:
        client.local_reads = True
        reader = HbmReader(client, jax.devices()[:1])
        metas = [await client.get_file_info(p) for p, _ in files]
        _without_native_library(monkeypatch)
        telemetry.enable()
        try:
            blocks = await reader.sweep_metas_to_device(metas)
        finally:
            telemetry.disable()
            records = telemetry.drain()
        assert [b.block_id for b in blocks] == \
            [b["block_id"] for m in metas for b in m["blocks"]]
        assert all(b.verified for b in blocks) and reader.sweep_blocks == 0
        fallbacks = [r.attrs for r in records if r.name == "sweep.fallback"]
        assert fallbacks == [{"blocks": 9}]
        assert [r.attrs for r in records if r.name == "hbm.sweep"] == [
            {"blocks": 9, "producers": 0, "rounds": 0, "rounds_ready": 0}]
        assert reader.sweep_rounds == reader.sweep_rounds_ready == 0
        got = b"".join(device_array_to_bytes(b.array, b.size) for b in blocks)
        assert got == b"".join(d for _, d in files)
    finally:
        await c.stop()


async def test_fused_local_round_without_library_falls_back(tmp_path,
                                                            monkeypatch):
    """A local round with no native library to pread it falls back whole,
    as a failed remote frame does; the file still reads bit-exactly."""
    data = _rand(4 * 64 * 1024, seed=95)
    c, client = await _cluster_with_files(tmp_path, [("/np/fu", data)])
    try:
        reader, comb = await _batched_reader(client, False)
        prime = await reader.read_file_to_device_blocks("/np/fu",
                                                        verify="lazy")
        await reader.confirm(prime)
        assert comb.blocks >= 1, "combiner never engaged"
        served = comb.blocks
        _without_native_library(monkeypatch)
        blocks = await reader.read_file_to_device_blocks("/np/fu",
                                                         verify="lazy")
        assert comb.blocks == served, "a round fused without the library"
        assert await _confirmed_bytes(reader, blocks) == data
    finally:
        await c.stop()
