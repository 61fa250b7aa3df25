"""Fault-tolerant sharded checkpoints: format, two-phase commit atomicity,
resumable saves, degraded restore, GC (client + master control-plane
exemption) and the stage→SIGKILL→restart blockstore regression."""

import asyncio
import json
import os
import signal
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from tests.test_master_service import MiniCluster
from tpudfs.chunkserver.blockstore import (
    BlockCorruptionError,
    BlockNotFoundError,
    BlockStore,
)
from tpudfs.client.client import ChecksumMismatchError, Client, DfsError
from tpudfs.common import ckptpaths
from tpudfs.common.checksum import crc32c
from tpudfs.common.resilience import deadline_scope
from tpudfs.common.rpc import RpcError
from tpudfs.testing.ckptchaos import assert_restores_bit_exact, ckpt_tree, trees_equal
from tpudfs.tpu.checkpoint import (
    CheckpointManager,
    CheckpointNotFoundError,
    IncompleteCheckpointError,
    pack_shard,
    unpack_shard,
)

REPO_ROOT = str(Path(__file__).resolve().parents[1])


# ------------------------------------------------------------- pure format


def test_pack_unpack_roundtrip_and_alignment():
    tree = ckpt_tree(3, 1)
    payload, specs = pack_shard(tree)
    # Deterministic: same tree -> byte-identical payload (the resume
    # probe's soundness rests on this).
    payload2, _ = pack_shard(dict(reversed(list(tree.items()))))
    assert payload == payload2
    for spec in specs:
        assert spec.offset % 512 == 0
    out = unpack_shard(payload, [s.to_dict() for s in specs])
    assert trees_equal(out, tree)


def test_unpack_detects_torn_payload():
    payload, specs = pack_shard({"w": np.arange(1024, dtype=np.int32)})
    torn = bytearray(payload)
    torn[100] ^= 0xFF
    with pytest.raises(ChecksumMismatchError):
        unpack_shard(bytes(torn), [s.to_dict() for s in specs])


def test_ckptpaths_parse():
    base = "/ckpt/run1"
    m = ckptpaths.manifest_path(base, 7)
    assert ckptpaths.parse_manifest_path(m) == (base, 7)
    assert ckptpaths.parse_manifest_path("/ckpt/run1/MANIFEST-xyz") is None
    p = ckptpaths.shard_data_path(base, 7, 2)
    assert ckptpaths.parse_step_path(p) == (base, 7)
    assert ckptpaths.parse_step_path("/user/data/file.bin") is None
    # A path that merely *mentions* the staging dir with no step component
    # is not staging.
    assert ckptpaths.parse_step_path("/a/.ckpt/notdigits/x") is None


# --------------------------------------------------------------- clusters


async def _ready(tmp_path, n_cs=3, block_size=64 * 1024, **kw):
    c = MiniCluster(tmp_path, n_masters=1, n_cs=n_cs, **kw)
    await c.start()
    leader = await c.leader()
    await c.wait_out_of_safe_mode(leader)
    client = Client(list(c.masters), rpc_client=c.client,
                    block_size=block_size)
    return c, client, leader


async def test_save_restore_roundtrip_host_and_device(tmp_path):
    import jax
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    try:
        device = jax.devices()[0]
        mgr = CheckpointManager(client, "/ckpt/run1", num_shards=2,
                                ec=(2, 1), reader=HbmReader(client, [device]))
        trees = {s: ckpt_tree(1, s) for s in range(2)}
        manifest = await mgr.save(1, trees)
        assert manifest["step"] == 1
        assert await mgr.list_steps() == [1]
        # Host restore: bit-exact through the replicated hot copy.
        assert_restores_bit_exact(await mgr.restore(), 1)
        # Device restore: blocks verified on-device, tensors assembled
        # from the word stream (bitcast f4/i4, host bounce for int8).
        dev_trees = await mgr.restore(1, device=device)
        assert_restores_bit_exact(
            {s: {k: np.asarray(v) for k, v in t.items()}
             for s, t in dev_trees.items()}, 1)
        for t in dev_trees.values():
            for arr in t.values():
                assert isinstance(arr, jax.Array)
    finally:
        await c.stop()


async def test_resumed_save_skips_durable_shards(tmp_path):
    c, client, _ = await _ready(tmp_path)
    try:
        base = "/ckpt/resume"
        mgr = CheckpointManager(client, base, num_shards=2, ec=(2, 1))
        # First attempt dies after shard 0 (simulated preemption: only
        # shard 0 was written, no commit).
        await mgr.save_shard(5, 0, ckpt_tree(5, 0))
        assert await mgr.list_steps() == []  # nothing visible
        # The restarted replica re-runs the whole save. Shard 0's payload
        # files are already durable -> probed and skipped, shard 1 written.
        mgr2 = CheckpointManager(client, base, num_shards=2, ec=(2, 1))
        await mgr2.save(5, {s: ckpt_tree(5, s) for s in range(2)})
        assert mgr2.stats["shards_skipped"] == 2  # shard 0: .bin + .ec
        assert await mgr2.latest_step() == 5
        assert_restores_bit_exact(await mgr2.restore(), 5)
    finally:
        await c.stop()


async def test_torn_checkpoint_never_listed_or_restorable(tmp_path):
    c, client, _ = await _ready(tmp_path)
    try:
        base = "/ckpt/torn"
        mgr = CheckpointManager(client, base, num_shards=2, ec=None)
        await mgr.save(1, {s: ckpt_tree(1, s) for s in range(2)})
        # Step 2 is interrupted mid-save: one shard landed, no manifest.
        await mgr.save_shard(2, 0, ckpt_tree(2, 0))
        assert await mgr.list_steps() == [1]
        with pytest.raises(CheckpointNotFoundError):
            await mgr.read_manifest(2)
        with pytest.raises(IncompleteCheckpointError):
            await mgr.commit(2)
        # Even a fully staged manifest that never published stays invisible.
        await client.create_file(
            ckptpaths.staged_manifest_path(base, 3), b"{}", overwrite=True)
        assert await mgr.list_steps() == [1]
        assert_restores_bit_exact(await mgr.restore(), 1)
    finally:
        await c.stop()


async def test_publish_is_idempotent_and_monotonic(tmp_path):
    c, client, _ = await _ready(tmp_path)
    try:
        base = "/ckpt/mono"
        mgr = CheckpointManager(client, base, num_shards=1, ec=None)
        await mgr.save(2, {0: ckpt_tree(2, 0)})
        # Replayed commit of the same step converges as a no-op.
        await mgr.commit(2)
        assert mgr.stats["already_published"] == 1
        assert await mgr.list_steps() == [2]
        # A zombie writer replaying an OLDER step is fenced at apply time.
        zombie = CheckpointManager(client, base, num_shards=1, ec=None)
        await zombie.save_shard(1, 0, ckpt_tree(1, 0))
        with pytest.raises(DfsError, match="stale"):
            await zombie.commit(1)
        assert await mgr.list_steps() == [2]
    finally:
        await c.stop()


async def test_restore_with_two_chunkservers_dead_via_ec(tmp_path):
    """Acceptance: 2 of 5 chunkservers permanently dead -> the EC cold
    copy reconstructs every shard, CRC-verified end-to-end."""
    c, client, _ = await _ready(tmp_path, n_cs=5)
    try:
        base = "/ckpt/degraded"
        mgr = CheckpointManager(client, base, num_shards=2, ec=(3, 2),
                                hot_copies=False)
        await mgr.save(1, {s: ckpt_tree(1, s) for s in range(2)})
        for i in (0, 1):  # permanent: processes stopped, never restarted
            c.heartbeats[i].stop()
            await c.chunkservers[i].stop()
        assert_restores_bit_exact(await mgr.restore(), 1)
    finally:
        await c.stop()


async def test_restore_falls_back_from_hot_to_ec(tmp_path):
    c, client, _ = await _ready(tmp_path, n_cs=5)
    try:
        base = "/ckpt/fallback"
        mgr = CheckpointManager(client, base, num_shards=1, ec=(3, 2))
        await mgr.save(1, {0: ckpt_tree(1, 0)})
        # Kill the hot copy outright; restore must degrade to EC
        # reconstruction per shard instead of failing.
        await client.delete_file(ckptpaths.shard_data_path(base, 1, 0))
        assert_restores_bit_exact(await mgr.restore(), 1)
        assert mgr.stats["degraded_shard_reads"] == 1
    finally:
        await c.stop()


async def test_prune_deletes_manifest_first_and_gc_incomplete(tmp_path):
    c, client, _ = await _ready(tmp_path)
    try:
        base = "/ckpt/gc"
        mgr = CheckpointManager(client, base, num_shards=1, ec=None)
        for step in (1, 2, 3):
            await mgr.save(step, {0: ckpt_tree(step, 0)})
        assert await mgr.prune(keep=2) == [1]
        assert await mgr.list_steps() == [2, 3]
        files = await client.list_files(ckptpaths.step_prefix(base, 1))
        assert files == []
        # Client-side incomplete GC: an abandoned (superseded) staging
        # prefix is removed; published data and fresh in-flight work stay.
        abandoned = ckptpaths.shard_data_path(base, 0, 0)
        await client.create_file(abandoned, b"abandoned save")
        await mgr.save_shard(4, 0, ckpt_tree(4, 0))  # in-flight, not stale
        deleted = await mgr.gc_incomplete(max_age_ms=10**9)
        assert deleted == [abandoned]
        assert await client.list_files(ckptpaths.step_prefix(base, 4)) != []
        assert_restores_bit_exact(await mgr.restore(), 3)
    finally:
        await c.stop()


async def test_master_ckpt_gc_shielded_and_shed_exempt(tmp_path, monkeypatch):
    """Satellite: incomplete-checkpoint GC is control-plane — it must run
    to completion under an expired ambient deadline AND while the
    admission shedder is saturated (the exact conditions that starve
    client-side cleanup)."""
    c, client, leader = await _ready(tmp_path)
    try:
        base = "/ckpt/mgc"
        mgr = CheckpointManager(client, base, num_shards=1, ec=None)
        await mgr.save(2, {0: ckpt_tree(2, 0)})
        # Unpublished, superseded staging file -> collectable.
        stale = ckptpaths.shard_data_path(base, 1, 0)
        await client.create_file(stale, b"superseded")
        # Fresh unpublished staging for a FUTURE step -> must be kept.
        live = ckptpaths.shard_data_path(base, 3, 0)
        await client.create_file(live, b"in-flight")

        # Saturate admission control: namespace RPCs shed...
        while leader.shedder.try_acquire():
            pass
        with pytest.raises(RpcError) as ei:
            await c.call(leader.address, "ListFiles", {"path": base})
        assert ei.value.code.name == "RESOURCE_EXHAUSTED"
        # ...but the GC proposes directly, shielded from the (expired)
        # ambient deadline, and still makes progress.
        with deadline_scope(0.001):
            await asyncio.sleep(0.01)
            await leader.run_ckpt_gc()
        assert leader.ckpt_gc_deleted >= 1
        for _ in range(leader.shedder.max_inflight):
            leader.shedder.release()
        assert await client.get_file_info(stale) is None
        assert await client.get_file_info(live) is not None
        # TTL rule: with the age floor at zero the fresh file goes too.
        monkeypatch.setenv("TPUDFS_CKPT_GC_AGE_SECS", "0")
        await leader.run_ckpt_gc()
        assert await client.get_file_info(live) is None
        # Published checkpoint data is never GC'd.
        assert_restores_bit_exact(await mgr.restore(), 2)
    finally:
        await c.stop()


# ------------------------------------------- stage -> SIGKILL -> restart

_CHILD = """
import os, signal, sys
from tpudfs.chunkserver.blockstore import BlockStore
store = BlockStore(sys.argv[1], sys.argv[2])
store.write_staged("blk1", b"x" * 4096, "tok1")
store.write_staged("blk2", b"y" * 8192, "tok2")
print("STAGED", flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""


def test_sigkill_between_stage_and_publish_boot_cleanup(tmp_path):
    """Stage blocks, SIGKILL before publish, restart: the owning store's
    boot cleanup removes the orphan tmps and no torn block is ever
    served."""
    hot, cold = tmp_path / "hot", tmp_path / "cold"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(hot), str(cold)],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "PYTHONPATH": REPO_ROOT},
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr
    assert "STAGED" in proc.stdout
    orphans = list(hot.glob("*.tmp-*"))
    assert orphans, "child should have left staged tmp files behind"
    store = BlockStore(hot, cold, owner=True)  # restart: boot cleanup
    assert not list(hot.glob("*.tmp-*"))
    assert not store.exists("blk1") and not store.exists("blk2")
    with pytest.raises(BlockNotFoundError):
        store.read_verified("blk1")


def test_corrupt_sidecar_quarantined_not_returned(tmp_path):
    """A published block whose bytes no longer match the CRC sidecar (or
    whose sidecar is mangled) must surface as BlockCorruptionError from
    every verified read — torn bytes are never handed back."""
    store = BlockStore(tmp_path / "hot", tmp_path / "cold", owner=True)
    data = np.random.default_rng(7).integers(
        0, 256, 100_000, dtype=np.uint8).tobytes()
    store.write("blk", data)
    assert store.read_verified("blk") == data
    # Flip one byte of the payload on disk.
    path = store.hot_dir / "blk"
    raw = bytearray(path.read_bytes())
    raw[12_345] ^= 0xFF
    path.write_bytes(bytes(raw))
    with pytest.raises(BlockCorruptionError):
        store.read_verified("blk")
    with pytest.raises(BlockCorruptionError):
        store.verify_full("blk")
    # Mangled sidecar header: also corruption, not data.
    (store.hot_dir / "blk.meta").write_bytes(b"JUNKJUNKJUNK")
    with pytest.raises(BlockCorruptionError):
        store.read_verified("blk")


# ------------------------------- mixed precision, device assembly (PR 31)

KIB = 1024
_DTYPES = ("bfloat16", "float16", "float32", "int32", "uint8")


def _bits(arr) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(arr)).reshape(-1).view(np.uint8)


def _mixed_trees(dtype: str, seed: int = 7) -> dict[int, dict]:
    """Two shards of ``dtype`` tensors made of random BYTES (NaN patterns
    included) in 64 KiB blocks: ``b`` and ``c`` straddle block boundaries
    (and, with rounds of 4 blocks, a round's), the odd-sized ``d`` leaves
    the last block short and not chunk-aligned."""
    import jax.numpy as jnp

    dt = np.dtype(jnp.dtype(dtype))
    rng = np.random.default_rng([seed, len(dtype)])

    def of(nbytes: int, shape=None) -> np.ndarray:
        arr = np.frombuffer(rng.bytes(nbytes), dtype=dt)
        return arr.reshape(shape) if shape else arr

    return {0: {"a": of(40 * KIB), "b": of(100 * KIB, (-1, 64)),
                "c": of(300 * KIB, (4, -1)), "d": of(777 * dt.itemsize),
                "step": np.int32(41)},
            1: {"e": of(70 * KIB, (2, 5, -1)), "f": of(dt.itemsize)}}


def _same(restored: dict, trees: dict) -> None:
    assert sorted(restored) == sorted(trees)
    for shard, tree in trees.items():
        assert sorted(restored[shard]) == sorted(tree)
        for name, want in tree.items():
            got = restored[shard][name]
            assert np.dtype(got.dtype) == np.asarray(want).dtype, name
            assert tuple(got.shape) == np.asarray(want).shape, name
            assert np.array_equal(_bits(got), _bits(want)), name


@pytest.mark.parametrize("path", ["host", "device"])
@pytest.mark.parametrize("dtype", _DTYPES)
async def test_mixed_precision_roundtrip_is_bit_identical(tmp_path, dtype,
                                                          path):
    import jax
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    try:
        device = jax.devices()[0]
        reader = HbmReader(client, [device], batch_reads=4)
        mgr = CheckpointManager(client, "/ckpt/mixed", num_shards=2, ec=None,
                                reader=reader)
        trees = _mixed_trees(dtype)
        manifest = await mgr.save(3, trees)
        by_name = {t["name"]: t for s in manifest["shards"]
                   for t in s["tensors"]}
        assert by_name["a"]["dtype"] == dtype  # the name, never "<V2"
        assert manifest["shards"][0]["size"] % 512  # a short last block
        if path == "host":
            _same(await mgr.restore(), trees)
            return
        restored = await mgr.restore(device=device)
        for tree in restored.values():
            assert all(isinstance(v, jax.Array) for v in tree.values())
        _same(restored, trees)
        # Through the fused rounds, and no byte of a 2- or 4-byte tensor
        # through the host.
        combiner = reader._combiners[device]
        assert combiner.rounds > 0 and combiner.blocks >= 7  # 6 + 1 whole
        state = sum(np.asarray(v).nbytes for t in trees.values()
                    for v in t.values())
        bounced = state - 4 if dtype == "uint8" else 0  # "step" is int32
        assert mgr.stats["tensor_bytes_host_bounce"] == bounced
        assert mgr.stats["tensor_bytes_device"] == state - bounced
    finally:
        await c.stop()


def test_assembly_across_rounds_of_mixed_shards_and_a_short_block():
    """The deterministic half of the above: blocks of two files arrive in
    fused rounds out of order and mixed; a tensor straddles the seam of
    two rounds; the last block is short and stands alone."""
    import jax
    import jax.numpy as jnp
    from tpudfs.tpu import ckpt_assemble
    from tpudfs.tpu.device_block import DeviceBatch, DeviceBlock

    device = jax.devices()[0]
    rows = 8  # block = 8 rows of 512 B
    bb = rows * 512
    tree = {"w": np.frombuffer(np.random.default_rng(5).bytes(3 * bb + 1024),
                               dtype=jnp.dtype("bfloat16")).reshape(-1, 128),
            "x": np.frombuffer(np.random.default_rng(6).bytes(2 * bb + 516),
                               dtype=np.float32),
            "z": np.arange(3, dtype=np.int16)}
    payload, specs = pack_shard(tree)
    other = np.random.default_rng(9).bytes(4 * bb)  # another shard's blocks
    nblocks = -(-len(payload) // bb)
    assert nblocks == 6 and len(payload) % 512

    def grid(data: bytes) -> np.ndarray:
        pad = -len(data) % 512
        return np.frombuffer(data + b"\0" * pad, "<u4").reshape(-1, 128)

    mine = [payload[j * bb:(j + 1) * bb] for j in range(nblocks)]
    foreign = [other[j * bb:(j + 1) * bb] for j in range(4)]
    # round A: [mine 2, foreign 0, mine 0, foreign 1]; round B: [foreign 2,
    # mine 4, mine 1, mine 3]; block 5 (short) alone.
    rounds = {"A": [mine[2], foreign[0], mine[0], foreign[1]],
              "B": [foreign[2], mine[4], mine[1], mine[3]]}
    batches = {k: DeviceBatch(
        words=jax.device_put(np.concatenate([grid(b) for b in v]), device),
        crcs=None, cpb=rows, nblocks=4) for k, v in rounds.items()}
    where = {0: ("A", 2), 1: ("B", 2), 2: ("A", 0), 3: ("B", 3), 4: ("B", 1)}
    blocks = [DeviceBlock(f"b{j}", None, bb, True, batch=batches[k],
                          batch_index=i) for j, (k, i) in where.items()]
    blocks.append(DeviceBlock("b5", jax.device_put(grid(mine[5]), device),
                              len(mine[5]), True))
    tensors = [s.to_dict() for s in specs]
    got, on_dev, bounced = ckpt_assemble.assemble_shard(
        tensors, [np.dtype(t["dtype"]) for t in tensors], len(payload),
        blocks, device, rows)
    _same({0: got}, {0: tree})
    assert (on_dev, bounced) == (sum(v.nbytes for v in tree.values()), 0)
    with pytest.raises(ValueError, match="do not make a file"):
        ckpt_assemble.assemble_shard(
            tensors, [np.dtype(t["dtype"]) for t in tensors], len(payload),
            blocks[:-1], device, rows)


async def test_device_restore_skips_a_staged_uncommitted_newer_step(tmp_path):
    import jax
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    try:
        device = jax.devices()[0]
        mgr = CheckpointManager(
            client, "/ckpt/torn", num_shards=2, ec=None,
            reader=HbmReader(client, [device], batch_reads=4))
        trees = _mixed_trees("bfloat16")
        await mgr.save(100, trees)
        newer = _mixed_trees("bfloat16", seed=8)
        await mgr.save_shard(200, 0, newer[0])  # staged, never committed
        assert await mgr.latest_step() == 100
        _same(await mgr.restore(device=device), trees)
        with pytest.raises(CheckpointNotFoundError):
            await mgr.restore(200, device=device)
    finally:
        await c.stop()


async def test_manifest_with_old_dtype_strings_still_loads(tmp_path):
    import jax
    from tpudfs.tpu.hbm_reader import HbmReader

    payload, specs = pack_shard({"w": np.arange(300, dtype=np.float32),
                                 "i": np.arange(7, dtype=np.int32)})
    old = [{**s.to_dict(), "dtype": np.dtype(s.dtype).str} for s in specs]
    assert {t["dtype"] for t in old} == {"<f4", "<i4"}
    out = unpack_shard(payload, old)
    assert out["w"].dtype == np.float32 and out["i"].dtype == np.int32
    c, client, _ = await _ready(tmp_path)
    try:
        device = jax.devices()[0]
        mgr = CheckpointManager(
            client, "/ckpt/old", num_shards=2, ec=None,
            reader=HbmReader(client, [device], batch_reads=4))
        trees = _mixed_trees("float32")
        manifest = await mgr.save(1, trees)
        for spec in manifest["shards"]:
            for t in spec["tensors"]:
                t["dtype"] = np.dtype(t["dtype"]).str  # as PR 7 wrote them
        for shard, tree in trees.items():
            _same({shard: await mgr.restore_shard(manifest, shard,
                                                  device=device)},
                  {shard: tree})
            _same({shard: await mgr.restore_shard(manifest, shard)},
                  {shard: tree})
    finally:
        await c.stop()


async def test_rot_under_every_replica_fails_the_restore_before_any_tensor(
        tmp_path, monkeypatch):
    import jax
    from tpudfs.tpu import ckpt_assemble
    from tpudfs.tpu.checkpoint import DegradedRestoreError
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    try:
        device = jax.devices()[0]
        mgr = CheckpointManager(
            client, "/ckpt/rot", num_shards=2, ec=None,
            reader=HbmReader(client, [device], batch_reads=4))
        manifest = await mgr.save(5, _mixed_trees("bfloat16"))
        meta = await client.get_file_info(manifest["shards"][0]["path"])
        bid = meta["blocks"][3]["block_id"]
        for cs in c.chunkservers:
            if cs.store.exists(bid):
                raw = bytearray(cs.store.read(bid))
                raw[1000] ^= 0x40
                cs.store.write(bid, bytes(raw))  # sidecar follows: silent
        handed = []
        real = ckpt_assemble.assemble_shard
        monkeypatch.setattr(
            ckpt_assemble, "assemble_shard",
            lambda *a, **kw: handed.append(a) or real(*a, **kw))
        with pytest.raises(DegradedRestoreError):
            await mgr.restore_shard(manifest, 0, device=device)
        assert handed == []  # the error, not a tensor
        with pytest.raises(DegradedRestoreError):
            await mgr.restore(device=device)
        # The healthy shard still restores; the rotten one never reached
        # the assembly.
        await mgr.restore_shard(manifest, 1, device=device)
        assert handed and all(a[0][0]["name"] == "e" for a in handed)
    finally:
        await c.stop()


def test_reference_ckpt_payload_equals_pack_shard_byte_for_byte():
    """The benchmark's plain reference against the program, seeded, small:
    the shard file the reference lays out is the one ``pack_shard`` makes
    of the same tensors."""
    import jax.numpy as jnp
    from benchmarks import reference_ckpt

    cfg = {"block_bytes": 64 * KIB, "assumed": {"num_shards": 3},
           "dataset": {
               "layer": "model.layers.1",
               "states": {"params": "bfloat16", "master": "float32"},
               "scalars": {"step": "int32"},
               "parameters": {"a.weight": [33, 7], "norm.weight": [5]},
               "experts_held": 2,
               "expert_parameters": {"experts.{e}.up.weight": [9, 11]}}}
    seed = 2**31 + 31
    assert len(reference_ckpt.table(cfg)) == 2 * 4 + 1
    for shard, names in enumerate(reference_ckpt.deal(cfg)):
        tree = {}
        for name in names:
            dtype, shape, data = reference_ckpt.tensor(seed, cfg, name)
            tree[name] = np.frombuffer(data, jnp.dtype(dtype)).reshape(shape)
        payload, specs = pack_shard(tree)
        assert payload == reference_ckpt.shard_payload(seed, cfg, shard)
        assert [(s.name, s.offset, s.size) for s in specs] \
            == reference_ckpt.layout(cfg, shard)[0]
        assert [s.dtype for s in specs] \
            == [reference_ckpt.table(cfg)[n][0] for n in names]


def test_what_a_tpu_cannot_make_bit_for_bit_bounces_through_the_host(
        monkeypatch):
    """A v5e packs float32 registers whenever XLA writes bfloat16 (NaN
    payloads and denormals go): bf16 gets its dtype in a Mosaic kernel run
    in the tensor's own shape, and what has no such shape, and float16,
    take the host bounce there; off the TPU everything stays on the
    device (the parametrised round trips above)."""
    import jax
    from tpudfs.tpu import ckpt_assemble
    from tpudfs.tpu.device_block import DeviceBlock

    bf16, f16 = np.dtype("bfloat16"), np.dtype(np.float16)
    assert ckpt_assemble.on_device(bf16, ()) and \
        ckpt_assemble.on_device(f16, (5,))
    monkeypatch.setattr(ckpt_assemble, "on_tpu", lambda: True)
    for shape, view in [((3072, 2048), (3072, 2048)), ((3, 100), (3, 100)),
                        ((2048,), (16, 128)), ((8, 64, 512), (512, 512)),
                        ((2, 3, 32, 7), (192, 7)), ((2, 5, 7168), None),
                        ((777,), None), ((), None)]:
        assert ckpt_assemble._relabel_shape(shape) == view
        assert ckpt_assemble.on_device(bf16, shape) is (view is not None)
    assert not ckpt_assemble.on_device(f16, (4, 4))
    for dtype in (np.float32, np.int32, np.uint32, np.int16, np.uint16):
        assert ckpt_assemble.on_device(np.dtype(dtype), (3,))
    assert not ckpt_assemble.on_device(np.dtype(np.uint8), (4,))
    assert not ckpt_assemble.on_device(np.dtype(np.float64), (4,))
    # float16 through the assembly as a TPU would run it: bounced, counted,
    # still bit for bit (no bf16 here, so no Mosaic kernel is called).
    device = jax.devices()[0]
    tree = {"h": np.frombuffer(np.random.default_rng(3).bytes(3000),
                               dtype=np.float16).reshape(3, -1),
            "w": np.frombuffer(np.random.default_rng(4).bytes(4000),
                               dtype=np.float32)}
    payload, specs = pack_shard(tree)
    rows = -(-len(payload) // 512)
    grid = np.frombuffer(payload + b"\0" * (-len(payload) % 512),
                         "<u4").reshape(-1, 128)
    block = DeviceBlock("b0", jax.device_put(grid, device), len(payload),
                        True)
    tensors = [s.to_dict() for s in specs]
    got, on_dev, bounced = ckpt_assemble.assemble_shard(
        tensors, [np.dtype(t["dtype"]) for t in tensors], len(payload),
        [block], device, rows)
    _same({0: got}, {0: tree})
    assert (on_dev, bounced) == (4000, 3000)
