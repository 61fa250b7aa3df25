"""Restore under another layout (``CheckpointManager.restore(target=...)``)
on four of the CPU's virtual devices, at small widths: a checkpoint saved
as layout A (two ranks, stacked experts split by rank, the dense part
saved once by rank 0) restores as layout B (a 2x2 mesh ``('expert',
'model')``: experts split 2 ways, rows, columns and heads 2 ways, the
rest replicated), every chip's shard bit for bit against the plain
reference (``benchmarks/reference_reshard.py``)."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmarks import reference_reshard as ref
from tests.test_checkpoint import _ready, _same
from tpudfs.tpu.checkpoint import (
    CheckpointManager,
    CheckpointNotFoundError,
    DegradedRestoreError,
    Piece,
    TensorSpec,
)

KIB = 1024
SEED = 2**31 + 4040
CFG = {
    "block_bytes": 64 * KIB,
    "assumed": {"ranks_saved": [0, 1], "experts_per_rank": 2,
                "files_per_rank": 2, "dedup_rank": 0},
    "dataset": {
        "layer": "model.layers.1",
        "states": {"params": "bfloat16", "master": "float32"},
        "scalars": {"step": "int32"},
        "parameters": {"self_attn.q_proj.weight": [96, 256],
                       "self_attn.o_proj.weight": [128, 96],
                       "input_layernorm.weight": [64]},
        "experts_published": 16,
        "experts_held": 4,
        "expert_parameters": {"mlp.experts.gate_proj.weight": [44, 256],
                              "mlp.experts.down_proj.weight": [256, 44]}},
    "target": {"mesh": {"expert": 2, "model": 2},
               "specs": {"mlp.experts.gate_proj.weight":
                         ["expert", "model", None],
                         "mlp.experts.down_proj.weight":
                         ["expert", None, "model"],
                         "self_attn.q_proj.weight": ["model", None],
                         "self_attn.o_proj.weight": [None, "model"]}},
}
L = "model.layers.1"


def _np(dtype: str, shape, data: bytes) -> np.ndarray:
    import jax.numpy as jnp

    return np.frombuffer(data, jnp.dtype(dtype)).reshape(shape)


def _trees(cfg, step="published"):
    """Layout A as the ranks save it: ``(trees, pieces)`` by shard."""
    tensors = ref.table(cfg)
    trees, pieces = {}, {}
    for shard, (rank, names) in enumerate(ref.shards(cfg)):
        trees[shard], pieces[shard] = {}, {}
        for name in names:
            start, shape = ref.rank_pieces(cfg, rank)[name]
            trees[shard][name] = _np(tensors[name][0], shape,
                                     ref.piece_bytes(SEED, cfg, name, rank,
                                                     step))
            pieces[shard][name] = Piece(tensors[name][1], start)
    return trees, pieces


def _target(cfg, devices=None):
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from tpudfs.tpu.ckpt_reshard import Target

    axes = cfg["target"]["mesh"]
    devices = devices or jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(tuple(axes.values())),
                tuple(axes))
    specs, index = {}, {}
    for name, (_d, gshape, rng) in ref.table(cfg).items():
        spec = ref.spec_of(cfg, name)
        if spec:
            specs[name] = P(*spec)
        if tuple(b - a for a, b in rng) != gshape:
            index[name] = rng
    return Target(mesh, specs, index)


def _bits(arr) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(arr)).reshape(-1).view(np.uint8)


def _check_against_reference(restored: dict, target, cfg=CFG) -> int:
    """Every chip's shard of every tensor against the reference; returns
    the shards compared."""
    tensors = ref.table(cfg)
    assert sorted(restored) == sorted(tensors)
    devices = list(target.mesh.devices.flat)
    compared = 0
    for name, arr in restored.items():
        dtype, gshape, rng = tensors[name]
        assert str(arr.dtype) == dtype, name
        assert tuple(arr.shape) == ref.host_shape(tensors[name]), name
        by_device = {s.device: s for s in arr.addressable_shards}
        shards = ref.device_shards(SEED, cfg, name)
        for chip, device in enumerate(devices):
            _dt, shape, want = shards[chip]
            got = by_device[device].data
            assert tuple(got.shape) == shape, (name, chip)
            assert np.array_equal(_bits(got), np.frombuffer(want, np.uint8)),\
                (name, chip)
            compared += 1
    return compared


async def _saved(tmp_path, cfg=CFG, batch_reads=4, devices=None):
    import jax

    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    devices = devices or jax.devices()[:4]
    nshards = len(ref.shards(cfg))
    mgr = CheckpointManager(client, "/ckpt/reshard", num_shards=nshards,
                            ec=None, reader=HbmReader(client, devices,
                                                      batch_reads=batch_reads))
    trees, pieces = _trees(cfg)
    manifest = await mgr.save(100, trees, pieces=pieces)
    return c, client, mgr, manifest, trees


# ------------------------------------------------------------------ format


def test_piece_metadata_in_the_spec_and_old_specs_still_load():
    from tpudfs.tpu.checkpoint import pack_shard

    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    _payload, specs = pack_shard({"w": arr, "v": arr},
                                 {"w": Piece((9, 4), (3, 0)),
                                  "v": Piece((3, 4), (0, 0))})
    by = {s.name: s.to_dict() for s in specs}
    assert by["w"]["global_shape"] == [9, 4] and by["w"]["start"] == [3, 0]
    # A piece that is the whole tensor is recorded as a whole tensor: the
    # spec of today's format, key for key.
    assert set(by["v"]) == {"name", "dtype", "shape", "offset", "size",
                            "crc32c"}
    assert TensorSpec.from_dict(by["w"]).start == (3, 0)
    assert TensorSpec.from_dict(by["v"]).global_shape is None
    with pytest.raises(ValueError, match="does not fit"):
        pack_shard({"w": arr}, {"w": Piece((4, 4), (3, 0))})


def test_the_reference_layout_is_what_pack_shard_makes():
    from tpudfs.tpu.checkpoint import pack_shard

    trees, pieces = _trees(CFG)
    for shard in trees:
        payload, specs = pack_shard(trees[shard], pieces[shard])
        assert payload == ref.shard_payload(SEED, CFG, shard)
        assert [(s.name, s.offset, s.size) for s in specs] \
            == ref.layout(CFG, shard)[0]


def test_the_partition_covers_the_host_share_once():
    """The union of the four chips' shards, each element counted once, is
    the reference's whole host share; what two or four chips hold is the
    duplication the chip-to-chip move has to carry."""
    tensors = ref.table(CFG)
    dup = 0
    for name, entry in tensors.items():
        shape = ref.host_shape(entry)
        seen = np.zeros(shape or (1,), np.int32)
        for chip in range(4):
            seen[ref.device_index(CFG, name, chip) or (slice(None),)] += 1
        assert seen.min() >= 1, name
        dup += int((seen - 1).sum()) * ref.ITEMSIZE[entry[0]]
    # q (2 chips a half), o (2), the norm and step (4 each)
    q, o, norm = 96 * 256, 128 * 96, 64
    assert dup == (q + o) * (2 + 4) + norm * 3 * (2 + 4) + 3 * 4


# ----------------------------------------------------------------- restore


async def test_layout_a_restores_as_layout_b_bit_for_bit(tmp_path):
    import jax

    c, client, mgr, manifest, _trees_ = await _saved(tmp_path)
    try:
        target = _target(CFG)
        restored = await mgr.restore(target=target)
        jax.block_until_ready(restored)
        assert _check_against_reference(restored, target) \
            == 4 * len(ref.table(CFG))
        # each kind is there: a row split, a column split whose half (22
        # bf16 = 44 B) is not a row of 512 B, 2-chip duplicates, replicated
        for name, spec in [(f"params/{L}.mlp.experts.gate_proj.weight",
                            ("expert", "model", None)),
                           (f"params/{L}.mlp.experts.down_proj.weight",
                            ("expert", None, "model")),
                           (f"master/{L}.self_attn.q_proj.weight",
                            ("model", None)),
                           ("step", ())]:
            assert tuple(restored[name].sharding.spec) == spec
        down = restored[f"params/{L}.mlp.experts.down_proj.weight"]
        assert down.addressable_shards[0].data.shape == (2, 256, 22)
        # random bf16 bytes hold NaN payloads and denormals: kept
        bits = np.asarray(restored[f"params/{L}.self_attn.q_proj.weight"]) \
            .view(np.uint16)
        exp = bits & 0x7F80
        assert ((exp == 0x7F80) & (bits & 0x7F) != 0).any()  # NaN payloads
        assert ((exp == 0) & (bits & 0x7F) != 0).any()  # denormals
        # each needed block crossed the bus once; what several chips hold
        # moved chip to chip
        s = mgr.stats
        assert s["reshard_h2d_bytes"] == s["reshard_unique_bytes"] \
            == sum(sh["size"] for sh in manifest["shards"])
        q, o, norm = 96 * 256, 128 * 96, 64
        duplicated = (q + o) * (2 + 4) + norm * 3 * (2 + 4) + 3 * 4
        assert s["reshard_ici_bytes"] >= duplicated
        assert s["reshard_pieces"] > 0 and s["tensor_bytes_host_bounce"] == 0
        assert s["restored_shards"] == len(manifest["shards"])
    finally:
        await c.stop()


async def test_the_torn_step_is_never_seen(tmp_path):
    c, client, mgr, _manifest, _t = await _saved(tmp_path)
    try:
        torn, pieces = _trees(CFG, "torn")
        await mgr.save_shard(200, 0, torn[0], pieces=pieces[0])
        target = _target(CFG)
        assert await mgr.latest_step() == 100
        _check_against_reference(await mgr.restore(target=target), target)
        with pytest.raises(CheckpointNotFoundError):
            await mgr.restore(200, target=target)
    finally:
        await c.stop()


async def test_rot_under_every_replica_raises_before_any_shard(
        tmp_path, monkeypatch):
    from tpudfs.tpu import ckpt_reshard

    c, client, mgr, manifest, _t = await _saved(tmp_path)
    try:
        meta = await client.get_file_info(manifest["shards"][0]["path"])
        bid = meta["blocks"][1]["block_id"]
        for cs in c.chunkservers:
            if cs.store.exists(bid):
                raw = bytearray(cs.store.read(bid))
                raw[700] ^= 0x10
                cs.store.write(bid, bytes(raw))  # sidecar follows: silent
        handed = []
        real = ckpt_reshard.assemble
        monkeypatch.setattr(ckpt_reshard, "assemble",
                            lambda *a: handed.append(a) or real(*a))
        with pytest.raises(DegradedRestoreError):
            await mgr.restore(target=_target(CFG))
        assert handed == []  # the error, never a wrong piece
    finally:
        await c.stop()


async def test_a_block_that_fails_on_the_device_is_read_again(
        tmp_path, monkeypatch):
    """One block's on-device CRC verdict is wrong once (the per-block
    path: on the CPU a round's blocks are verified on the host as they
    arrive): the block is read again through the verified path, counted
    as another upload, and every shard still comes out right."""
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, mgr, _manifest, _t = await _saved(tmp_path, batch_reads=0)
    try:
        real = HbmReader.confirm
        spoiled = []

        async def confirm(self, blocks, **kw):
            for b in blocks:
                if not spoiled and b.pending_crc is not None:
                    b.expected_crc ^= 1
                    spoiled.append(b)
            await real(self, blocks, **kw)

        monkeypatch.setattr(HbmReader, "confirm", confirm)
        target = _target(CFG)
        restored = await mgr.restore(target=target)
        assert spoiled
        _check_against_reference(restored, target)
        s = mgr.stats
        assert s["reshard_h2d_bytes"] == s["reshard_unique_bytes"] \
            + spoiled[0].size
    finally:
        await c.stop()


# ------------------------------------------- today's format, today's path


async def test_the_saved_layout_on_one_chip_plans_to_todays_restore(
        tmp_path):
    """A whole-tensor checkpoint: no target, and the identity target
    (the saved layout on one chip), read the same blocks in the same
    rounds through the same programs and hand over the same bits."""
    import jax
    from jax.sharding import Mesh

    from tests.test_checkpoint import _mixed_trees
    from tpudfs.common import telemetry
    from tpudfs.tpu import ckpt_assemble, ckpt_reshard
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    try:
        device = jax.devices()[0]
        trees = _mixed_trees("bfloat16")

        def manager():
            return CheckpointManager(
                client, "/ckpt/same", num_shards=2, ec=None,
                reader=HbmReader(client, [device], batch_reads=4))

        await manager().save(3, trees)
        runs = {}
        for how in ("none", "identity"):
            mgr = manager()
            ckpt_assemble._assembler.cache_clear()
            telemetry.enable()
            try:
                if how == "none":
                    got = await mgr.restore(device=device)
                else:
                    target = ckpt_reshard.Target(
                        Mesh(np.array([device]), ("x",)))
                    flat = await mgr.restore(target=target)
                    got = {s: {n: flat[n] for n in t}
                           for s, t in trees.items()}
            finally:
                records = telemetry.drain()
                telemetry.disable()
            combiner = mgr.reader._combiners[device]
            runs[how] = (
                sorted((r.name, tuple(sorted(r.attrs.items())))
                       for r in records if r.name.startswith("ckpt.")
                       and r.name != "ckpt.restore"),
                combiner.rounds, combiner.blocks,
                ckpt_assemble._assembler.cache_info().misses,
                {k: v for k, v in mgr.stats.items()})
            _same(got, trees)
        assert runs["none"] == runs["identity"]
        assert not any(name == "ckpt.plan" for name, _a in runs["none"][0])
    finally:
        await c.stop()


async def test_an_old_format_manifest_restores_onto_a_target(tmp_path):
    """A manifest of today's format (whole tensors, no piece keys) is the
    pieces at 0: restored onto the 2x2 as replicated and split tensors."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from tpudfs.tpu import ckpt_reshard
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    try:
        devices = jax.devices()[:4]
        mgr = CheckpointManager(client, "/ckpt/old-format", num_shards=2,
                                ec=None, reader=HbmReader(client, devices,
                                                          batch_reads=4))
        rng = np.random.default_rng(3)
        trees = {0: {"w": rng.standard_normal((64, 300)).astype(np.float32)},
                 1: {"b": np.frombuffer(rng.bytes(2 * 40 * 128),
                                        np.dtype("bfloat16")).reshape(40, 128),
                     "step": np.int32(7)}}
        manifest = await mgr.save(1, trees)
        assert json.dumps(manifest).count("global_shape") == 0
        mesh = Mesh(np.array(devices).reshape(2, 2), ("a", "b"))
        target = ckpt_reshard.Target(mesh, {"w": P(None, "b"),
                                            "b": P(("a", "b"))})
        got = await mgr.restore(target=target)
        for name, want in (("w", trees[0]["w"]), ("b", trees[1]["b"]),
                           ("step", trees[1]["step"])):
            assert np.array_equal(_bits(got[name]), _bits(want)), name
        assert got["b"].addressable_shards[0].data.shape == (10, 128)
        assert got["step"].shape == ()
    finally:
        await c.stop()


def test_what_the_planner_refuses():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from tpudfs.tpu import ckpt_reshard
    from tpudfs.tpu.checkpoint import _dtype_of

    def manifest(*tensors):
        return {"shards": [{"shard": 0, "size": 4096,
                            "tensors": list(tensors)}]}

    t = {"name": "w", "dtype": "float32", "shape": [6, 4], "offset": 0,
         "size": 96, "crc32c": 0}
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    with pytest.raises(ckpt_reshard.ReshardError, match="does not divide"):
        ckpt_reshard.plan(manifest(t), ckpt_reshard.Target(
            mesh, {"w": P("x")}), _dtype_of, 64 * KIB)
    half = {**t, "shape": [3, 4], "global_shape": [6, 4], "start": [0, 0]}
    with pytest.raises(ckpt_reshard.ReshardError, match="cover"):
        ckpt_reshard.plan(manifest(half), ckpt_reshard.Target(mesh),
                          _dtype_of, 64 * KIB)
    with pytest.raises(ckpt_reshard.ReshardError, match="2- and 4-byte"):
        ckpt_reshard.plan(manifest({**t, "dtype": "uint8", "size": 24}),
                          ckpt_reshard.Target(mesh), _dtype_of, 64 * KIB)



async def test_a_bf16_shard_the_chip_lays_out_transposed(tmp_path,
                                                         monkeypatch):
    """Where the chip lays a bf16 shard out other than row-major (a v5e
    puts the 704 of (8, 2048, 704) major to the 2048), the relabelling runs
    on the bits moved into that order and the way back is free; a shard
    whose moved shape the kernel has no view of bounces. Played here with
    the kernel's stand-in, an exact bitcast, and every 2- and 3-D bf16
    shard laid out minor dims first."""
    import jax
    from jax import lax

    from tpudfs.tpu import ckpt_assemble, ckpt_reshard

    def swapped(name, shape, device):
        return (tuple(range(len(shape) - 2)) + (len(shape) - 1,
                                                len(shape) - 2)
                if len(shape) >= 2 else None)

    monkeypatch.setattr(ckpt_assemble, "on_tpu", lambda: True)
    monkeypatch.setattr(ckpt_reshard, "on_tpu", lambda: True)
    monkeypatch.setattr(ckpt_assemble, "default_order", swapped)
    monkeypatch.setattr(ckpt_assemble, "_relabel_bf16",
                        lambda bits: lax.bitcast_convert_type(
                            bits, jax.numpy.bfloat16))
    ckpt_reshard._assembler.cache_clear()
    c, client, mgr, _manifest, _t = await _saved(tmp_path)
    try:
        target = _target(CFG)
        _check_against_reference(await mgr.restore(target=target), target)
        # down_proj's (2, 256, 22) moves to (2, 22, 256): no view, bounced;
        # so does the 64-wide norm, as on a TPU without any order
        down, norm = 2 * 256 * 22 * 2, 64 * 2
        assert mgr.stats["tensor_bytes_host_bounce"] == 4 * (down + norm)
    finally:
        ckpt_reshard._assembler.cache_clear()
        await c.stop()
