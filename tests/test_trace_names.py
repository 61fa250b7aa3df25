"""The names the trace's readers find the device programs by, pinned where
the programs are made: each lowers on the CPU under the module name the
benchmark's readers match (``crc_verify_roofline_pct``,
``rs_decode_roofline_pct``, ``ici_round_ms``, ``ckpt_assemble_roofline_pct``,
``reshard_ici_roofline_pct``, ``reshard_assemble_roofline_pct``) and
carries its ``tpudfs.*`` scope in the lowered text. The restore's
``ckpt.*`` span names, which five readers match (and those of a restore
under another layout, two more), and the Grain infeed's six ``infeed.*``
spans, which four more match, are pinned beside them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudfs.tpu.crc32c_pallas import (
    WORDS_PER_CHUNK,
    batch_block_crc_device,
    block_crc_device,
)
from tpudfs.tpu.ici_replication import (
    EcShardGather,
    EcShardScatter,
    IciReplicator,
    make_mesh,
)
from tpudfs.tpu.rs_pallas import (
    decode_rows,
    rs_decode_block,
    rs_decode_device,
    rs_encode_device,
)

CHUNKS = 8


def _words(rows: int):
    return jax.ShapeDtypeStruct((rows, WORDS_PER_CHUNK), jnp.uint32)


def _crc_batch():
    return batch_block_crc_device.lower(_words(4 * CHUNKS), nblocks=4)


def _crc_block():
    return block_crc_device.lower(_words(CHUNKS))


def _rs_encode():
    return jax.jit(lambda d: rs_encode_device(d, 6, 3)).lower(
        jax.ShapeDtypeStruct((6, 1024), jnp.uint8))


def _rs_decode():
    present = (0, 2, 3, 4, 5, 6)
    return jax.jit(lambda a: rs_decode_device(a, 6, 3, present)).lower(
        jax.ShapeDtypeStruct((6, 1024), jnp.uint8))


def _rs_decode_block():
    """The degraded read's program, RS(6,3) over a 64 KiB block."""
    slen = -(-65536 // 6)
    return rs_decode_block.lower(
        jax.ShapeDtypeStruct((6, decode_rows(slen), WORDS_PER_CHUNK),
                             jnp.uint32),
        jax.ShapeDtypeStruct((6, 6), jnp.uint8),
        slen=slen, size=65536)


def _ckpt_assemble():
    """One shard layout: a bf16, an f32 and a bounced uint8 tensor."""
    from tpudfs.tpu import ckpt_assemble

    layout = ((0, 300, "bfloat16", (3, 100)), (2, 128, "float32", (128,)),
              (3, 5, "uint8", (5,)))
    return ckpt_assemble.assembler(layout).lower(_words(4 * CHUNKS))


def _ckpt_gather():
    from tpudfs.tpu.ckpt_assemble import ckpt_assemble_gather

    return ckpt_assemble_gather.lower(
        _words(5 * CHUNKS), _words(4 * CHUNKS),
        jax.ShapeDtypeStruct((4,), jnp.int32), nblocks=4)


def _reshard_plan():
    """A two-rank save of one (8, 256) bf16 tensor restored as row halves
    on a 2x2: one chip-to-chip move, one assembly a chip."""
    from jax.sharding import Mesh, PartitionSpec as P

    from tpudfs.tpu import ckpt_reshard
    from tpudfs.tpu.checkpoint import _dtype_of

    def piece(shard, start):
        return {"shard": shard, "size": 2048, "tensors": [{
            "name": "w", "dtype": "bfloat16", "shape": [4, 256],
            "offset": 0, "size": 2048, "crc32c": 0, "global_shape": [8, 256],
            "start": [start, 0]}]}

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("e", "m"))
    return ckpt_reshard.plan({"shards": [piece(0, 0), piece(1, 4)]},
                             ckpt_reshard.Target(mesh, {"w": P("m")}),
                             _dtype_of, 4 * CHUNKS * 512)


def _reshard_ici():
    from tpudfs.tpu import ckpt_reshard

    plan = _reshard_plan()
    _mesh, program = ckpt_reshard._ici_program(
        tuple(plan.devices), plan.block_rows, plan.sends)
    return program.lower(_words(4 * plan.stage_rows))


def _reshard_assemble():
    from tpudfs.tpu import ckpt_reshard

    plan = _reshard_plan()
    return ckpt_reshard._assembler(plan.layouts[0], False).lower(
        _words(plan.stage_rows), _words(plan.block_rows))


def _ici_replicate():
    mesh = make_mesh(jax.devices()[:4])
    return IciReplicator(mesh, replication=3)._fn.lower(
        _words(4 * CHUNKS), jax.ShapeDtypeStruct((4 * CHUNKS,), jnp.uint32))


def _ec_scatter():
    mesh = make_mesh(jax.devices()[:4])
    return EcShardScatter(mesh, 2, 2)._fn.lower(_words(4 * CHUNKS))


def _ec_gather():
    mesh = make_mesh(jax.devices()[:4])
    gather = EcShardGather(mesh, 2, 2)
    shards = jax.ShapeDtypeStruct((4 * 4, CHUNKS // 2, WORDS_PER_CHUNK),
                                  jnp.uint32)
    return gather._fn.lower(shards, gather._matrices(None))


@pytest.mark.parametrize("lower,module,scope", [
    (_crc_batch, "jit_batch_block_crc_device", "tpudfs.crc_verify"),
    (_crc_block, "jit_block_crc_device", "tpudfs.crc_verify"),
    (_rs_encode, None, "tpudfs.rs_encode"),
    (_rs_decode, None, "tpudfs.rs_decode"),
    (_rs_decode_block, "jit_rs_decode_block", "tpudfs.rs_decode"),
    (_ckpt_assemble, "jit_ckpt_assemble", "tpudfs.ckpt_assemble"),
    (_ckpt_gather, "jit_ckpt_assemble_gather", "tpudfs.ckpt_assemble"),
    (_reshard_ici, "jit_ckpt_reshard_ici", "tpudfs.ckpt_reshard_ici"),
    (_reshard_assemble, "jit_ckpt_reshard_assemble", "tpudfs.ckpt_reshard"),
    (_ici_replicate, "jit_step", "tpudfs.ici_replicate"),
    (_ec_scatter, "jit_step", "tpudfs.ec_scatter"),
    (_ec_gather, "jit_step", "tpudfs.ec_gather"),
])
def test_program_lowers_under_its_module_name_with_its_scope(
        lower, module, scope):
    text = lower().as_text(debug_info=True)
    if module is not None:
        assert f"module @{module} " in text, text[:200]
    assert scope in text


async def test_a_device_restore_records_its_ckpt_spans_under_one_restore(
        tmp_path):
    """The names ``ckpt_meta_ms_per_restore``, ``ckpt_read_ms_per_restore``,
    ``ckpt_assemble_ms_per_restore`` and ``ckpt_assemble_roofline_pct``
    match, their attrs, and what hangs under what."""
    import numpy as np

    from tests.test_checkpoint import _ready
    from tpudfs.common import telemetry
    from tpudfs.tpu.checkpoint import CheckpointManager
    from tpudfs.tpu.hbm_reader import HbmReader

    c, client, _ = await _ready(tmp_path)
    try:
        device = jax.devices()[0]
        mgr = CheckpointManager(
            client, "/ckpt/spans", num_shards=2, ec=None,
            reader=HbmReader(client, [device], batch_reads=4))
        trees = {s: {"w": np.arange(40_000, dtype=np.float32) + s,
                     "h": np.arange(999, dtype=np.float16)}
                 for s in range(2)}
        manifest = await mgr.save(9, trees)
        telemetry.enable()
        try:
            await mgr.restore(device=device)
        finally:
            records = telemetry.drain()
            telemetry.disable()
    finally:
        await c.stop()
    by_name: dict = {}
    for r in records:
        by_name.setdefault(r.name, []).append(r)
    (whole,) = by_name["ckpt.restore"]
    assert whole.attrs == {"step": 9, "shards": 2}
    children = {"ckpt.latest_step": 1, "ckpt.manifest": 1,
                "ckpt.read_shard": 2, "ckpt.confirm": 2,
                "ckpt.combined_crc": 2, "ckpt.assemble": 2}
    assert {n for n in by_name if n.startswith("ckpt.")} \
        == {"ckpt.restore", *children}
    for name, count in children.items():
        assert len(by_name[name]) == count, name
        assert all(r.parent_id == whole.span_id
                   and r.request_id == whole.request_id
                   for r in by_name[name]), name
    assert by_name["ckpt.manifest"][0].attrs == {"step": 9}
    sizes = {s["shard"]: s["size"] for s in manifest["shards"]}
    for r in by_name["ckpt.read_shard"]:
        assert r.attrs["source"] == "hot" and r.attrs["blocks"] == 3
    for r in by_name["ckpt.assemble"]:
        assert r.attrs == {"shard": r.attrs["shard"], "tensors": 2,
                           "bytes": sizes[r.attrs["shard"]]}
    # The reader's and the combiner's spans come for free underneath.
    reads = {r.span_id for r in by_name["ckpt.read_shard"]}
    assert len(by_name["hbm.read_file"]) == 2
    assert {r.parent_id for r in by_name["hbm.read_file"]} == reads
    assert by_name["combiner.fetch"] and by_name["client.get_file_info"]


async def test_an_infeed_epoch_records_its_six_spans(tmp_path):
    """The names ``infeed_fetch_ms_per_record``, ``infeed_gate_wait_pct``,
    ``infeed_collate_ms_per_batch`` and ``infeed_device_put_ms_per_batch``
    match, their attrs, and what hangs under what."""
    import asyncio

    from tests.test_infeed_wds import (N, dataset_on_cluster, decode,
                                       infeed_threads)
    from tpudfs.common import telemetry
    from tpudfs.tpu import grain_infeed as gi
    from tpudfs.tpu.wds import DfsWdsSource

    async with dataset_on_cluster(tmp_path) as (c, _client, shards):
        def epoch():
            telemetry.enable()
            try:
                source = DfsWdsSource(list(c.masters), shards)
                try:
                    ds = gi.make_dataset(source, batch_size=8,
                                         shuffle_seed=1, decode=decode)
                    return len(list(gi.device_iterator(ds))), source.stats()
                finally:
                    source.close()
            finally:
                telemetry.disable()

        batches, stats = await asyncio.to_thread(epoch)
    records = telemetry.drain()
    by_name: dict = {}
    for r in records:
        by_name.setdefault(r.name, []).append(r)
    assert {n for n in by_name if n.startswith("infeed.")} == {
        "infeed.index", "infeed.fetch", "infeed.gate_wait",
        "infeed.collate", "infeed.device_put", "infeed.next_wait"}
    (index,) = by_name["infeed.index"]
    assert index.attrs["shards"] == 3 and index.attrs["samples"] == N
    assert index.attrs["range_reads"] >= 3
    fetches = by_name["infeed.fetch"]
    assert len(fetches) == N == stats["records"]
    assert sum(r.attrs["bytes"] for r in fetches) == stats["bytes"]
    assert len({r.request_id for r in fetches}) == N  # a request each
    by_id = {r.span_id: r for r in fetches}
    # The index's metadata fetch waits at the gate too, outside any fetch.
    waits = [r for r in by_name["infeed.gate_wait"] if r.parent_id in by_id]
    assert len(waits) == N
    assert all(by_id[w.parent_id].request_id == w.request_id
               and by_id[w.parent_id].start_ns <= w.start_ns
               and w.end_ns <= by_id[w.parent_id].end_ns for w in waits)
    # A fetch's blockport calls, made on the client's loop, hang under it.
    assert any(r.parent_id in by_id for r in records
               if r.name.startswith("blockport."))
    assert batches == N // 8
    for name in ("infeed.collate", "infeed.device_put"):
        assert len(by_name[name]) == batches, name
        assert all(r.request_id is None for r in by_name[name])
    assert all(r.attrs == {"records": 8, "bytes": 8 * (10_000 + 4 + 4)}
               for r in by_name["infeed.collate"])
    assert len(by_name["infeed.next_wait"]) == batches + 1  # and the end
    assert infeed_threads() == []  # nothing outlives the pipeline


async def test_a_restore_under_another_layout_records_its_spans(tmp_path):
    """The names ``reshard_plan_ms_per_restore``, ``ckpt_read_ms_per_restore``
    and ``reshard_assemble_roofline_pct`` match, their attrs, and what
    hangs under what; the four counters."""
    from tests.test_checkpoint_reshard import _saved, _target
    from tests.test_checkpoint_reshard import CFG
    from tpudfs.common import telemetry

    c, _client, mgr, manifest, _trees = await _saved(tmp_path)
    try:
        telemetry.enable()
        try:
            await mgr.restore(target=_target(CFG))
        finally:
            records = telemetry.drain()
            telemetry.disable()
    finally:
        await c.stop()
    by_name: dict = {}
    for r in records:
        by_name.setdefault(r.name, []).append(r)
    (whole,) = by_name["ckpt.restore"]
    files = len(manifest["shards"])
    children = {"ckpt.latest_step": 1, "ckpt.manifest": 1, "ckpt.plan": 1,
                "ckpt.read_shard": files, "ckpt.confirm": files,
                "ckpt.combined_crc": files, "ckpt.redistribute": 1,
                "ckpt.assemble": 4}
    assert {n for n in by_name if n.startswith("ckpt.")} \
        == {"ckpt.restore", *children}
    for name, count in children.items():
        assert len(by_name[name]) == count, name
        assert all(r.parent_id == whole.span_id for r in by_name[name]), name
    (plan,) = by_name["ckpt.plan"]
    assert plan.attrs["devices"] == 4 and plan.attrs["pieces"] > 0
    assert plan.attrs["reads"] == sum(-(-s["size"] // (64 * 1024))
                                      for s in manifest["shards"])
    assert by_name["ckpt.redistribute"][0].attrs["bytes"] \
        == mgr.stats["reshard_ici_bytes"] > 0
    assert sorted(r.attrs["device"] for r in by_name["ckpt.assemble"]) \
        == sorted(d.id for d in jax.devices()[:4])
    assert all(r.attrs["bytes"] > 0 and r.attrs["tensors"] == 11
               for r in by_name["ckpt.assemble"])
    assert all(r.attrs["devices"] >= 1 for r in by_name["ckpt.read_shard"])
    assert {k for k in mgr.stats if k.startswith("reshard_")} == {
        "reshard_unique_bytes", "reshard_h2d_bytes", "reshard_ici_bytes",
        "reshard_pieces"}
