"""Compile the main path's device programs for a TPU v5e that is described,
not attached — the only file in the tree that describes the chip.

A CPU test run cannot execute a Mosaic kernel, but the TPU compiler is
installed and compiles for a topology description: what it refuses here
(an unaligned slice, too much VMEM, a program that does not fit HBM) it
would refuse on the chip. Shapes are ``chip_smoke.py``'s real ones. Nothing
runs, so this says nothing about results or times — ``chip_smoke.py`` on the
chip does.

The kernels pick interpret mode from ``on_tpu()``, which asks
``jax.devices()`` and sees the CPU here; the ``chip`` fixture steers all
three imported names from the test instead of adding an option to the
program. Everything that touches the topology lives in fixtures of THIS
file: only the xdist worker that is handed the file loads libtpu.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

MIB = 1 << 20
CHUNKS_1MIB = MIB // 512
#: Padded shard length of RS(6,3) over one 64 MiB block (the client's
#: default block): ceil(64 MiB / 6) rounded up to the 128-byte lane.
RS_SHARD = -(-(-(-(64 * MIB) // 6)) // 128) * 128
#: One v5e chip's HBM as the runtime reports it (`bytes_limit`, PR 22).
HBM_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(monkeypatch):
    """Steer the kernels' single decision point to "on the chip" and keep
    the traces made under it out of every other test: jitted functions
    cache their trace per shape, interpret flag included."""
    import tpudfs.tpu
    from tpudfs.tpu import ckpt_assemble, crc32c_pallas, rs_pallas

    jax.clear_caches()
    for mod in (tpudfs.tpu, ckpt_assemble, crc32c_pallas, rs_pallas):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _words(chunks: int, sharding):
    return jax.ShapeDtypeStruct((chunks, 128), jnp.uint32, sharding=sharding)


def _ring(topo, n: int):
    mesh = Mesh(np.array(topo.devices[:n]), ("hosts",))
    return mesh, NamedSharding(mesh, P("hosts"))


# Each case: (topo, one_chip) -> (jitted, args, expect_kernel, needles).


def _crc_pallas_case(chunks: int):
    def build(topo, one_chip):
        from tpudfs.tpu.crc32c_pallas import _crc_pallas

        wcontrib = jax.ShapeDtypeStruct((32, 128), jnp.uint32,
                                        sharding=one_chip)
        return _crc_pallas, (_words(chunks, one_chip), wcontrib), True, ()

    return build


def _block_crc(topo, one_chip):
    from tpudfs.tpu.crc32c_pallas import block_crc_device

    return block_crc_device, (_words(CHUNKS_1MIB, one_chip),), True, ()


def _batch_block_crc(topo, one_chip):
    from tpudfs.tpu.crc32c_pallas import batch_block_crc_device

    return (jax.jit(lambda w: batch_block_crc_device(w, 32)),
            (_words(32 * CHUNKS_1MIB, one_chip),), True, ())


def _verify_block(topo, one_chip):
    from tpudfs.tpu.crc32c_pallas import verify_block_device

    expected = jax.ShapeDtypeStruct((CHUNKS_1MIB,), jnp.uint32,
                                    sharding=one_chip)
    return (jax.jit(verify_block_device),
            (_words(CHUNKS_1MIB, one_chip), expected), True, ())


def _rs_encode(topo, one_chip):
    from tpudfs.tpu.rs_pallas import rs_encode_device

    shards = jax.ShapeDtypeStruct((6, RS_SHARD), jnp.uint8,
                                  sharding=one_chip)
    return (jax.jit(lambda d: rs_encode_device(d, 6, 3)), (shards,),
            True, ())


def _rs_decode(topo, one_chip):
    from tpudfs.tpu.rs_pallas import rs_decode_device

    shards = jax.ShapeDtypeStruct((6, RS_SHARD), jnp.uint8,
                                  sharding=one_chip)
    present = (0, 1, 2, 3, 5, 7, 8)  # data 4 and parity 6 missing
    return (jax.jit(lambda a: rs_decode_device(a, 6, 3, present)),
            (shards,), True, ())


def _rs_decode_block_case(block_bytes: int):
    """The degraded read's one program per (k, shard length, block size),
    matrix as an operand: RS(6,3) at the benchmark's 1 MiB block and at the
    client's default 64 MiB block."""
    def build(topo, one_chip):
        from tpudfs.tpu.rs_pallas import decode_rows, rs_decode_block

        slen = -(-block_bytes // 6)
        words = jax.ShapeDtypeStruct((6, decode_rows(slen), 128), jnp.uint32,
                                     sharding=one_chip)
        mat = jax.ShapeDtypeStruct((6, 6), jnp.uint8, sharding=one_chip)
        return (jax.jit(lambda w, a: rs_decode_block(
            w, a, slen=slen, size=block_bytes)), (words, mat), True, ())

    return build


def _ec_round_unstack(topo, one_chip):
    """A fused round of 16 degraded RS(6,3) blocks of 1 MiB: its stack of
    survivors as the decode program's 16 operands."""
    from tpudfs.tpu.read_combiner import _unstack
    from tpudfs.tpu.rs_pallas import decode_rows

    stack = jax.ShapeDtypeStruct(
        (16, 6, decode_rows(-(-MIB // 6)), 128), jnp.uint32,
        sharding=one_chip)
    return _unstack, (stack,), False, ()


def _ec_round_restack(topo, one_chip):
    """... and its 16 decoded chunk grids as the one array the batched CRC
    (``batch_block_crc_device_32x1MiB`` above) and a DeviceBatch take."""
    from tpudfs.tpu.read_combiner import _restack

    return _restack, (_words(CHUNKS_1MIB, one_chip),) * 16, False, ()


def _gf_matmul_runtime(topo, one_chip):
    from tpudfs.tpu.rs_pallas import gf_matmul_runtime

    mat = jax.ShapeDtypeStruct((2, 4), jnp.uint8, sharding=one_chip)
    words = jax.ShapeDtypeStruct((4, 4 * MIB // 4), jnp.uint32,
                                 sharding=one_chip)
    # A runtime matrix means no baked constants: plain XLA by design.
    return jax.jit(gf_matmul_runtime), (mat, words), False, ()


def _write_step_case(n: int, needles: tuple):
    def build(topo, one_chip):
        from tpudfs.tpu.ici_replication import replicated_write_step

        mesh, sharding = _ring(topo, n)
        chunks = n * 8 * CHUNKS_1MIB  # 8 MiB per ring position
        crcs = jax.ShapeDtypeStruct((chunks,), jnp.uint32,
                                    sharding=sharding)
        step = replicated_write_step(mesh, replication=3)
        return jax.jit(step), (_words(chunks, sharding), crcs), True, needles

    return build


def _ec_scatter(topo, one_chip):
    from tpudfs.tpu.ici_replication import EcShardScatter

    mesh, sharding = _ring(topo, 4)
    scatter = EcShardScatter(mesh, 2, 2)
    return (scatter._fn, (_words(4 * 8 * CHUNKS_1MIB, sharding),), True,
            ("collective-permute", "all-reduce"))


def _ec_gather(topo, one_chip):
    from tpudfs.tpu.ici_replication import EcShardGather

    mesh, sharding = _ring(topo, 4)
    gather = EcShardGather(mesh, 2, 2)
    shards = jax.ShapeDtypeStruct((4 * 4, 4 * CHUNKS_1MIB, 128), jnp.uint32,
                                  sharding=sharding)
    mats = jax.ShapeDtypeStruct((4, 2, 4), jnp.uint8, sharding=sharding)
    return gather._fn, (shards, mats), False, ("collective-permute",)


CASES = {
    "crc_pallas_2048_chunks": _crc_pallas_case(2048),
    "crc_pallas_65536_chunks": _crc_pallas_case(65536),
    "block_crc_device_1MiB": _block_crc,
    "batch_block_crc_device_32x1MiB": _batch_block_crc,
    "verify_block_device_1MiB": _verify_block,
    "rs_encode_device_6_3_64MiB_block": _rs_encode,
    "rs_decode_device_6_3_64MiB_block": _rs_decode,
    "rs_decode_block_6_3_1MiB_block": _rs_decode_block_case(MIB),
    "rs_decode_block_6_3_64MiB_block": _rs_decode_block_case(64 * MIB),
    "ec_round_unstack_16x6_shards_of_1MiB_blocks": _ec_round_unstack,
    "ec_round_restack_16x1MiB": _ec_round_restack,
    "gf_matmul_runtime": _gf_matmul_runtime,
    "replicated_write_step_1_device": _write_step_case(
        1, ("collective-permute",)),
    "replicated_write_step_4_devices": _write_step_case(
        4, ("collective-permute", "all-reduce")),
    "ec_shard_scatter_2_2_4_devices": _ec_scatter,
    "ec_shard_gather_2_2_4_devices": _ec_gather,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, topo, one_chip, chip):
    jitted, args, expect_kernel, needles = CASES[case](topo, one_chip)
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) is expect_kernel, (
        f"{case}: expected a Mosaic kernel: {expect_kernel}")
    for needle in needles:
        assert needle in text, f"{case}: no {needle} in the compiled program"
    # The RS programs' uint8 pack is ~100x padded in XLA temp (8.5 GiB for
    # a 64 MiB block). It runs on the chip, so PR 22 left it; this only
    # holds each program inside one chip's HBM.
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < HBM_BYTES, f"{case}: does not fit HBM"


# --------------------------------------------- checkpoint assembly (PR 31)


def _ckpt_shard(shard: int):
    """One shard of ``ckpt-3m5cs-r3`` as the manifest would spell it."""
    import json
    from pathlib import Path

    from benchmarks import reference_ckpt

    cfg = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                      / "configs" / "ckpt-3m5cs-r3.json").read_text())
    table = reference_ckpt.table(cfg)
    placed, size = reference_ckpt.layout(cfg, shard)
    tensors = [{"name": n, "offset": off, "size": nbytes,
                "dtype": table[n][0], "shape": list(table[n][1])}
               for n, off, nbytes in placed]
    return tensors, size, cfg["block_bytes"]


@pytest.mark.parametrize("shard", [0, 3])
def test_checkpoint_assembly_compiles_for_v5e_within_a_shard_of_temp(
        shard, topo, one_chip, chip):
    """336 blocks of 1 MiB into a shard's 31-38 bf16 and f32 tensors: one
    program, the bf16 tensors relabelled by the Mosaic kernel (XLA's own
    bitcast packs floats on a v5e and loses NaN payloads and denormals),
    and less HBM in temporaries than the shard itself (a bitcast to
    ``(n, 2)`` bf16 took 64x the tensor). The gather of a 16-block round
    updates the donated buffer in place."""
    from tpudfs.tpu import ckpt_assemble

    tensors, size, block_bytes = _ckpt_shard(shard)
    dtypes = [jnp.dtype(t["dtype"]) for t in tensors]
    assert {d.name for d in dtypes} == {"bfloat16", "float32"} \
        | ({"int32"} if shard == 3 else set())
    rows = block_bytes // 512
    nblocks = -(-size // block_bytes)
    assert nblocks == 336
    buf = _words((nblocks + 1) * rows, one_chip)
    program = ckpt_assemble.assembler(ckpt_assemble._layout(tensors, dtypes))
    compiled = program.lower(buf).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= sum(
        d.name == "bfloat16" for d in dtypes)
    # Nothing but the kernel makes bf16, and nothing but a free bitcast
    # follows it: no fusion, copy, reshape or slice touches the bits.
    made = [line for line in text.splitlines()[1:] if " = bf16[" in line]
    assert made and all(
        any(op in line for op in ("custom-call(", "get-tuple-element(",
                                  " bitcast("))
        for line in made), [m[:120] for m in made][:5]
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= sum(t["size"] for t in tensors)
    assert mem.temp_size_in_bytes < size, mem.temp_size_in_bytes
    dest = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    gather = ckpt_assemble.ckpt_assemble_gather.lower(
        buf, _words(16 * rows, one_chip), dest, nblocks=16).compile()
    mem = gather.memory_analysis()
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes > size
    assert mem.temp_size_in_bytes < block_bytes, mem.temp_size_in_bytes


def test_bf16_views_of_vectors_and_stacked_tensors_are_free_bitcasts(
        topo, one_chip, chip):
    """What follows the relabelling kernel for a vector of whole rows and
    for tensors of three and four dimensions whose second-minor one is
    whole tiles is a bitcast: no copy, reshape or reduce runs on bf16."""
    from tpudfs.tpu import ckpt_assemble

    shapes = [(2048,), (512,), (8, 64, 512), (2, 3, 32, 256), (300, 100)]
    layout, row = [], 0
    for shape in shapes:
        assert ckpt_assemble.on_device(np.dtype(jnp.bfloat16), shape)
        count = int(np.prod(shape))
        layout.append((row, count, "bfloat16", shape))
        row += -(-count * 2 // 512)
    text = ckpt_assemble.assembler(tuple(layout)).lower(
        _words(row + 8, one_chip)).compile().as_text()
    made = [line for line in text.splitlines()[1:] if " = bf16[" in line]
    assert len([m for m in made if "custom-call(" in m]) == len(shapes)
    assert all(any(op in line for op in ("custom-call(", " bitcast(",
                                         "get-tuple-element("))
               for line in made), [m[:140] for m in made]


# ----------------------------- restore under another layout (ckpt_reshard)


def _reshard_plan(topo):
    """``ckpt-reshard-3m5cs-r3``'s manifest as the ranks save it, planned
    onto the described 2x2 as the cell's target (``reference_reshard``)."""
    import json
    from pathlib import Path

    from benchmarks import reference_reshard as ref
    from tpudfs.tpu import ckpt_reshard
    from tpudfs.tpu.checkpoint import _dtype_of

    cfg = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                      / "configs" / "ckpt-reshard-3m5cs-r3.json").read_text())
    table = ref.table(cfg)
    shards = []
    for shard, (rank, _names) in enumerate(ref.shards(cfg)):
        placed, size = ref.layout(cfg, shard)
        tensors = []
        for name, offset, nbytes in placed:
            start, shape = ref.rank_pieces(cfg, rank)[name]
            tensors.append({"name": name, "dtype": table[name][0],
                            "shape": list(shape), "offset": offset,
                            "size": nbytes, "global_shape":
                            list(table[name][1]), "start": list(start)})
        shards.append({"shard": shard, "size": size, "tensors": tensors})
    axes = cfg["target"]["mesh"]
    mesh = Mesh(np.array(topo.devices).reshape(tuple(axes.values())),
                tuple(axes))
    target = ckpt_reshard.Target(
        mesh, {n: P(*ref.spec_of(cfg, n)) for n in table
               if ref.spec_of(cfg, n)},
        {n: e[2] for n, e in table.items() if ref.host_shape(e) != e[1]})
    return ckpt_reshard.plan({"shards": shards}, target, _dtype_of,
                             cfg["block_bytes"])


def test_reshard_programs_compile_for_a_2x2_at_the_cells_widths(
        topo, chip, monkeypatch):
    """The cell's chip-to-chip move (one program over the four chips: a
    switch of static slices and three collective permutes) and chip 0's
    assembly of its 57 shards: bf16 made by the relabelling kernel alone,
    everything inside one chip's HBM."""
    from tpudfs.tpu import ckpt_reshard

    monkeypatch.setattr(ckpt_reshard, "on_tpu", lambda: True)
    plan = _reshard_plan(topo)
    assert plan.unique_bytes == 2_374_564_868 and len(plan.outputs) == 57
    mesh, program = ckpt_reshard._ici_program(
        tuple(plan.devices), plan.block_rows, plan.sends)
    stage = jax.ShapeDtypeStruct((4 * plan.stage_rows, 128), jnp.uint32,
                                 sharding=NamedSharding(mesh, P("ckpt")))
    compiled = program.lower(stage).compile()
    assert compiled.as_text().count("collective-permute") >= len(plan.sends)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < HBM_BYTES
    one = SingleDeviceSharding(plan.devices[0])
    inbox = sum(w for _s, _l, w in plan.sends) * plan.block_rows
    compiled = ckpt_reshard._program(plan, 0).lower(
        _words(plan.stage_rows, one), _words(inbox, one)).compile()
    text = compiled.as_text()
    bf16 = sum(o[1].name == "bfloat16" for o in plan.outputs)
    assert text.count("tpu_custom_call") >= bf16
    made = [line for line in text.splitlines()[1:] if " = bf16[" in line]
    assert made and all(
        any(op in line for op in ("custom-call(", "get-tuple-element(",
                                  " bitcast("))
        for line in made), [m[:120] for m in made][:5]
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= plan.resident
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < HBM_BYTES
