"""The names the trace's readers find the device programs by, pinned where
the programs are made: each lowers on the CPU under the module name the
benchmark's readers match (``crc_verify_roofline_pct``,
``rs_decode_roofline_pct``, ``ici_round_ms``) and carries its ``tpudfs.*``
scope in the lowered text."""

from __future__ import annotations

import jax
import jax.numpy as jnp
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
