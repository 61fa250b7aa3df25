"""CRC32C as a TPU Pallas kernel (device twin of native/crc32c.cc).

The reference computes at-rest checksums as one CRC32C per 512-byte chunk
(chunkserver.rs:16,182-190) on the host CPU. On a TPU host the block data is
headed for HBM anyway, so verification can ride the accelerator: CRC is linear
over GF(2), so the CRC of a 512-byte chunk is the XOR of fixed per-bit
contributions:

    crc(chunk) = ~( INV ^ XOR_{w<128, b<32} [bit b of word w] * WCONTRIB[w, b] )

with WCONTRIB precomputed once from the byte-level contribution table
(tpudfs.common.checksum.contrib_table — the same table the numpy twin uses, so
all three implementations are bit-exact). The kernel is gather-free: 32
shift/mask/select passes over (chunks, 128) uint32 words, a pure VPU workload
that vectorizes across every chunk of a block simultaneously — this is the
"CRC32C as a Pallas kernel" north star from BASELINE.json.

Layout: a block of N bytes (zero-padded to 512) becomes a (N/512, 128) uint32
array — 128 little-endian words per 512-byte chunk; lane dimension = 128
matches the TPU tile width exactly.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE, contrib_table
from tpudfs.tpu import on_tpu

WORDS_PER_CHUNK = CHECKSUM_CHUNK_SIZE // 4  # 128 — one TPU lane row per chunk
_CHUNK_TILE = 256  # chunks (= 128 KiB of data) per grid step


@lru_cache(maxsize=1)
def word_contrib_table() -> np.ndarray:
    """(32, 128) uint32: WCONTRIB[b, w] = CRC-register contribution of bit b
    of little-endian word w of a 512-byte chunk (zero init register).
    Bit-major layout so the kernel's unrolled per-bit loop takes a static
    leading-axis slice (lane-aligned; Mosaic can't lower a trailing-axis
    gather here)."""
    rows, _ = contrib_table(CHECKSUM_CHUNK_SIZE)  # (512, 256) byte-level
    out = np.zeros((32, WORDS_PER_CHUNK), dtype=np.uint32)
    for w in range(WORDS_PER_CHUNK):
        for bit in range(32):
            byte_pos = w * 4 + bit // 8
            byte_val = 1 << (bit % 8)
            out[bit, w] = rows[byte_pos, byte_val]
    return out


@lru_cache(maxsize=1)
def inv_contrib() -> int:
    """Contribution of the 0xFFFFFFFF init register across one chunk."""
    _, inv = contrib_table(CHECKSUM_CHUNK_SIZE)
    return inv


def bytes_to_words(data: bytes) -> np.ndarray:
    """Zero-pad to a chunk multiple and view as (chunks, 128) uint32.

    Chunk-aligned input (every full block) is a zero-copy view — the
    1 MiB memcpy per block otherwise taxes the single-core read path.
    """
    n = len(data)
    if n and n % CHECKSUM_CHUNK_SIZE == 0:
        return np.frombuffer(data, dtype="<u4").reshape(-1, WORDS_PER_CHUNK)
    padded_len = -(-max(n, 1) // CHECKSUM_CHUNK_SIZE) * CHECKSUM_CHUNK_SIZE
    buf = np.zeros(padded_len, dtype=np.uint8)
    buf[:n] = np.frombuffer(data, dtype=np.uint8)
    return buf.view("<u4").reshape(-1, WORDS_PER_CHUNK)


def _crc_rows(words: jnp.ndarray, wcontrib: jnp.ndarray) -> jnp.ndarray:
    """(C, 128) words -> (C, 128) per-word XORed contributions (still needs a
    lane reduction + inversion). Shared by the kernel and the jnp fallback."""
    acc = jnp.zeros_like(words)
    for bit in range(32):
        mask = (words >> jnp.uint32(bit)) & jnp.uint32(1)
        acc = acc ^ jnp.where(
            mask.astype(jnp.bool_), wcontrib[bit][None, :], jnp.uint32(0)
        )
    return acc


def _fold_lanes(acc: jnp.ndarray) -> jnp.ndarray:
    """XOR-reduce (C, 128) over lanes via log2 pairwise folds -> (C, 1)."""
    width = acc.shape[1]
    while width > 1:
        half = width // 2
        acc = acc[:, :half] ^ acc[:, half : 2 * half]
        width = half
    return acc


def _crc_kernel(words_ref, wcontrib_ref, out_ref):
    acc = _crc_rows(words_ref[:], wcontrib_ref[:])
    folded = _fold_lanes(acc)
    out_ref[:] = (folded ^ jnp.uint32(inv_contrib())) ^ jnp.uint32(0xFFFFFFFF)


@partial(jax.jit, static_argnames=("interpret",))
def _crc_pallas(words: jnp.ndarray, wcontrib: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    chunks = words.shape[0]
    tile = min(_CHUNK_TILE, chunks)
    grid = pl.cdiv(chunks, tile)
    return pl.pallas_call(
        _crc_kernel,
        out_shape=jax.ShapeDtypeStruct((chunks, 1), jnp.uint32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((tile, WORDS_PER_CHUNK), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((32, WORDS_PER_CHUNK), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(words, wcontrib)


def crc32c_chunks_device(words: jax.Array, *,
                         use_pallas: bool | None = None) -> jax.Array:
    """Per-chunk CRC32C of on-device chunk words ((C, 128) uint32 -> (C,)
    uint32). Jittable; used inside the infeed verify step."""
    wcontrib = jnp.asarray(word_contrib_table())
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        out = _crc_pallas(words, wcontrib, interpret=not on_tpu())
        return out[:, 0]
    acc = _fold_lanes(_crc_rows(words, wcontrib))
    return (acc[:, 0] ^ jnp.uint32(inv_contrib())) ^ jnp.uint32(0xFFFFFFFF)


def crc32c_chunks_jax(data: bytes, **kw) -> np.ndarray:
    """Host convenience: bytes -> per-512B-chunk CRCs via the device path."""
    words = jnp.asarray(bytes_to_words(data))
    return np.asarray(crc32c_chunks_device(words, **kw))


@jax.jit
@jax.named_scope("tpudfs.crc_verify")
def block_crc_device(words: jax.Array) -> jax.Array:
    """Whole-(padded-)block CRC32C, entirely on device — uint32 scalar.

    Per-chunk Pallas CRCs folded with the GF(2) combine table
    (tpudfs.common.checksum.combine_fold_table): CRC concatenation is linear
    over GF(2), so the whole-block CRC is an XOR of per-bit contributions of
    the chunk CRCs. No host readback: folding on device and syncing once
    per *batch* (HbmReader.confirm) keeps per-block verification at one
    host sync per batch. NOTE: computed over the zero-padded chunk stream; equals the
    stored whole-block CRC only when the block length is a chunk multiple.
    """
    from tpudfs.common.checksum import combine_fold_table

    n = words.shape[0]
    if n == 0:
        return jnp.uint32(0)  # crc32c(b"") == 0
    crcs = crc32c_chunks_device(words)
    d = jnp.asarray(combine_fold_table(CHECKSUM_CHUNK_SIZE, n))
    bits = ((crcs[:, None] >> jnp.arange(32, dtype=jnp.uint32)[None, :])
            & jnp.uint32(1)) != 0
    contrib = jnp.where(bits, d, jnp.uint32(0))
    return jax.lax.reduce(contrib, np.uint32(0), jax.lax.bitwise_xor, (0, 1))


@partial(jax.jit, static_argnames=("nblocks",))
@jax.named_scope("tpudfs.crc_verify")
def batch_block_crc_device(words: jax.Array, nblocks: int) -> jax.Array:
    """Whole-block CRC32C of ``nblocks`` equal-chunk-count blocks laid out
    contiguously in ONE (nblocks*cpb, 128) device array -> (nblocks,) uint32.

    The batched twin of :func:`block_crc_device`: one Pallas launch CRCs the
    whole batch's chunk grid, then the GF(2) combine-fold runs per block with
    a shared (cpb, 32) table: a 32-block batch folds in one program
    instead of 32 dispatches (per-dispatch cost not measured on the chip
    yet).
    """
    from tpudfs.common.checksum import combine_fold_table

    total = words.shape[0]
    if total == 0 or nblocks == 0:
        return jnp.zeros((nblocks,), jnp.uint32)
    cpb = total // nblocks
    crcs = crc32c_chunks_device(words).reshape(nblocks, cpb)
    d = jnp.asarray(combine_fold_table(CHECKSUM_CHUNK_SIZE, cpb))  # (cpb, 32)
    bits = ((crcs[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)[None, None, :])
            & jnp.uint32(1)) != 0
    contrib = jnp.where(bits, d[None, :, :], jnp.uint32(0))
    return jax.lax.reduce(contrib, np.uint32(0), jax.lax.bitwise_xor, (1, 2))


def verify_block_device(words: jax.Array, expected: jax.Array) -> jax.Array:
    """Jittable full-block verify: True iff every chunk CRC matches.

    NOTE: callers checksum the PADDED chunk stream (bytes_to_words pads the
    tail chunk with zeros), so ``expected`` must be computed over the same
    padded layout — see HbmReader.
    """
    actual = crc32c_chunks_device(words)
    return jnp.all(actual == expected)
