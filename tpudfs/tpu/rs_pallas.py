"""Reed-Solomon GF(2^8) encode as a TPU Pallas kernel (device twin of
native/gf256.cc and tpudfs.common.erasure).

The reference encodes RS(k,m) shards on the host CPU with table lookups
(erasure.rs:7-29). Table gathers are hostile to the VPU, but GF(2^8)
multiplication by a CONSTANT is linear over GF(2):

    c * x = XOR_{j<8} [bit j of x] * (c * 2^j)

so each parity byte is an XOR of masked constants — 8 shift/mask/select passes
per (parity, data-shard) pair, fully vectorized across the shard length. For
RS(6,3) that is 6*3*8 = 144 VPU ops per byte lane, no gathers, no MXU needed.
This is the "GF(2^8) RS-encode as a Pallas kernel" item from SURVEY.md §7
step 1.

The c*2^j constants are derived from the same systematic Vandermonde matrix as
the host encoder (so device parities are bit-exact with ``erasure.encode``)
and are baked into the kernel as compile-time scalars — the generator matrix
is static per (k, m), and scalar immediates lower cleanly in Mosaic where
small-table gathers do not. Shards are uint8 with length padded to the
128-lane tile.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudfs.common.erasure import _matrix_invert, encode_matrix, gf_mul
from tpudfs.tpu import on_tpu

_LANE = 128
_TILE = 8 * 1024  # bytes of shard length per grid step


def _matrix_bits(mat_flat: tuple, rows: int, cols: int) -> tuple:
    """Nested tuple [rows][cols][8]: bits[r][c][j] = mat[r, c] * 2^j in
    GF(2^8) — the compile-time constants of one constant-matrix GF matmul."""
    return tuple(
        tuple(
            tuple(gf_mul(int(mat_flat[r * cols + c]), 1 << j) for j in range(8))
            for c in range(cols)
        )
        for r in range(rows)
    )


@lru_cache(maxsize=16)
def coef_bits(k: int, m: int) -> tuple:
    """Constants of the parity rows G[k:] (the encode matmul)."""
    gen = encode_matrix(k, m)[k:]  # parity rows
    return _matrix_bits(tuple(int(x) for x in gen.flatten()), m, k)


def pad_shard_len(n: int) -> int:
    return -(-n // _LANE) * _LANE


_BYTE_LSB = 0x01010101  # bit 0 of each packed byte


def _parity_rows(words: jnp.ndarray, coefs: tuple) -> jnp.ndarray:
    """(k, W) uint32 data shards (4 packed bytes per word) -> (m, W) uint32
    parity; coefs are Python constants baked into the compiled kernel.

    This Mosaic version legalizes only shift/and/or/xor on integer vectors
    (no int8 mul/sub, no i1 relayout), so GF(2^8) runs on uint32-packed
    bytes: extract bit j of every byte ((x >> j) & 0x01010101), expand each
    set bit to a full 0xFF byte with three shift-or doublings (bits never
    cross byte boundaries), AND with the constant replicated into all four
    byte lanes. Byte order inside the word is irrelevant — every byte gets
    identical treatment."""
    k, W = words.shape
    m = len(coefs)
    parities = []
    for p in range(m):
        acc = jnp.zeros((1, W), dtype=jnp.uint32)
        for d in range(k):
            x = words[d : d + 1, :]
            for j in range(8):
                c = coefs[p][d][j]
                if c == 0:
                    continue
                bits = (x >> jnp.uint32(j)) & jnp.uint32(_BYTE_LSB)
                mask = bits | (bits << jnp.uint32(1))
                mask = mask | (mask << jnp.uint32(2))
                mask = mask | (mask << jnp.uint32(4))
                acc = acc ^ (mask & jnp.uint32(c * _BYTE_LSB))
        parities.append(acc)
    return jnp.concatenate(parities, axis=0)


@lru_cache(maxsize=128)
def _gf_pallas_fn(coefs: tuple, interpret: bool):
    """Pallas kernel applying the constant GF(2^8) matrix encoded by
    ``coefs`` ((rows, cols) bit-plane constants) to (cols, W) uint32 words."""
    rows, cols = len(coefs), len(coefs[0])

    def kernel(words_ref, out_ref):
        out_ref[:] = _parity_rows(words_ref[:], coefs)

    @jax.jit
    def run(words: jnp.ndarray) -> jnp.ndarray:
        W = words.shape[1]
        tile = min(_TILE // 4, W)
        grid = pl.cdiv(W, tile)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((rows, W), jnp.uint32),
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((cols, tile), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((rows, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
        )(words)

    return run


def _rs_pallas_fn(k: int, m: int, interpret: bool):
    return _gf_pallas_fn(coef_bits(k, m), interpret)


def _pack_words(data_shards: jax.Array) -> jax.Array:
    k, L = data_shards.shape
    return jax.lax.bitcast_convert_type(
        data_shards.reshape(k, L // 4, 4), jnp.uint32
    )


def _unpack_words(words: jax.Array) -> jax.Array:
    m, W = words.shape
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(m, W * 4)


@jax.named_scope("tpudfs.rs_encode")
def rs_encode_device(data_shards: jax.Array, k: int, m: int, *,
                     use_pallas: bool | None = None) -> jax.Array:
    """Parity shards for on-device data ((k, L) uint8 -> (m, L) uint8).
    Jittable; L must be a multiple of 128 (pad_shard_len)."""
    if use_pallas is None:
        use_pallas = on_tpu()
    words = _pack_words(data_shards)
    if use_pallas:
        out = _rs_pallas_fn(k, m, not on_tpu())(words)
    else:
        out = _parity_rows(words, coef_bits(k, m))
    return _unpack_words(out)


def gf_matmul_device(mat, shards: jax.Array, *,
                     use_pallas: bool | None = None) -> jax.Array:
    """``out[r] = xor_c mat[r, c] * shards[c]`` over GF(2^8), on device.

    ``mat`` ((rows, cols) uint8, a host value) is baked into the compiled
    kernel as bit-plane constants — the device twin of erasure._gf_matmul
    (native/gf256.cc). ``shards`` is (cols, L) uint8 with L a multiple of
    128; jittable in ``shards`` (one compile per distinct matrix)."""
    mat = np.asarray(mat, dtype=np.uint8)
    rows, cols = mat.shape
    coefs = _matrix_bits(tuple(int(x) for x in mat.flatten()), rows, cols)
    if use_pallas is None:
        use_pallas = on_tpu()
    words = _pack_words(shards)
    if use_pallas:
        out = _gf_pallas_fn(coefs, not on_tpu())(words)
    else:
        out = _parity_rows(words, coefs)
    return _unpack_words(out)


def _xtime(x: jnp.ndarray) -> jnp.ndarray:
    """x * 2 over GF(2^8) on uint32-packed bytes, shifts and xors only
    (0x1D = 1 + 4 + 8 + 16; nothing crosses a byte lane)."""
    t = (x & jnp.uint32(0x80808080)) >> jnp.uint32(7)
    red = t ^ (t << jnp.uint32(2)) ^ (t << jnp.uint32(3)) \
        ^ (t << jnp.uint32(4))
    return ((x & jnp.uint32(0x7F7F7F7F)) << jnp.uint32(1)) ^ red


def _xtimes(words: jnp.ndarray) -> list[jnp.ndarray]:
    """[words * 2^j for j in 0..7] over GF(2^8), on uint32-packed bytes."""
    xs = [words]
    for _ in range(7):
        xs.append(_xtime(xs[-1]))
    return xs


def gf_matmul_runtime(mat: jax.Array, words: jnp.ndarray) -> jnp.ndarray:
    """``out[r] = xor_c mat[r, c] * words[c]`` over GF(2^8) with a RUNTIME
    coefficient matrix (a traced jax value), unlike gf_matmul_device whose
    matrix is a compile-time constant. ``mat`` is (r, c) uint8, ``words``
    is (c, W) uint32-packed bytes. The multiply decomposes over the bit
    planes of each coefficient: c*x = XOR_j bit_j(c) * (x * 2^j), with the
    x*2^j ladder shared across rows — 8 xtime steps + r*c*8 selects, all
    vectorized over W. One compiled program serves EVERY coefficient
    matrix, which is what makes per-host decode matrices viable inside one
    SPMD program (each failure pattern would otherwise need its own
    compile)."""
    r, c = mat.shape
    if words.shape[0] != c:
        raise ValueError(f"matrix is {r}x{c} but words has {words.shape[0]} rows")
    ladders = [_xtimes(words[ci]) for ci in range(c)]
    rows = []
    for ri in range(r):
        acc = jnp.zeros(words.shape[1:], jnp.uint32)
        for ci in range(c):
            coef = mat[ri, ci].astype(jnp.uint32)
            for j in range(8):
                bit = ((coef >> jnp.uint32(j)) & jnp.uint32(1)).astype(bool)
                acc = acc ^ jnp.where(bit, ladders[ci][j], jnp.uint32(0))
        rows.append(acc)
    return jnp.stack(rows)


@lru_cache(maxsize=256)
def decode_matrix(k: int, m: int, present: tuple) -> np.ndarray:
    """(k, k) GF(2^8) matrix mapping the first k PRESENT shards (rows
    ``present[:k]`` of the code word, in index order) back to the k data
    shards — the inverse the host reconstruct() builds per erasure pattern
    (erasure.py reconstruct; reference chunkserver.rs:503-640)."""
    rows = list(present)[:k]
    if len(rows) < k:
        raise ValueError(f"need {k} present shards, have {len(rows)}")
    return _matrix_invert(encode_matrix(k, m)[rows])


@jax.named_scope("tpudfs.rs_decode")
def rs_decode_device(avail: jax.Array, k: int, m: int, present: tuple, *,
                     use_pallas: bool | None = None) -> jax.Array:
    """Reconstruct the k data shards ON DEVICE from any k survivors.

    ``avail``: (k, L) uint8 — the shards at code-word indices
    ``present[:k]`` (sorted ascending), L a multiple of 128. Returns the
    (k, L) data shards, bit-exact with the host ``erasure.reconstruct``.
    The per-erasure-pattern inverse is a compile-time constant, so each
    observed failure pattern costs one XLA compile and then runs at encode
    speed — degraded reads never leave the accelerator."""
    return gf_matmul_device(
        decode_matrix(k, m, tuple(present)), avail, use_pallas=use_pallas
    )


# ------------------------------------------------- degraded read into HBM
#
# One program per (k, shard length, block size) serves EVERY failure
# pattern: the inverse matrix is an operand, not a constant, so a deployment
# that cannot know which servers will die compiles nothing when they do.

_SUBLANES = 8  # rows of one uint32 vreg
_DECODE_TILE_ROWS = 512  # rows of 128 words per grid step (k x 256 KiB)


def decode_rows(shard_bytes: int) -> int:
    """Rows of 128 uint32 words one shard of ``shard_bytes`` pads to in the
    decode program's layout (whole (8, 128) vregs)."""
    return -(-shard_bytes // (4 * _LANE * _SUBLANES)) * _SUBLANES


def _gf_rows_kernel(src_ref, masks_ref, words_ref, out_ref):
    """out[i] = xor_c mat[i, c] * words[c] for a RUNTIME matrix, one tile of
    (k, rows, 128) words. ``src_ref[i]`` >= 0 says row i of the matrix is
    the unit vector of that input (a data shard that survived): a copy.
    Any other row is Horner over the coefficient's bit planes,
    ``acc = 2 * acc ^ xor_c (bit_j(mat[i, c]) ? words[c] : 0)`` for j = 7..0,
    so the work is 7 doublings per OUTPUT row and none per input.
    ``masks_ref[(i * k + c) * 8 + j]`` is that bit as 0 / 0xFFFFFFFF."""
    k, rows, _ = words_ref.shape
    for i in range(k):
        src = src_ref[i]

        @pl.when(src >= 0)
        def _():
            out_ref[i] = words_ref[jnp.maximum(src, 0)]

        @pl.when(src < 0)
        def _():
            def vreg(g, carry):
                at = pl.ds(pl.multiple_of(g * _SUBLANES, _SUBLANES),
                           _SUBLANES)
                xs = [words_ref[c, at, :] for c in range(k)]
                acc = jnp.zeros((_SUBLANES, _LANE), jnp.uint32)
                for j in range(7, -1, -1):
                    if j != 7:
                        acc = _xtime(acc)
                    for c in range(k):
                        acc = acc ^ (xs[c] & masks_ref[(i * k + c) * 8 + j])
                out_ref[i, at, :] = acc
                return carry

            jax.lax.fori_loop(0, rows // _SUBLANES, vreg, 0)


def _gf_rows(mat: jax.Array, words: jax.Array, interpret: bool) -> jax.Array:
    """(k, k) uint8 runtime matrix applied to (k, R, 128) uint32 words."""
    k, rows, _ = words.shape
    bits = (mat.astype(jnp.uint32)[:, :, None]
            >> jnp.arange(8, dtype=jnp.uint32)) & jnp.uint32(1)
    masks = (jnp.uint32(0) - bits).reshape(-1)
    nonzero = mat != 0
    unit = (nonzero.sum(axis=1) == 1) & (mat.max(axis=1) == 1)
    src = jnp.where(unit, jnp.argmax(nonzero, axis=1), -1).astype(jnp.int32)
    tile = min(rows, _DECODE_TILE_ROWS)
    spec = pl.BlockSpec((k, tile, _LANE), lambda t, src, masks: (0, t, 0))
    return pl.pallas_call(
        _gf_rows_kernel,
        out_shape=jax.ShapeDtypeStruct(words.shape, jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pl.cdiv(rows, tile),),
            in_specs=[spec], out_specs=spec),
        interpret=interpret,
    )(src, masks, words)


def _shards_to_grid(data: jax.Array, slen: int, size: int) -> jax.Array:
    """(k, R, 128) data-shard words -> the block's (nchunks, 128) chunk
    grid. Shard i starts at byte ``i * slen`` of the block, which is not a
    word boundary when ``slen`` is not a multiple of 4, so each shard is
    funnel-shifted by its own STATIC byte offset and OR-ed into place
    (bytes past ``slen`` in a row are zero, so neighbours never clash)."""
    from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE

    k = data.shape[0]
    nchunks = -(-max(size, 1) // CHECKSUM_CHUNK_SIZE)
    nwords = nchunks * (CHECKSUM_CHUNK_SIZE // 4)
    flat = data.reshape(k, -1)

    def placed(x: jax.Array, at: int) -> jax.Array:
        take = min(x.shape[0], nwords - at)  # at <= nwords: i * slen < size
        return jax.lax.pad(x[:take], jnp.uint32(0),
                           [(at, nwords - at - take, 0)])

    out = jnp.zeros((nwords,), jnp.uint32)
    for i in range(k):
        at, shift = divmod(i * slen, 4)
        if shift == 0:
            out = out | placed(flat[i], at)
        else:
            out = out | placed(flat[i] << jnp.uint32(8 * shift), at) \
                | placed(flat[i] >> jnp.uint32(32 - 8 * shift), at + 1)
    return out.reshape(nchunks, -1)


@partial(jax.jit, static_argnames=("slen", "size", "interpret"))
def rs_decode_block(words: jax.Array, mat: jax.Array, *, slen: int,
                    size: int, interpret: bool | None = None) -> jax.Array:
    """The degraded read's ONE device program: k surviving shards in, the
    block's chunk-padded word grid out (what ``block_crc_device`` takes).

    ``words``: (k, decode_rows(slen), 128) uint32, the shards at code-word
    indices ``present[:k]`` as the host views them (zero past ``slen``).
    ``mat``: ``decode_matrix(k, m, present)``, (k, k) uint8, an OPERAND:
    every failure pattern of one (k, shard length, block size) runs this
    one compiled program. Missing data shards are computed by the Pallas
    kernel (interpreted off the TPU unless ``interpret`` says otherwise);
    bit-exact with ``erasure.reconstruct``."""
    if interpret is None:
        interpret = not on_tpu()
    with jax.named_scope("tpudfs.rs_decode"):
        return _shards_to_grid(_gf_rows(mat, words, interpret), slen, size)


def survivors_to_words(shards: list, slen: int) -> np.ndarray:
    """Host side of :func:`rs_decode_block`: the k shard buffers of
    ``slen`` bytes stacked zero-padded and VIEWED as their uint32 words."""
    rows = decode_rows(slen)
    stack = np.zeros((len(shards), rows * _LANE * 4), dtype=np.uint8)
    for r, shard in enumerate(shards):
        stack[r, :slen] = np.frombuffer(shard, dtype=np.uint8)
    return stack.view("<u4").reshape(len(shards), rows, _LANE)


def rs_encode_jax(data: bytes, k: int, m: int, **kw) -> list[bytes]:
    """Host convenience mirroring erasure.encode: returns k+m shard byte
    strings (shard length = ceil(len/k), zero padded; parity computed over
    128-aligned device layout then truncated — parity is bytewise independent
    so the truncation is exact)."""
    shard = -(-len(data) // k)
    padded = pad_shard_len(shard)
    buf = np.zeros((k, padded), dtype=np.uint8)
    flat = np.frombuffer(data, dtype=np.uint8)
    for i in range(k):
        piece = flat[i * shard : (i + 1) * shard]
        buf[i, : len(piece)] = piece
    parity = np.asarray(rs_encode_device(jnp.asarray(buf), k, m, **kw))
    return [buf[i, :shard].tobytes() for i in range(k)] + [
        parity[i, :shard].tobytes() for i in range(m)
    ]
