"""Checkpoint shard → typed tensors, on the device.

What ``HbmReader`` leaves in HBM for one shard file is a list of
``DeviceBlock``s: most ride fused rounds (``DeviceBatch``: up to 16 blocks
of several shards in arrival order), a few stand alone (the short last
block, a block that fell back). Two programs turn that into the shard's
tensors without the bytes leaving the device:

1. ``ckpt_assemble_gather`` copies one round's blocks to their places in a
   row buffer laid out as the file is (one row = 128 words = the 512 bytes
   tensors are aligned to). The buffer is donated, so the copies are in
   place; where each block goes is an operand, so one compiled program
   serves every round of its size whatever order the blocks arrived in.
   Blocks of the round that belong to other shards go to a scratch slot
   behind the payload.
2. ``ckpt_assemble`` (one per shard layout: every tensor's row, element
   count, dtype and shape are static) cuts the buffer into tensors. A
   2-byte dtype takes each word apart, the low half first (little-endian),
   by way of two transposes: a ``bitcast_convert_type`` to shape ``(n, 2)``
   puts a dimension of 2 in the lanes, which the TPU's tiling pads to 128
   (64x the tensor in temporaries; PR 27 met the same with uint8). Every
   move is made on unsigned integers of the tensor's width; the bits get
   their dtype last. Dtypes of 1 and 8 bytes leave as rows of words and
   bounce through the host, counted.

**Bit patterns.** A checkpoint is bytes: what comes back has to be the same
bytes, NaN payloads and denormals included (the benchmark's seeded tensors
are both as often as not). A TPU v5e has no 16-bit float lanes: an XLA
program that touches bfloat16 with its vector unit unpacks to float32 and
packs again, which flushes denormals to zero and quiets NaNs. That holds
for ``bitcast_convert_type`` from uint16 and for every move: reshape,
transpose, slice, ``dynamic_update_slice`` (PR 31's probe on the chip: each
lost exactly the patterns of exponent 0 and 255; a plain copy, a
``device_put``, integers of any width and float32 lost none). So on the TPU
a bf16 tensor gets its dtype in a Mosaic kernel whose body is one
``pltpu.bitcast`` of packed registers (``_relabel_bf16``: a relabelling, no
conversion), run in a two-dimensional view from which the tensor's own
shape is a free bitcast, so that no XLA op follows it; a bf16 tensor with
no such view (a scalar, a vector that is not whole rows of 128 lanes,
``(2, 5, n)``) and float16, which Mosaic has no register type for, bounce
through the host. Off the TPU ``bitcast_convert_type`` is exact and serves
every 2- and 4-byte dtype.

Nothing else is kept: the caller drops blocks and buffer once both are
dispatched, so HBM holds at most the rounds, the buffer and the tensors of
a shard at once, and afterwards the tensors alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudfs.client.client import ChecksumMismatchError
from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE, crc32c
from tpudfs.tpu import on_tpu
from tpudfs.tpu.crc32c_pallas import WORDS_PER_CHUNK
from tpudfs.tpu.device_block import DeviceBlock, device_array_to_bytes

ROW_BYTES = CHECKSUM_CHUNK_SIZE  # one (128,) uint32 row of a block's grid


def _rows(nbytes: int) -> int:
    return -(-nbytes // ROW_BYTES)


#: elements of one block of the relabelling kernel (1 MiB of bfloat16; in
#: and out, double-buffered, stay under the 16 MiB of scoped VMEM)
_RELABEL_BLOCK = 512 * 1024
_RELABEL_LANES = 32768


def _relabel_shape(shape: tuple) -> tuple | None:
    """The two dimensions the relabelling kernel runs in so that nothing
    but a free bitcast follows it: a matrix's own; whole rows of 128 lanes
    for a vector; the leading dimensions merged where the second-minor one
    is whole tiles of 16 rows. None where there is no such view (a scalar,
    a ragged vector, (2, 5, n): XLA relays those out with its vector unit,
    and PR 31's probe read a (2, 5, 7168) tensor back with 380 patterns
    changed)."""
    if len(shape) == 1 and shape[0] % WORDS_PER_CHUNK == 0:
        return (shape[0] // WORDS_PER_CHUNK, WORDS_PER_CHUNK)
    if len(shape) == 2:
        return shape
    if len(shape) > 2 and shape[-2] % 16 == 0:
        return (int(np.prod(shape[:-1], dtype=np.int64)), shape[-1])
    return None


def on_device(dtype: np.dtype, shape: tuple) -> bool:
    """Whether ``ckpt_assemble`` produces this tensor itself, bit for bit:
    the 2- and 4-byte numeric dtypes JAX holds on a TPU, less what a TPU
    cannot make without touching the bits (module docstring)."""
    if dtype.itemsize not in (2, 4):
        return False
    if on_tpu() and dtype.name == "float16":
        return False
    if dtype.name == "bfloat16":
        return not on_tpu() or _relabel_shape(tuple(shape)) is not None
    return dtype.kind in "fiu"


def _relabel_kernel(bits_ref, out_ref):
    out_ref[...] = pltpu.bitcast(bits_ref[...], jnp.bfloat16)


def _relabel_bf16(bits):
    """(m, l) uint16 -> the same bits as (m, l) bfloat16, on the TPU:
    packed registers in, the same registers out."""
    m, lanes = bits.shape
    tl = min(lanes, _RELABEL_LANES)
    tm = max(16, _RELABEL_BLOCK // tl // 16 * 16)
    spec = pl.BlockSpec((m if tm >= m else tm, tl), lambda i, j: (i, j))
    return pl.pallas_call(
        _relabel_kernel, out_shape=jax.ShapeDtypeStruct(bits.shape,
                                                        jnp.bfloat16),
        grid=(pl.cdiv(m, spec.block_shape[0]), pl.cdiv(lanes, tl)),
        in_specs=[spec], out_specs=spec, name="tpudfs.ckpt_relabel")(bits)


#: (dtype name, shape, device kind) -> ``default_order``'s answer
_ORDERS: dict = {}


def default_order(dtype_name: str, shape: tuple, device) -> tuple | None:
    """The dims of ``shape`` major to minor as the chip lays out such an
    array by default, where that is not row-major (None): a v5e puts the
    second-minor dim of ``(2048, 704)`` or ``(8, 2048, 704)`` bf16 minor,
    to pad less. Asked of the compiler, once a shape and kind of chip."""
    key = (dtype_name, tuple(shape), device.device_kind)
    if len(shape) < 2:
        return None
    if key not in _ORDERS:
        out = jax.jit(lambda: jnp.zeros(shape, np.dtype(dtype_name)),
                      out_shardings=jax.sharding.SingleDeviceSharding(device)
                      ).lower().compile().output_formats
        order = tuple(out.layout.major_to_minor)
        _ORDERS[key] = None if order == tuple(range(len(shape))) else order
    return _ORDERS[key]


def _typed(bits, dtype: np.dtype, shape: tuple, order: tuple | None = None):
    """``bits`` (flat, unsigned, the dtype's width) as the tensor. ``order``
    (``default_order``): the layout the tensor will have; the relabelling
    kernel then runs on the bits moved into that order (integers: nothing
    lost), and going back to ``shape`` is a free bitcast, where XLA would
    otherwise move the bf16 result into that layout itself."""
    if order is not None and dtype.name == "bfloat16" and on_tpu():
        physical = tuple(shape[d] for d in order)
        moved = jnp.transpose(bits.reshape(shape), order)
        return jnp.transpose(_typed(moved, dtype, physical),
                             tuple(np.argsort(order)))
    if dtype.name == "bfloat16" and on_tpu():
        # The reshape after the kernel is a bitcast: nothing runs.
        return _relabel_bf16(bits.reshape(_relabel_shape(shape))) \
            .reshape(shape)
    # The barrier keeps the bitcast last: XLA hoists it above the moves
    # otherwise, which then run on floats.
    return lax.bitcast_convert_type(
        lax.optimization_barrier(bits.reshape(shape)), dtype)


@functools.partial(jax.jit, static_argnames=("nblocks",), donate_argnums=0)
def ckpt_assemble_gather(buf, words, dest, *, nblocks: int):
    """``words``: ``nblocks`` blocks of equal rows; block ``i`` lands at
    row ``dest[i]`` of ``buf``."""
    rows = words.shape[0] // nblocks
    with jax.named_scope("tpudfs.ckpt_assemble"):
        for i in range(nblocks):
            block = lax.slice(words, (i * rows, 0),
                              ((i + 1) * rows, WORDS_PER_CHUNK))
            buf = lax.dynamic_update_slice(buf, block, (dest[i], 0))
    return buf


def _halves_in_order(rows):
    """(r, 128) uint32 -> (r, 256) uint16, each word's low half before its
    high half. Transposed, the halves interleave along the major axis,
    which costs no padding."""
    t = rows.T
    lo = (t & 0xFFFF).astype(jnp.uint16)
    hi = (t >> 16).astype(jnp.uint16)
    return jnp.stack([lo, hi], axis=1).reshape(2 * WORDS_PER_CHUNK, -1).T


@functools.lru_cache(maxsize=64)
def _assembler(layout: tuple, tpu: bool):
    """The jitted ``ckpt_assemble`` of one shard layout: a tuple of
    ``(row, elements, dtype name, shape)`` per tensor, in output order.
    Cached at module level: a restore builds a new manager, and a new jit
    object would compile again. Keyed by the backend too: what is
    assembled on the device, and how, differs on a TPU (module docstring)."""
    def ckpt_assemble(buf):
        out = []
        with jax.named_scope("tpudfs.ckpt_assemble"):
            for row, count, name, shape in layout:
                dtype = np.dtype(name)
                if not count:
                    out.append(jnp.zeros(shape, dtype))
                    continue
                rows = lax.slice(
                    buf, (row, 0),
                    (row + _rows(count * dtype.itemsize), WORDS_PER_CHUNK))
                if not on_device(dtype, shape):
                    out.append(rows)  # the host's to finish
                    continue
                if dtype.itemsize == 2:
                    rows = _halves_in_order(rows)
                out.append(_typed(rows.reshape(-1)[:count], dtype, shape))
        return out

    return jax.jit(ckpt_assemble)


def assembler(layout: tuple):
    return _assembler(layout, on_tpu())


def _layout(tensors: list[dict], dtypes: list[np.dtype]) -> tuple:
    return tuple(
        (t["offset"] // ROW_BYTES, int(np.prod(t["shape"], dtype=np.int64)),
         dt.name, tuple(t["shape"]))
        for t, dt in zip(tensors, dtypes))


def _new_buffer(size: int, block_rows: int, device):
    """Rows for every block of a ``size``-byte file plus one scratch slot.
    Whole blocks, so that shards of one block count share one buffer shape
    and with it the gather programs."""
    nblocks = -(-size // (block_rows * ROW_BYTES))
    return jnp.zeros(((nblocks + 1) * block_rows, WORDS_PER_CHUNK),
                     jnp.uint32, device=device), nblocks * block_rows


def assemble_shard(tensors: list[dict], dtypes: list[np.dtype], size: int,
                   blocks: list[DeviceBlock], device,
                   block_rows: int) -> tuple[dict, int, int]:
    """``blocks``: the ``size``-byte shard file's blocks in file order,
    confirmed; every block but the last is ``block_rows`` rows. Returns
    ``({name: jax.Array on device}, bytes assembled on the device, bytes
    bounced through the host)``."""
    if any(b.size != block_rows * ROW_BYTES for b in blocks[:-1]) \
            or sum(b.size for b in blocks) != size \
            or any(b.batch.cpb > block_rows for b in blocks
                   if b.batch is not None):
        raise ValueError(f"blocks of {[b.size for b in blocks][:3]}... do "
                         f"not make a file of {size} bytes in blocks of "
                         f"{block_rows} rows")
    buf, scratch = _new_buffer(size, block_rows, device)
    rounds: dict[int, tuple] = {}
    for j, b in enumerate(blocks):
        if b.batch is None:
            buf = ckpt_assemble_gather(
                buf, jax.device_put(b.array, device),
                np.asarray([j * block_rows], np.int32), nblocks=1)
            continue
        batch, dest = rounds.setdefault(
            id(b.batch),
            (b.batch, np.full(b.batch.nblocks, scratch, np.int32)))
        dest[b.batch_index] = j * block_rows
    for batch, dest in rounds.values():
        buf = ckpt_assemble_gather(buf, jax.device_put(batch.words, device),
                                   dest, nblocks=batch.nblocks)
    parts = assembler(_layout(tensors, dtypes))(buf)
    del buf  # the program holds it until it is done with it
    tree: dict = {}
    on_dev = bounced = 0
    for t, dt, part in zip(tensors, dtypes, parts):
        if on_device(dt, t["shape"]):
            tree[t["name"]] = part
            on_dev += t["size"]
            continue
        raw = device_array_to_bytes(part, t["size"])
        if crc32c(raw) != t["crc32c"]:
            raise ChecksumMismatchError(
                f"tensor {t['name']!r} failed CRC on host bounce")
        tree[t["name"]] = jax.device_put(
            np.frombuffer(raw, dtype=dt).reshape(t["shape"]), device)
        bounced += t["size"]
    return tree, on_dev, bounced


def warm_shard(tensors: list[dict], dtypes: list[np.dtype], size: int,
               device, block_rows: int, max_round: int) -> None:
    """Compile and run, on zeros, every program ``assemble_shard`` can
    dispatch for this shard: the gather at each round size the combiner
    ships (powers of two up to ``max_round``), at the last block's own
    size, and the assembly of this layout. How a restore's blocks fall
    into rounds differs from one restore to the next; what a warm-up
    restore happened to see does not cover the next one."""
    buf, scratch = _new_buffer(size, block_rows, device)
    last_rows = _rows(size) - (scratch - block_rows)  # the short last block
    shapes = [(1, last_rows or block_rows)]
    n = 1
    while n <= max(1, max_round):
        shapes.append((n, block_rows))
        n <<= 1
    for nblocks, rows in shapes:
        buf = ckpt_assemble_gather(
            buf, jnp.zeros((nblocks * rows, WORDS_PER_CHUNK), jnp.uint32,
                           device=device),
            np.full(nblocks, scratch, np.int32), nblocks=nblocks)
    jax.block_until_ready(assembler(_layout(tensors, dtypes))(buf))
