"""Restore under another layout: saved pieces -> a mesh's device shards.

A job that saved as one layout (each rank wrote pieces of global tensors,
``TensorSpec.global_shape`` / ``start``) resumes as another: a
``jax.sharding.Mesh``, a ``PartitionSpec`` per global tensor and, for a
tensor that extends beyond this host, the index range the host holds
(:class:`Target`). :func:`plan` works out, from the manifest alone:

- which blocks of which shard files some chip needs (a block holds a byte
  of some chip's box of some tensor), and on which chip each lands: a
  block one chip needs lands there; a block several chips need lands on
  the one of them with the fewest bytes so far. So each needed block is
  read from the cluster once and crosses the host-to-device bus once,
  through the read combiner of the chip it lands on;
- which blocks move chip to chip afterwards: every block a chip needs and
  does not hold. ONE program over all chips (``ckpt_reshard_ici``, a
  ``shard_map`` of ``ppermute`` steps: at step ``s`` chip ``i`` sends to
  chip ``i + s``) moves them, over ICI on a TPU host;
- what each chip cuts out of what it holds: ONE program a chip
  (``ckpt_reshard_assemble``) builds each of its shards from contiguous
  pieces (a row split) and strided ones (a column split: every row of the
  saved piece divides between chips).

Blocks first land in a staging buffer a chip, one slot a block it holds
(``ckpt_assemble_gather``, one call a round, as a plain restore does).
Every move is made on unsigned integers and the dtype comes last, as in
:mod:`tpudfs.tpu.ckpt_assemble` (bf16 through its relabelling kernel on a
TPU); a shard the chip cannot type bit for bit goes through the host,
counted. 1- and 8-byte dtypes are not planned (:class:`ReshardError`).

What a chip needs of a block it does not hold moves as the whole block:
a column split moves whole rows, so twice its chip's share of them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudfs.tpu import ckpt_assemble, on_tpu
from tpudfs.tpu.ckpt_assemble import ROW_BYTES, ckpt_assemble_gather
from tpudfs.tpu.crc32c_pallas import WORDS_PER_CHUNK

#: sub-boxes one saved piece may be cut into for one chip: a part is one
#: contiguous stretch of the file, so a stacked piece whose inner dims are
#: split is cut expert by expert instead of read across the other halves
MAX_PARTS = 64
_AXIS = "ckpt"


class ReshardError(ValueError):
    """The target cannot be planned from this manifest."""


@dataclasses.dataclass
class Target:
    """Where a restore puts each global tensor: ``mesh``'s devices (this
    host's), ``specs[name]`` (absent: replicated) and ``host_index[name]``,
    ``((start, stop), ...)`` a dimension, the part of the global tensor
    this host holds (absent: all of it). A restored array has the shape of
    that part and is sharded ``NamedSharding(mesh, specs[name])``."""

    mesh: Mesh
    specs: Mapping[str, P] = dataclasses.field(default_factory=dict)
    host_index: Mapping[str, tuple] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class SavedPiece:
    """One saved piece: ``shape`` elements of global tensor ``name`` from
    ``start``, at ``offset`` of shard file ``shard`` (a scalar is planned
    as a piece of shape ``(1,)``)."""

    name: str
    dtype: np.dtype
    shard: int
    offset: int
    shape: tuple
    start: tuple
    global_shape: tuple
    rank0: bool = False


def pieces_of(manifest: dict, dtype_of) -> dict[str, list[SavedPiece]]:
    """``{global name: [SavedPiece]}``; a tensor saved whole is the piece at 0
    with the full shape (every manifest of the format, old ones too)."""
    out: dict[str, list[SavedPiece]] = {}
    for spec in manifest["shards"]:
        for t in spec["tensors"]:
            shape = tuple(t["shape"]) or (1,)
            out.setdefault(t["name"], []).append(SavedPiece(
                t["name"], dtype_of(t["dtype"]), spec["shard"], t["offset"],
                shape, tuple(t.get("start") or (0,) * len(shape)),
                tuple(t.get("global_shape") or shape), not t["shape"]))
    for name, pieces in out.items():
        if len({(p.dtype, p.global_shape) for p in pieces}) != 1:
            raise ReshardError(f"{name}: pieces disagree on dtype or shape")
    return out


# ------------------------------------------------------------------- plan


@dataclasses.dataclass
class Plan:
    devices: list
    block_rows: int
    #: per shard file: the device index each block lands on (None: skipped)
    placement: dict[int, list]
    #: per device: {(shard, block): staging slot}
    stage_slots: list[dict]
    #: rows of every chip's staging buffer (the slots + one scratch slot)
    stage_rows: int
    #: per shift: (s, per source device the slots it sends, slots a source)
    sends: tuple
    #: per device: its assembly program's layout (static, hashable)
    layouts: list[tuple]
    #: (name, dtype, host shape, sharding, shard shape) per output
    outputs: list[tuple]
    unique_bytes: int
    ici_bytes: int
    pieces: int
    #: bytes of the shards each chip holds (one of every output)
    resident: int

    @property
    def uploads(self) -> int:
        return sum(d is not None for p in self.placement.values() for d in p)


def _parts(piece: SavedPiece, lo: tuple, hi: tuple) -> list[tuple]:
    """The global box ``[lo, hi)`` of ``piece`` as parts, each ``(lo,
    hi)`` in piece coordinates with its leading dims of extent 1, so that
    a part is one stretch of the file once whole inner rows are taken."""
    plo = tuple(max(a, s) - s for a, s in zip(lo, piece.start))
    phi = tuple(min(b, s + n) - s
                for b, s, n in zip(hi, piece.start, piece.shape))
    if any(a >= b for a, b in zip(plo, phi)):
        return []
    t, count = 0, 1
    while t < len(plo) - 1 and count * (phi[t] - plo[t]) <= MAX_PARTS \
            and not all(plo[d] == 0 and phi[d] == piece.shape[d]
                        for d in range(t + 1, len(plo))):
        count *= phi[t] - plo[t]
        t += 1
    out = []
    for head in np.ndindex(*(phi[d] - plo[d] for d in range(t))):
        fixed = tuple(h + plo[d] for d, h in enumerate(head))
        out.append((fixed + plo[t:], tuple(f + 1 for f in fixed) + phi[t:]))
    return out


def _blocks_of(piece: SavedPiece, plo: tuple, phi: tuple,
               bb: int) -> list[int]:
    """Blocks of the file holding a byte of the box ``[plo, phi)`` of
    ``piece``: one contiguous run of bytes per index of the dims before
    the last one not covered whole."""
    shape, isz = piece.shape, piece.dtype.itemsize
    k = len(shape) - 1
    while k > 0 and plo[k] == 0 and phi[k] == shape[k]:
        k -= 1
    inner = math.prod(shape[k + 1:])
    first = np.zeros(1, np.int64)
    for d in range(k):
        first = (first[:, None] + np.arange(plo[d], phi[d], dtype=np.int64)
                 * math.prod(shape[d + 1:])).reshape(-1)
    first = piece.offset + (first + plo[k] * inner) * isz
    last = first + (phi[k] - plo[k]) * inner * isz - 1
    a, b = first // bb, last // bb
    if int((b - a).max()) <= 1:
        return np.unique(np.concatenate([a, b])).tolist()
    return sorted({j for x, y in zip(a.tolist(), b.tolist())
                   for j in range(x, y + 1)})


def _split_of(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    return math.prod(mesh.shape[a]
                     for a in ((axes,) if isinstance(axes, str) else axes))


def plan(manifest: dict, target: Target, dtype_of, block_bytes: int) -> Plan:
    """Reads, chip-to-chip moves and assemblies of a restore of
    ``manifest`` as ``target`` (module docstring)."""
    if block_bytes % ROW_BYTES:
        raise ReshardError(f"block size {block_bytes} is not whole rows")
    mesh = target.mesh
    devices = list(mesh.devices.flat)
    n = len(devices)
    index_of = {d: i for i, d in enumerate(devices)}
    br = block_bytes // ROW_BYTES
    sizes = {s["shard"]: s["size"] for s in manifest["shards"]}
    #: per device: (output, piece, lo, hi, the shard's global lo)
    needs: list[list] = [[] for _ in range(n)]
    needed: list[set] = [set() for _ in range(n)]  # (shard, block)
    outputs: list[tuple] = []
    for name, pieces in sorted(pieces_of(manifest, dtype_of).items()):
        first = pieces[0]
        if first.dtype.itemsize not in (2, 4):
            raise ReshardError(f"{name}: {first.dtype} is not planned (2- "
                               "and 4-byte dtypes only)")
        gshape = first.global_shape
        rng = target.host_index.get(name) or tuple((0, g) for g in gshape)
        lo = tuple(a for a, _b in rng)
        host_shape = () if first.rank0 else tuple(b - a for a, b in rng)
        spec = target.specs.get(name, P())
        for dim, axes in zip(host_shape, spec):
            if dim % _split_of(mesh, axes):
                raise ReshardError(
                    f"{name}: {host_shape} does not divide by {spec}")
        sharding = NamedSharding(mesh, spec)
        shard_shape = sharding.shard_shape(host_shape)
        out = len(outputs)
        outputs.append((name, first.dtype, host_shape, sharding, shard_shape))
        indices = sharding.devices_indices_map(host_shape or (1,))
        for device, index in indices.items():
            i = index_of[device]
            blo = tuple(a + (s.start or 0) for a, s in zip(lo, index))
            bhi = tuple(a + b for a, b in zip(blo, shard_shape or (1,)))
            covered = 0
            for p in pieces:
                for plo, phi in _parts(p, blo, bhi):
                    needs[i].append((out, p, plo, phi, blo))
                    covered += math.prod(b - a for a, b in zip(plo, phi))
                    needed[i].update((p.shard, j) for j in
                                     _blocks_of(p, plo, phi, block_bytes))
            if covered != math.prod(shard_shape or (1,)):
                raise ReshardError(f"{name}: the saved pieces cover {covered}"
                                   f" of {math.prod(shard_shape)} elements "
                                   f"of a shard")
    # ---- where each needed block lands
    needers: dict = {}
    for i in range(n):
        for key in needed[i]:
            needers.setdefault(key, []).append(i)
    load = [0] * n
    home: dict = {}
    for key in sorted(needers, key=lambda k: (len(needers[k]), k)):
        i = min(needers[key], key=lambda d: (load[d], d))
        home[key] = i
        load[i] += _block_size(key, sizes, block_bytes)
    placement = {s: [None] * -(-size // block_bytes)
                 for s, size in sizes.items()}
    stage_slots: list[dict] = [dict() for _ in range(n)]
    for key in sorted(home):
        placement[key[0]][key[1]] = home[key]
        stage_slots[home[key]][key] = len(stage_slots[home[key]])
    stage_rows = (max(len(s) for s in stage_slots) + 1) * br
    # ---- what moves chip to chip: at shift s, source i -> i + s
    sends = []
    inbox: list[dict] = [dict() for _ in range(n)]  # key -> inbox row
    base = 0
    for s in range(1, n):
        lists = [sorted(k for k in needed[(i + s) % n] if home[k] == i)
                 for i in range(n)]
        width = max(len(keys) for keys in lists)
        if not width:
            continue
        for i, keys in enumerate(lists):
            for k, key in enumerate(keys):
                inbox[(i + s) % n][key] = base + k * br
        base += width * br
        sends.append((s, tuple(tuple(stage_slots[i][k] for k in keys)
                               for i, keys in enumerate(lists)), width))
    layouts = []
    for i in range(n):
        def where(key, i=i):
            if key in stage_slots[i]:
                return 0, stage_slots[i][key] * br
            return (1, inbox[i][key]) if key in inbox[i] else None
        layouts.append(_layout(needs[i], outputs, where, block_bytes))
    return Plan(devices=devices, block_rows=br, placement=placement,
                stage_slots=stage_slots, stage_rows=stage_rows,
                sends=tuple(sends), layouts=layouts, outputs=outputs,
                unique_bytes=sum(_block_size(k, sizes, block_bytes)
                                 for k in home),
                ici_bytes=sum(n * w * br * ROW_BYTES for _s, _l, w in sends),
                pieces=sum(len(x) for x in needs),
                resident=sum(math.prod(o[4]) * o[1].itemsize
                             for o in outputs))


def _block_size(key: tuple, sizes: dict, bb: int) -> int:
    shard, j = key
    return min(bb, sizes[shard] - j * bb)


def _layout(needs: list, outputs: list, where, bb: int) -> tuple:
    """One chip's assembly: per output ``(dtype name, shard shape, final
    shape, parts)``; a part is ``(segments, first, count, slab, select,
    dest)``: the rows of ``segments`` (``(buffer, row, rows)``: buffer 0
    the staging, 1 the inbox, None zeros, for a block the chip does not
    hold, whose bytes the part does not select) hold, from element
    ``first`` on, the ``count`` elements of a ``slab`` of whole inner rows;
    ``select`` (start, stop a dim) of it lands at ``dest`` of the shard."""
    parts: dict[int, list] = {}
    for out, p, lo, hi, blo in needs:
        isz = p.dtype.itemsize
        f = next((d for d in range(len(lo)) if hi[d] - lo[d] != 1),
                 len(lo) - 1)
        inner = math.prod(p.shape[f + 1:])
        start = sum(lo[d] * math.prod(p.shape[d + 1:]) for d in range(f)) \
            + lo[f] * inner
        count = (hi[f] - lo[f]) * inner
        a = p.offset + start * isz
        row, row1 = a // ROW_BYTES, -(-(a + count * isz) // ROW_BYTES)
        first = (a - row * ROW_BYTES) // isz
        segments = []
        while row < row1:
            j = row * ROW_BYTES // bb
            j_row = j * bb // ROW_BYTES
            take = min(row1, j_row + bb // ROW_BYTES) - row
            got = where((p.shard, j))
            segments.append((None, 0, take) if got is None
                            else (got[0], got[1] + row - j_row, take))
            row += take
        slab = (hi[f] - lo[f],) + tuple(p.shape[f + 1:])
        select = ((0, hi[f] - lo[f]),) + tuple(
            (lo[d], hi[d]) for d in range(f + 1, len(lo)))
        dest = tuple(s + a - b for s, a, b in zip(p.start, lo, blo))
        parts.setdefault(out, []).append(
            (tuple(segments), first, count, slab, select, dest))
    return tuple(
        (dtype.name, tuple(shard_shape) or (1,), tuple(shard_shape),
         tuple(parts.get(o, ())))
        for o, (_name, dtype, _host, _sh, shard_shape) in enumerate(outputs))


# ------------------------------------------------------------ the programs


@functools.lru_cache(maxsize=16)
def _ici_program(devices: tuple, br: int, sends: tuple):
    """The chip-to-chip move of one plan: ``(mesh, jitted program)``."""
    n = len(devices)
    mesh = Mesh(np.array(devices), (_AXIS,))

    def pack(slots: tuple, width: int):
        def outbox(stage):
            rows = [lax.slice(stage, (k * br, 0), ((k + 1) * br,
                                                   WORDS_PER_CHUNK))
                    for k in slots]
            if width > len(slots):
                rows.append(jnp.zeros(((width - len(slots)) * br,
                                       WORDS_PER_CHUNK), jnp.uint32))
            return rows[0] if len(rows) == 1 else jnp.concatenate(rows)
        return outbox

    def ckpt_reshard_ici(stage):
        me = lax.axis_index(_AXIS)
        got = []
        with jax.named_scope("tpudfs.ckpt_reshard_ici"):
            for s, lists, width in sends:
                box = lax.switch(me, [pack(slots, width) for slots in lists],
                                 stage)
                got.append(lax.ppermute(
                    box, _AXIS, [(i, (i + s) % n) for i in range(n)]))
        return got[0] if len(got) == 1 else jnp.concatenate(got)

    return mesh, jax.jit(shard_map(ckpt_reshard_ici, mesh=mesh,
                                   in_specs=P(_AXIS), out_specs=P(_AXIS),
                                   check_vma=False))


def _part_bits(bufs: tuple, part: tuple, itemsize: int):
    segments, first, count, slab, select, _dest = part
    rows = [jnp.zeros((take, WORDS_PER_CHUNK), jnp.uint32) if buf is None
            else lax.slice(bufs[buf], (row, 0), (row + take, WORDS_PER_CHUNK))
            for buf, row, take in segments]
    rows = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
    if itemsize == 2:
        rows = ckpt_assemble._halves_in_order(rows)
    x = rows.reshape(-1)[first:first + count].reshape(slab)
    return lax.slice(x, [a for a, _b in select], [b for _a, b in select])


@functools.lru_cache(maxsize=64)
def _assembler(layout: tuple, tpu: bool, orders: tuple | None = None):
    """The jitted ``ckpt_reshard_assemble`` of one chip's layout. Keyed by
    the backend too, as ``ckpt_assemble``'s programs are, and by the chip's
    own layout of each output where that is not row-major (``orders``)."""
    orders = orders or (None,) * len(layout)

    def ckpt_reshard_assemble(stage, inbox):
        out = []
        with jax.named_scope("tpudfs.ckpt_reshard"):
            for (name, shape, final, parts), order in zip(layout, orders):
                dtype = np.dtype(name)
                unsigned = jnp.uint16 if dtype.itemsize == 2 else jnp.uint32
                shard = None
                for part in parts:
                    x = _part_bits((stage, inbox), part, dtype.itemsize)
                    x = x.reshape((1,) * (len(shape) - x.ndim) + x.shape)
                    if x.shape == shape:
                        shard = x
                        continue
                    if shard is None:
                        shard = jnp.zeros(shape, unsigned)
                    shard = lax.dynamic_update_slice(shard, x, part[5])
                shard = shard.reshape(final)
                out.append(ckpt_assemble._typed(shard, dtype, final, order)
                           if _on_chip(dtype, final, order)
                           else shard)  # the host's to finish
        return out

    return jax.jit(ckpt_reshard_assemble)


# -------------------------------------------------------- running a plan


def _zeros(rows: int, device):
    return jnp.zeros((rows, WORDS_PER_CHUNK), jnp.uint32, device=device)


def stage(plan_: Plan, blocks: dict[int, list]) -> list:
    """Every read block into its chip's staging slot: one gather a round,
    one a block that stands alone. ``blocks``: per shard file, its
    confirmed ``DeviceBlock``s in file order (None where skipped)."""
    br = plan_.block_rows
    stages = [_zeros(plan_.stage_rows, d) for d in plan_.devices]
    scratch = plan_.stage_rows - br
    rounds: dict[int, tuple] = {}
    for shard, held in blocks.items():
        for j, b in enumerate(held):
            if b is None:
                continue
            i = plan_.placement[shard][j]
            slot = plan_.stage_slots[i][(shard, j)] * br
            if b.size > br * ROW_BYTES or (
                    b.batch is not None and b.batch.cpb > br):
                raise ValueError(f"block {b.block_id} is larger than the "
                                 f"plan's {br} rows")
            if b.batch is None:
                stages[i] = ckpt_assemble_gather(
                    stages[i], jax.device_put(b.array, plan_.devices[i]),
                    np.asarray([slot], np.int32), nblocks=1)
                continue
            _i, _batch, dest = rounds.setdefault(
                id(b.batch), (i, b.batch,
                              np.full(b.batch.nblocks, scratch, np.int32)))
            dest[b.batch_index] = slot
    for i, batch, dest in rounds.values():
        stages[i] = ckpt_assemble_gather(
            stages[i], jax.device_put(batch.words, plan_.devices[i]), dest,
            nblocks=batch.nblocks)
    return stages


def redistribute(plan_: Plan, stages: list) -> list:
    """Each chip's inbox: the blocks it needs from the others' staging."""
    if not plan_.sends:
        return [_zeros(1, d) for d in plan_.devices]
    mesh, program = _ici_program(tuple(plan_.devices), plan_.block_rows,
                                 plan_.sends)
    n = len(plan_.devices)
    whole = jax.make_array_from_single_device_arrays(
        (n * plan_.stage_rows, WORDS_PER_CHUNK),
        NamedSharding(mesh, P(_AXIS)), stages)
    got = {s.device: s.data for s in program(whole).addressable_shards}
    return [got[d] for d in plan_.devices]


def _on_chip(dtype: np.dtype, shape: tuple, order: tuple | None) -> bool:
    """Whether the chip types this shard itself, bit for bit: as
    ``ckpt_assemble.on_device`` says, and a bf16 shard laid out in
    ``order`` only where the relabelling kernel has a view of it so."""
    if order is None or dtype.name != "bfloat16":
        return ckpt_assemble.on_device(dtype, shape)
    return ckpt_assemble.on_device(dtype, tuple(shape[d] for d in order))


def _orders(plan_: Plan, i: int) -> tuple | None:
    """Per output of chip ``i``: its layout on a TPU where that is not
    row-major and the output is bf16 (``ckpt_assemble.default_order``)."""
    if not on_tpu():
        return None
    orders = tuple(
        ckpt_assemble.default_order(name, final, plan_.devices[i])
        if name == "bfloat16" else None
        for name, _shape, final, _parts in plan_.layouts[i])
    return orders if any(orders) else None


def _program(plan_: Plan, i: int):
    """Chip ``i``'s assembly program."""
    return _assembler(plan_.layouts[i], on_tpu(), _orders(plan_, i))


def assemble(plan_: Plan, i: int, stage_, inbox) -> tuple[list, int, int]:
    """Chip ``i``'s shard of every output: ``(shards, bytes typed on the
    chip, bytes bounced through the host)``."""
    device = plan_.devices[i]
    parts = _program(plan_, i)(stage_, inbox)
    orders = _orders(plan_, i) or (None,) * len(parts)
    shards, on_dev, bounced = [], 0, 0
    for (name, dtype, _host, _sh, shape), part, order in zip(
            plan_.outputs, parts, orders):
        nbytes = math.prod(shape) * dtype.itemsize
        if _on_chip(dtype, shape, order):
            shards.append(part)
            on_dev += nbytes
            continue
        bits = np.asarray(part)  # unsigned, the dtype's width
        shards.append(jax.device_put(bits.view(dtype).reshape(shape),
                                     device))
        bounced += nbytes
    return shards, on_dev, bounced


def arrays(plan_: Plan, per_device: list[list]) -> dict:
    """``{name: jax.Array}`` of the outputs from every chip's shards."""
    return {name: jax.make_array_from_single_device_arrays(
                host, sharding, [shards[o] for shards in per_device])
            for o, (name, _dt, host, sharding, _shape)
            in enumerate(plan_.outputs)}


def warm(plan_: Plan, max_round: int, block_rows: set[int]) -> None:
    """Compile and run, on zeros, every program a restore of this plan can
    dispatch on the device after its blocks are in: the gather at each
    round size the combiners ship and at each size a block standing alone
    has (``block_rows``), the chip-to-chip move and every assembly."""
    br = plan_.block_rows
    stages = []
    for d in plan_.devices:
        buf = _zeros(plan_.stage_rows, d)
        shapes = [(1, r) for r in sorted(block_rows | {br})]
        k = 1
        while k <= max(1, max_round):
            shapes.append((k, br))
            k <<= 1
        for nblocks, rows in shapes:
            buf = ckpt_assemble_gather(
                buf, _zeros(nblocks * rows, d),
                np.full(nblocks, plan_.stage_rows - br, np.int32),
                nblocks=nblocks)
        stages.append(buf)
    inboxes = redistribute(plan_, stages)
    jax.block_until_ready([_program(plan_, i)(stages[i], inboxes[i])
                           for i in range(len(plan_.devices))])
