"""The plain reference of the re-layout restore: what one host's share of a
training state holds (every global tensor of the host's part made from the
seed), how the job that saved it laid it out (layout A: ranks saved pieces
of the stacked experts, rank 0 the rest once, each rank in its shard
files), and what each chip of the layout it resumes as (layout B: a mesh
and a ``PartitionSpec`` a tensor) must hold. From ``--seed`` and the
configuration alone; nothing here imports ``tpudfs`` or ``native/``, and
a device's slice is plain numpy indexing.

The semantics it states are the configuration's guarantees:

- every global tensor's host part is ``dtype``, its host shape and the
  bytes of one stream of the seed (stream ``STREAMS[step] + i``, ``i`` its
  place among all names sorted), little-endian, C order;
- rank ``r`` saved experts ``[r * E, (r + 1) * E)`` of each stacked expert
  tensor (``E`` = ``experts_per_rank``); rank 0 saved every other tensor
  whole (ranks that also hold it saved nothing of it);
- a rank's tensors are dealt to its ``files_per_rank`` files largest
  first, each to the lightest file so far (ties: the name, then the lowest
  file); file ``k`` of rank ``r`` is shard ``r * files_per_rank + k``; a
  file holds its tensors sorted by name, each at the next offset that is a
  multiple of 512, zeros between, nothing after the last;
- chip ``i`` of the mesh (row-major over ``target.mesh``'s axes in their
  order) holds of each tensor the host part indexed, dim by dim, by the
  chip's coordinate on the spec's axis (a dim without one: whole), with
  the saved dtype's bytes;
- nothing of a step that was only staged is seen.

Comparison is of bit patterns: a restore that went through another
precision, swapped halves, sheared a stride or gave a chip another chip's
slice fails by every byte it touched.
"""

from __future__ import annotations

import itertools
import math

import numpy as np

from benchmarks import reference

ALIGN = 512
STREAMS = {"published": 1000, "torn": 5000}
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int32": 4,
            "uint32": 4, "int16": 2, "uint16": 2}


def table(cfg: dict) -> dict[str, tuple[str, tuple[int, ...], tuple]]:
    """``{name: (dtype, global shape, host range)}``: every parameter once
    per optimizer state (the experts stacked), plus the scalars."""
    ds = cfg["dataset"]
    params: dict[str, tuple[tuple, tuple]] = {}
    for name, shape in ds["parameters"].items():
        params[f"{ds['layer']}.{name}"] = (tuple(shape),
                                          tuple((0, n) for n in shape))
    for name, shape in ds["expert_parameters"].items():
        gshape = (ds["experts_published"], *shape)
        params[f"{ds['layer']}.{name}"] = (
            gshape, ((0, ds["experts_held"]),) + tuple((0, n) for n in shape))
    out = {f"{state}/{name}": (dtype, gshape, rng)
           for state, dtype in ds["states"].items()
           for name, (gshape, rng) in params.items()}
    out.update({name: (dtype, (), ())
                for name, dtype in ds["scalars"].items()})
    return out


def host_shape(entry: tuple) -> tuple[int, ...]:
    return tuple(b - a for a, b in entry[2])


def nbytes(dtype: str, shape: tuple[int, ...]) -> int:
    return ITEMSIZE[dtype] * math.prod(shape)


def is_expert(cfg: dict, name: str) -> bool:
    ds = cfg["dataset"]
    return any(name.endswith(f"{ds['layer']}.{p}")
               for p in ds["expert_parameters"])


def host_bytes(seed: int, cfg: dict, name: str, step: str = "published"
               ) -> bytes:
    """The bytes of the host part of global tensor ``name``."""
    tensors = table(cfg)
    entry = tensors[name]
    stream = STREAMS[step] + sorted(tensors).index(name)
    return reference.seeded_bytes(seed, stream,
                                  nbytes(entry[0], host_shape(entry)))


def _as_words(data: bytes, dtype: str, shape: tuple) -> np.ndarray:
    return np.frombuffer(data, f"<u{ITEMSIZE[dtype]}").reshape(shape)


# ------------------------------------------------------ layout A: the save


def rank_pieces(cfg: dict, rank: int) -> dict[str, tuple[tuple, tuple]]:
    """``{name: (start, shape)}`` of what ``rank`` saved (global index)."""
    a = cfg["assumed"]
    per = a["experts_per_rank"]
    out = {}
    for name, (dtype, gshape, rng) in table(cfg).items():
        if is_expert(cfg, name):
            out[name] = ((rank * per,) + (0,) * (len(gshape) - 1),
                         (per,) + gshape[1:])
        elif rank == a["dedup_rank"]:
            out[name] = ((0,) * len(gshape), gshape)
    return out


def piece_bytes(seed: int, cfg: dict, name: str, rank: int,
                step: str = "published") -> bytes:
    dtype, _gshape, rng = table(cfg)[name]
    start, shape = rank_pieces(cfg, rank)[name]
    host = _as_words(host_bytes(seed, cfg, name, step), dtype,
                     host_shape(table(cfg)[name]))
    index = tuple(slice(s - a, s - a + n)
                  for s, (a, _b), n in zip(start, rng, shape))
    return host[index].tobytes()


def deal(cfg: dict, rank: int) -> list[list[str]]:
    """The names of each of ``rank``'s files, sorted."""
    tensors = table(cfg)
    pieces = rank_pieces(cfg, rank)
    files: list[list[str]] = [[] for _ in range(
        cfg["assumed"]["files_per_rank"])]
    load = [0] * len(files)
    size = {n: nbytes(tensors[n][0], pieces[n][1]) for n in pieces}
    for name in sorted(pieces, key=lambda n: (-size[n], n)):
        lightest = min(range(len(files)), key=lambda f: (load[f], f))
        files[lightest].append(name)
        load[lightest] += size[name]
    return [sorted(names) for names in files]


def shards(cfg: dict) -> list[tuple[int, list[str]]]:
    """``[(rank, names)]`` by shard id."""
    return [(rank, names) for rank in cfg["assumed"]["ranks_saved"]
            for names in deal(cfg, rank)]


def layout(cfg: dict, shard: int) -> tuple[list[tuple[str, int, int]], int]:
    """``([(name, offset, size)], payload bytes)`` of one shard file."""
    rank, names = shards(cfg)[shard]
    tensors, pieces = table(cfg), rank_pieces(cfg, rank)
    placed, end = [], 0
    for name in names:
        offset = -(-end // ALIGN) * ALIGN
        size = nbytes(tensors[name][0], pieces[name][1])
        placed.append((name, offset, size))
        end = offset + size
    return placed, end


def shard_payload(seed: int, cfg: dict, shard: int,
                  step: str = "published") -> bytes:
    rank, _names = shards(cfg)[shard]
    placed, end = layout(cfg, shard)
    out = bytearray(end)
    for name, offset, size in placed:
        out[offset:offset + size] = piece_bytes(seed, cfg, name, rank, step)
    return bytes(out)


# ---------------------------------------------------- layout B: the resume


def mesh_coords(cfg: dict) -> list[dict[str, int]]:
    """Each chip's coordinate on every mesh axis, chips row-major."""
    axes = cfg["target"]["mesh"]
    return [dict(zip(axes, c))
            for c in itertools.product(*(range(n) for n in axes.values()))]


def spec_of(cfg: dict, name: str) -> list:
    """The ``PartitionSpec`` entries of ``name`` (a list; [] replicated)."""
    ds = cfg["dataset"]
    for param, spec in cfg["target"]["specs"].items():
        if name.endswith(f"/{ds['layer']}.{param}"):
            return list(spec)
    return []


def device_index(cfg: dict, name: str, chip: int) -> tuple[slice, ...]:
    """Chip ``chip``'s slice of the host part of ``name``."""
    axes = cfg["target"]["mesh"]
    shape = host_shape(table(cfg)[name])
    coord = mesh_coords(cfg)[chip]
    spec = spec_of(cfg, name) + [None] * (len(shape) - len(spec_of(cfg, name)))
    out = []
    for dim, axis in zip(shape, spec):
        if axis is None:
            out.append(slice(0, dim))
            continue
        step = dim // axes[axis]
        out.append(slice(coord[axis] * step, (coord[axis] + 1) * step))
    return tuple(out)


def device_shards(seed: int, cfg: dict, name: str
                  ) -> list[tuple[str, tuple[int, ...], bytes]]:
    """``(dtype, shape, bytes)`` every chip, in order, must hold of
    ``name``."""
    entry = table(cfg)[name]
    host = _as_words(host_bytes(seed, cfg, name), entry[0], host_shape(entry))
    out = []
    for chip in range(len(mesh_coords(cfg))):
        got = host[device_index(cfg, name, chip)]
        out.append((entry[0], tuple(got.shape), got.tobytes()))
    return out


def unique_bytes(cfg: dict) -> int:
    """Bytes of the host's share: every global tensor's host part once."""
    return sum(nbytes(e[0], host_shape(e)) for e in table(cfg).values())
