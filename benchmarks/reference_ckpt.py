"""The plain reference of the checkpoint deployment: what a published,
sharded, mixed-precision training state holds, where its bytes lie in the
shard files, and what a restore has to return. From ``--seed`` and the
configuration's tensor table alone; nothing here imports ``tpudfs`` or
``native/``.

The semantics it states are the configuration's guarantees:

- every tensor is ``dtype``, ``shape`` and the bytes of one stream of the
  seed (stream ``STREAMS[step] + i``, ``i`` the tensor's place among all
  names sorted), little-endian, C order;
- tensors are dealt to ``num_shards`` shards largest first, each to the
  lightest shard so far (ties: the name, then the lowest shard);
- a shard's file is its tensors sorted by name, each at the next offset
  that is a multiple of 512, zeros between, nothing after the last;
- files live where the format says (``manifest_path``, ``shard_path``), so
  ``harness.Expect.metadata`` / ``replicas`` can hold them to the payload;
- a restore returns ``{shard: {name: (dtype, shape, bytes)}}`` of the
  newest PUBLISHED step and nothing of a step that was only staged.

Comparison is of bit patterns (seeded bytes are NaNs as often as not):
there is no tolerance, so a restore that went through a lower precision,
swapped the halves of a bfloat16 pair or sheared an offset fails by every
tensor it touched.
"""

from __future__ import annotations

from benchmarks import reference

ALIGN = 512
#: first stream of each step's tensors; a staged step holds other bytes
#: than the published one, so a restore that saw it cannot pass
STREAMS = {"published": 1000, "torn": 5000}
ITEMSIZE = {"bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
            "float32": 4, "int32": 4, "uint32": 4,
            "int8": 1, "uint8": 1, "bool": 1, "float64": 8, "int64": 8}


def table(cfg: dict) -> dict[str, tuple[str, tuple[int, ...]]]:
    """``{name: (dtype, shape)}`` of the whole training state: every
    parameter of the layer once per optimizer state, plus the scalars."""
    ds = cfg["dataset"]
    params = {f"{ds['layer']}.{name}": tuple(shape)
              for name, shape in ds["parameters"].items()}
    for e in range(ds.get("experts_held", 0)):
        for name, shape in ds["expert_parameters"].items():
            params[f"{ds['layer']}.{name.format(e=e)}"] = tuple(shape)
    out = {f"{state}/{name}": (dtype, shape)
           for state, dtype in ds["states"].items()
           for name, shape in params.items()}
    out.update({name: (dtype, ()) for name, dtype in ds["scalars"].items()})
    return out


def nbytes(dtype: str, shape: tuple[int, ...]) -> int:
    n = ITEMSIZE[dtype]
    for dim in shape:
        n *= dim
    return n


def deal(cfg: dict) -> list[list[str]]:
    """The names of each shard, sorted: largest tensor first, each to the
    lightest shard so far."""
    tensors = table(cfg)
    shards: list[list[str]] = [[] for _ in range(cfg["assumed"]["num_shards"])]
    load = [0] * len(shards)
    for name in sorted(tensors, key=lambda n: (-nbytes(*tensors[n]), n)):
        lightest = min(range(len(shards)), key=lambda s: (load[s], s))
        shards[lightest].append(name)
        load[lightest] += nbytes(*tensors[name])
    return [sorted(names) for names in shards]


def layout(cfg: dict, shard: int) -> tuple[list[tuple[str, int, int]], int]:
    """``([(name, offset, size)], payload bytes)`` of one shard file."""
    tensors = table(cfg)
    placed, end = [], 0
    for name in deal(cfg)[shard]:
        offset = -(-end // ALIGN) * ALIGN
        size = nbytes(*tensors[name])
        placed.append((name, offset, size))
        end = offset + size
    return placed, end


def tensor(seed: int, cfg: dict, name: str, step: str = "published"
           ) -> tuple[str, tuple[int, ...], bytes]:
    """``(dtype, shape, bytes)`` of one tensor of the ``published`` step
    (or of the ``torn`` one)."""
    tensors = table(cfg)
    dtype, shape = tensors[name]
    stream = STREAMS[step] + sorted(tensors).index(name)
    return dtype, shape, reference.seeded_bytes(seed, stream,
                                                nbytes(dtype, shape))


def shard_payload(seed: int, cfg: dict, shard: int,
                  step: str = "published") -> bytes:
    placed, end = layout(cfg, shard)
    out = bytearray(end)
    for name, offset, size in placed:
        out[offset:offset + size] = tensor(seed, cfg, name, step)[2]
    return bytes(out)


def expected_restore(seed: int, cfg: dict) -> dict[int, dict[str, tuple]]:
    """What ``restore`` must return: every tensor of the newest published
    step, by shard. (The whole state: tests and small sizes; the cell's
    check regenerates the tensors it samples one at a time.)"""
    return {shard: {name: tensor(seed, cfg, name) for name in names}
            for shard, names in enumerate(deal(cfg))}


# ------------------------------------------------ where the format puts it


def manifest_path(base: str, step: int) -> str:
    return f"{base.rstrip('/')}/MANIFEST-{step:016d}"


def shard_path(base: str, step: int, shard: int) -> str:
    return f"{base.rstrip('/')}/.ckpt/{step:016d}/shard-{shard:05d}.bin"


def straddlers(cfg: dict, dtype: str) -> list[str]:
    """Names of the ``dtype`` tensors whose bytes cross a block boundary
    of their shard file."""
    bb = cfg["block_bytes"]
    tensors = table(cfg)
    return [name for shard in range(cfg["assumed"]["num_shards"])
            for name, offset, size in layout(cfg, shard)[0]
            if tensors[name][0] == dtype and size
            and offset // bb != (offset + size - 1) // bb]
