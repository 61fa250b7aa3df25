"""The plain reference of the training-infeed deployment: what an
ImageNet-shaped WebDataset holds, where its bytes lie in the shard files,
and what an epoch hands to the step. From ``--seed`` and the
configuration's ``dataset`` alone; nothing here imports ``tpudfs`` or
``native/``.

The semantics it states are the configuration's guarantees:

- sample ``key`` (0 .. samples - 1; shard ``key // samples_per_shard``) is
  ``NNNNNNNN.img``, ``record_bytes`` bytes of stream ``IMG_STREAM`` of
  ``(seed, key)``, and ``NNNNNNNN.cls``, its label 0 .. ``classes`` - 1 from
  stream ``LABEL_STREAM`` of the seed, in decimal ASCII: bytes, key and
  label belong together;
- a shard file is its samples in key order as members of one plain tar laid
  out by the standard library's ``tarfile`` with its defaults (ustar
  headers for these short ASCII names, zeroed times and owners, the
  archive's end padded to 10 240 B), so ``harness.Expect.metadata`` /
  ``replicas`` can hold the cluster's copies to ``shard_tar``;
- an epoch hands over every key exactly once (``epoch_faults``), in an
  order that no other epoch of the run has (``same_order``); which order is
  the pipeline's own business and no part of the reference;
- a record on the device is held to ``digest``: two ``uint32`` of its
  little-endian 32-bit words ``w[j]``, the wrapping sum of ``(j + 1) *
  w[j]`` and the xor of all ``w[j]``. Exact, no tolerance: the sum moves
  with any word that changed or changed place (a sheared offset, two
  records' rows mixed), the xor with any single bit wherever it is, and a
  record exchanged whole for another key's fails both against its key.
  Integers, because a float sum would make the order of a reduction part
  of the answer.
"""

from __future__ import annotations

import io
import tarfile

import numpy as np

IMG_STREAM = 7000
LABEL_STREAM = 7001


def shard_path(cfg: dict, shard: int) -> str:
    """Where ``wds.write_wds_shards(client, f"{prefix}-{shard:02d}", ...)``
    puts the one shard it is given."""
    return f"{cfg['dataset']['prefix']}-{shard:02d}-000000.tar"


def samples(cfg: dict) -> int:
    ds = cfg["dataset"]
    return ds["shards"] * ds["samples_per_shard"]


def name(key: int) -> str:
    return f"{key:08d}"


def image(seed: int, cfg: dict, key: int) -> bytes:
    """The ``record_bytes`` bytes of sample ``key``."""
    n = cfg["dataset"]["record_bytes"]
    words = np.random.SFC64([int(seed), IMG_STREAM, int(key)]).random_raw(
        -(-n // 8))
    return words.tobytes()[:n]


def labels(seed: int, cfg: dict) -> np.ndarray:
    """``(samples,)`` int32: the label of every key."""
    raw = np.random.SFC64([int(seed), LABEL_STREAM]).random_raw(samples(cfg))
    return (raw % np.uint64(cfg["dataset"]["classes"])).astype(np.int32)


def keys_of_shard(cfg: dict, shard: int) -> range:
    per = cfg["dataset"]["samples_per_shard"]
    return range(shard * per, (shard + 1) * per)


def shard_samples(seed: int, cfg: dict, shard: int):
    """The samples of one shard as ``wds.write_wds_shards`` takes them."""
    label = labels(seed, cfg)
    for key in keys_of_shard(cfg, shard):
        yield {"__key__": name(key), "img": image(seed, cfg, key),
               "cls": str(int(label[key])).encode()}


def shard_tar(seed: int, cfg: dict, shard: int) -> bytes:
    """The whole shard file, byte for byte."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for sample in shard_samples(seed, cfg, shard):
            for ext in ("img", "cls"):
                info = tarfile.TarInfo(name=f"{sample['__key__']}.{ext}")
                info.size = len(sample[ext])
                tf.addfile(info, io.BytesIO(sample[ext]))
    return buf.getvalue()


def digest(rows: np.ndarray) -> np.ndarray:
    """``(n, 2)`` uint32 of ``(n, record_bytes)`` uint8 rows (a record's
    length is a multiple of 4): the position-weighted wrapping sum and the
    xor fold of each row's little-endian words."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    words = rows.view("<u4")
    weights = np.arange(1, words.shape[1] + 1, dtype=np.uint32)
    return np.stack([(words * weights).sum(axis=1, dtype=np.uint32),
                     np.bitwise_xor.reduce(words, axis=1)], axis=1)


def digests(seed: int, cfg: dict) -> np.ndarray:
    """``(samples, 2)`` uint32: the digest of every key's record."""
    n = cfg["dataset"]["record_bytes"]
    out = np.empty((samples(cfg), 2), dtype=np.uint32)
    for shard in range(cfg["dataset"]["shards"]):
        keys = keys_of_shard(cfg, shard)
        rows = np.frombuffer(
            b"".join(image(seed, cfg, key) for key in keys),
            dtype=np.uint8).reshape(len(keys), n)
        out[keys.start:keys.stop] = digest(rows)
    return out


def epoch_faults(keys: np.ndarray, total: int) -> int:
    """Of one epoch's delivered keys: how many of the ``total`` keys it did
    not hand over exactly once (absent, twice, or no key at all)."""
    keys = np.asarray(keys, dtype=np.int64)
    valid = keys[(keys >= 0) & (keys < total)]
    counts = np.bincount(valid, minlength=total)
    return int((counts != 1).sum()) + int(len(keys) - len(valid))


def same_order(epochs: list[np.ndarray]) -> int:
    """How many epochs repeat the order of an earlier one."""
    seen: set[bytes] = set()
    repeats = 0
    for keys in epochs:
        order = np.asarray(keys, dtype=np.int64).tobytes()
        repeats += order in seen
        seen.add(order)
    return repeats
