"""The least work a restore under another layout asks of the chips,
counted from the configuration's tensor table and its target layout
(``reference_reshard``), whatever implements the restore.

- Assembly (HBM-bound): each chip's shards, each aligned to the 512-byte
  rows the bytes arrive in, read once from HBM and written once. A split
  of a word into its halves or a bitcast is a few integer operations a
  word, under the VPU's rate at that bandwidth. No assembly can do less:
  every byte a chip holds has to be written there, out of bytes that were
  read there, so the share of the roofline cannot pass 100%.
- Chip to chip (ICI-bound): the bytes held by more than one chip beyond
  their first copy, which have to cross from the chip they landed on to
  the others (each saved byte crosses the bus once), spread evenly over the
  chips that receive them, each at its published ICI rate. A move that
  carries anything else (whole blocks, padding) only takes longer, so this
  share cannot pass 100% either.
"""

from __future__ import annotations

import math

from benchmarks import reference_reshard as ref

ROW = 512


def chips(cfg: dict) -> int:
    return math.prod(cfg["target"]["mesh"].values())


def aligned(nbytes: int) -> int:
    return -(-nbytes // ROW) * ROW


def chip_bytes(cfg: dict) -> list[int]:
    """Per chip, the aligned bytes of every shard it holds."""
    tensors = ref.table(cfg)
    out = []
    for chip in range(chips(cfg)):
        total = 0
        for name, entry in tensors.items():
            index = ref.device_index(cfg, name, chip)
            total += aligned(ref.nbytes(entry[0], tuple(
                s.stop - s.start for s in index)))
        out.append(total)
    return out


def duplicated_bytes(cfg: dict) -> int:
    """Bytes held by more than one chip, beyond their first copy: the
    chips' shards, less the host share once."""
    tensors = ref.table(cfg)
    held = sum(ref.nbytes(entry[0], tuple(s.stop - s.start for s in
                                          ref.device_index(cfg, name, c)))
               for name, entry in tensors.items()
               for c in range(chips(cfg)))
    return held - ref.unique_bytes(cfg)


def assemble_min_seconds(cfg: dict, chip: int, peaks: dict) -> float:
    """One chip's assembly: its aligned shards in and out of HBM."""
    return 2 * chip_bytes(cfg)[chip] / peaks["hbm_bytes_per_s"]


def ici_min_seconds(cfg: dict, peaks: dict) -> float:
    """One chip's part of the move: the duplicated bytes over the chips,
    at one chip's ICI rate."""
    return duplicated_bytes(cfg) / chips(cfg) / (peaks["ici_bits_per_s"] / 8)
