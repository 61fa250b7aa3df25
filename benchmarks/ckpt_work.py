"""The least work the assembly of restored shards asks of the chip, counted
from the shard files' sizes, whatever implements it: every byte of a shard's
payload, in the rows of 512 bytes it arrives in, is read from HBM once (out
of the blocks the read left there) and written once (into its tensor)."""

from __future__ import annotations

ROW = 512


def aligned(payload_bytes: int) -> int:
    return -(-payload_bytes // ROW) * ROW


def assemble_min_bytes(payload_sizes) -> int:
    """HBM bytes the assembly of these shards cannot avoid: each shard's
    aligned payload in, the same out."""
    return 2 * sum(aligned(n) for n in payload_sizes)


def assemble_min_seconds(payload_sizes, peaks: dict) -> float:
    """HBM-bound: a bitcast or a split of a word into its halves is a few
    integer operations a word, under the VPU's rate at that bandwidth."""
    return assemble_min_bytes(payload_sizes) / peaks["hbm_bytes_per_s"]
