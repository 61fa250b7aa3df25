"""The least work a degraded erasure-coded read asks of the chip, counted
from the code's shape and the reader's counters, whatever implements the
decode: for every block that lost a data shard, the k surviving shards are
read from HBM once and the missing data shards are written once."""

from __future__ import annotations


def shard_bytes(block_bytes: int, k: int) -> int:
    """Upstream's shard length: ``ceil(block / k)``."""
    return -(-block_bytes // k)


def decode_min_bytes(degraded_blocks: int, missing_data_shards: int,
                     block_bytes: int, k: int) -> int:
    """HBM bytes the reconstruction cannot avoid: ``k`` shards in for each
    degraded block, each missing data shard out."""
    return (k * degraded_blocks + missing_data_shards) \
        * shard_bytes(block_bytes, k)


def decode_min_seconds(degraded_blocks: int, missing_data_shards: int,
                       block_bytes: int, k: int, peaks: dict) -> float:
    """HBM-bound: the GF(2^8) arithmetic is a few dozen integer operations
    a word, under the VPU's rate at that bandwidth."""
    return decode_min_bytes(degraded_blocks, missing_data_shards,
                            block_bytes, k) / peaks["hbm_bytes_per_s"]
