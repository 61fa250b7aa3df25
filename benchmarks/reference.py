"""The plain reference: what the served path has to answer, from the seed.

Nothing here imports ``tpudfs`` or ``native/``. Bytes are regenerated from
``--seed``; CRC32C (Castagnoli, reflected 0x82F63B78) is a table-driven
implementation vectorised over blocks with numpy. The semantics the
reference states are the configuration's guarantees: a put that was acknowledged is a file of exactly
those bytes, cut into blocks of the client's block size, whose recorded
checksum is the CRC32C of each block, held by ``replication`` distinct
chunkservers that each return those bytes; a read into HBM leaves exactly
those bytes on the device, every block confirmed verified.
"""

from __future__ import annotations

import numpy as np

CHUNK = 512
_POLY = 0x82F63B78


def _byte_table() -> np.ndarray:
    table = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        table = np.where(table & 1, (table >> 1) ^ np.uint32(_POLY),
                         table >> 1).astype(np.uint32)
    return table


_T = _byte_table()


def _zero_shift_tables(nbytes: int) -> np.ndarray:
    """(4, 256) tables of the linear map "feed ``nbytes`` zero bytes" on a
    raw CRC register, one table per register byte."""
    cols = np.uint32(1) << np.arange(32, dtype=np.uint32)  # basis registers
    for _ in range(nbytes):
        cols = _T[cols & 0xFF] ^ (cols >> 8)
    tables = np.zeros((4, 256), dtype=np.uint32)
    for byte in range(4):
        for bit in range(8):
            mask = (np.arange(256) >> bit) & 1
            tables[byte] ^= np.where(mask, cols[8 * byte + bit],
                                     0).astype(np.uint32)
    return tables


_SHIFT_CHUNK = _zero_shift_tables(CHUNK)


def _shift(reg: np.ndarray, tables: np.ndarray) -> np.ndarray:
    return (tables[0][reg & 0xFF] ^ tables[1][(reg >> 8) & 0xFF]
            ^ tables[2][(reg >> 16) & 0xFF] ^ tables[3][reg >> 24])


def _raw(rows: np.ndarray, reg: np.ndarray) -> np.ndarray:
    """Feed each row of ``rows`` (n, L) uint8 to its register in ``reg``."""
    for col in range(rows.shape[1]):
        reg = _T[(reg ^ rows[:, col]) & 0xFF] ^ (reg >> 8)
    return reg


def crc32c(data) -> int:
    """CRC32C of one buffer, byte by byte (short inputs, test vectors)."""
    row = np.frombuffer(bytes(data), dtype=np.uint8)[None, :]
    reg = _raw(row, np.full(1, 0xFFFFFFFF, dtype=np.uint32))
    return int(reg[0] ^ np.uint32(0xFFFFFFFF))


def crc32c_blocks(data, block_bytes: int) -> list[int]:
    """CRC32C of every ``block_bytes`` block of ``data`` (the last may be
    short). Whole 512-byte chunks are folded vectorised over all blocks:
    raw registers of every chunk from 0, then Horner over the chunk columns
    with the zero-shift map; the 0xFFFFFFFF preset rides the first chunk."""
    buf = np.frombuffer(data, dtype=np.uint8)
    out: list[int] = []
    whole = len(buf) // block_bytes if block_bytes % CHUNK == 0 else 0
    if whole:
        cpb = block_bytes // CHUNK
        chunks = buf[: whole * block_bytes].reshape(whole * cpb, CHUNK)
        raws = _raw(chunks, np.zeros(whole * cpb, dtype=np.uint32)
                    ).reshape(whole, cpb)
        # The preset: 0xFFFFFFFF fed through the first chunk's zero-shift.
        acc = _shift(np.full(whole, 0xFFFFFFFF, dtype=np.uint32),
                     _SHIFT_CHUNK) ^ raws[:, 0]
        for col in range(1, cpb):
            acc = _shift(acc, _SHIFT_CHUNK) ^ raws[:, col]
        out = [int(v) for v in acc ^ np.uint32(0xFFFFFFFF)]
    for off in range(whole * block_bytes, len(buf), block_bytes):
        out.append(crc32c(buf[off : off + block_bytes]))
    if not len(buf):
        out.append(crc32c(b""))
    return out


def seeded_bytes(seed: int, stream: int, nbytes: int) -> bytes:
    """The benchmark's data: ``nbytes`` from (seed, stream). ``seed`` may
    exceed 2**31."""
    words = np.random.SFC64([int(seed), int(stream)]).random_raw(
        -(-nbytes // 8))
    return words.tobytes()[:nbytes]


def expected_file(data, block_bytes: int, replication: int) -> dict:
    """What the metadata of an acknowledged put of ``data`` has to say."""
    return {
        "size": len(data),
        "block_sizes": [min(block_bytes, len(data) - off)
                        for off in range(0, max(len(data), 1), block_bytes)],
        "block_crcs": crc32c_blocks(data, block_bytes),
        "replicas": replication,
    }
