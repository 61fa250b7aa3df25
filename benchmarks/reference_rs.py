"""The plain reference for erasure-coded deployments: Reed-Solomon RS(k, m)
over GF(2^8) as upstream's ``reed-solomon-erasure`` crate defines it.

Nothing here imports ``tpudfs`` or ``native/``. The field is GF(2^8) with
the polynomial x^8 + x^4 + x^3 + x^2 + 1 (0x11D) and generator 2; the code
is the Vandermonde matrix ``V[r][c] = r**c`` (rows 0 .. k+m-1) made
systematic by the inverse of its top k x k block, so the first k shards are
the data itself. A block is cut as upstream cuts it: ``shard_len =
ceil(len / k)``, the data zero-padded to ``k * shard_len``. Everything is
table-driven ``numpy``: one 256 x 256 product table, shards as uint8 rows.

The semantics the reference states are the configuration's guarantees: the
k + m shards of an acknowledged block are exactly ``encode``'s, one per
chunkserver, and ANY k of them give the block back (``decode``).
"""

from __future__ import annotations

import numpy as np

_POLY = 0x11D


def _product_table() -> np.ndarray:
    """``MUL[a, b]`` = a * b in GF(2^8), from the powers of the generator."""
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for power in range(255):
        exp[power] = x
        log[x] = power
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:] = exp[:255]
    table = exp[log[:, None] + log[None, :]].astype(np.uint8)
    table[0, :] = 0
    table[:, 0] = 0
    return table


MUL = _product_table()


def _power(a: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = int(MUL[out, a])
    return out


def _inverse_of(a: int) -> int:
    return int(np.flatnonzero(MUL[a] == 1)[0])


def _matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Matrix product over GF(2^8); ``b`` may be a matrix or shard rows."""
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for r in range(a.shape[0]):
        for c in range(a.shape[1]):
            if a[r, c]:
                out[r] ^= MUL[a[r, c], b[c]]
    return out


def _inverted(matrix: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = matrix.shape[0]
    work = np.concatenate([matrix.astype(np.uint8),
                           np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivots = np.flatnonzero(work[col:, col])
        if not len(pivots):
            raise ValueError("singular matrix")
        pivot = col + int(pivots[0])
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        work[col] = MUL[_inverse_of(int(work[col, col])), work[col]]
        for row in range(n):
            if row != col and work[row, col]:
                work[row] ^= MUL[int(work[row, col]), work[col]]
    return work[:, n:]


def generator(k: int, m: int) -> np.ndarray:
    """The systematic (k + m) x k matrix: identity on top, parity rows
    below."""
    if k <= 0 or m <= 0 or k + m > 256:
        raise ValueError(f"no RS({k},{m}) over GF(2^8)")
    vandermonde = np.array([[_power(r, c) for c in range(k)]
                            for r in range(k + m)], dtype=np.uint8)
    return _matmul(vandermonde, _inverted(vandermonde[:k]))


def shard_len(nbytes: int, k: int) -> int:
    return -(-nbytes // k)


def encode(block, k: int, m: int) -> list[bytes]:
    """The k + m shards of ``block``: k of the zero-padded data, then m of
    parity."""
    if not len(block):
        raise ValueError("an empty block has no shards")
    size = shard_len(len(block), k)
    padded = np.zeros(k * size, dtype=np.uint8)
    padded[: len(block)] = np.frombuffer(block, dtype=np.uint8)
    data = padded.reshape(k, size)
    parity = _matmul(generator(k, m)[k:], data)
    return [row.tobytes() for row in data] + [row.tobytes() for row in parity]


def decode(shards: list, k: int, m: int, nbytes: int) -> bytes:
    """The block of ``nbytes`` from any k of its k + m shards (``None``
    where a shard is lost)."""
    if len(shards) != k + m:
        raise ValueError(f"RS({k},{m}) has {k + m} slots, got {len(shards)}")
    have = [i for i, s in enumerate(shards) if s is not None][:k]
    if len(have) < k:
        raise ValueError(f"{len(have)} shards survive, {k} are needed")
    rows = np.stack([np.frombuffer(shards[i], dtype=np.uint8) for i in have])
    data = _matmul(_inverted(generator(k, m)[have]), rows)
    return data.reshape(-1).tobytes()[:nbytes]
