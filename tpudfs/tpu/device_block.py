"""What a read into HBM hands back: a block's words on a device, alone or
as a row range of one fused round. Data only: the readers build these, and
none of them is imported here."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np


@dataclass
class DeviceBatch:
    """One fused round living on device: ``words`` holds ``nblocks``
    consecutive blocks of ``cpb`` chunks each; ``crcs`` is the (nblocks,)
    on-device whole-block CRC fold, resolved lazily (``resolved``) by the
    reader's batched confirm with one device→host transfer per confirm
    call covering every batch."""

    words: jax.Array  # (nblocks * cpb, 128) uint32
    crcs: jax.Array | None  # (nblocks,) uint32, on device
    cpb: int
    nblocks: int
    resolved: np.ndarray | None = None

    def block_words(self, i: int) -> jax.Array:
        return self.words[i * self.cpb : (i + 1) * self.cpb]


class DeviceBlock:
    """One block's words on one device — either its own (chunks, 128) array
    or a slice-on-demand view into a fused :class:`DeviceBatch` (the batched
    read path). ``pending_crc``/``batch_pending`` mark lazy verification:
    the 0-d (or batch-vector) on-device CRC fold is resolved against
    ``expected_crc`` by ``HbmReader.confirm`` with ONE host sync per confirm
    call. The comparison happens on the HOST — an eager per-block
    ``== expected`` would upload a scalar and sync the host once per block
    instead of once per batch."""

    def __init__(self, block_id: str, array: jax.Array | None, size: int,
                 verified: bool, *, pending_crc: jax.Array | None = None,
                 expected_crc: int | None = None, source: dict | None = None,
                 device: object | None = None,
                 batch: DeviceBatch | None = None,
                 batch_index: int = 0, batch_pending: bool = False):
        self.block_id = block_id
        self._array = array
        self.size = size  # unpadded byte length
        self.verified = verified
        self.pending_crc = pending_crc
        self.expected_crc = expected_crc
        #: source block metadata + target device, kept so a failed lazy
        #: verify can be retried through the host-verified fetch path.
        self.source = source
        self.device = device
        #: fused-round fields: the DeviceBatch this block rides in, its row
        #: index there, and whether its verdict is still unresolved in the
        #: batch's (n,) CRC vector.
        self.batch = batch
        self.batch_index = batch_index
        self.batch_pending = batch_pending

    @property
    def array(self) -> jax.Array:
        """(chunks, 128) uint32 words. Batched blocks materialize their
        slice of the round lazily — slicing dispatches a device op, so the
        hot infeed path synchronizes on :attr:`sync_arrays` instead and
        only consumers that need per-block arrays pay for the slice."""
        if self._array is None and self.batch is not None:
            self._array = self.batch.block_words(self.batch_index)
        return self._array

    @array.setter
    def array(self, value: jax.Array) -> None:
        self._array = value
        self.batch = None

    @property
    def sync_arrays(self) -> list:
        """Device values a completion wait must cover for this block —
        WITHOUT materializing per-block slices of a fused batch."""
        if self.batch is not None and self._array is None:
            out = [self.batch.words]
            if self.batch.crcs is not None:
                out.append(self.batch.crcs)
            return out
        out = [self._array]
        if self.pending_crc is not None:
            out.append(self.pending_crc)
        return out


def device_array_to_bytes(arr: jax.Array, size: int) -> bytes:
    """Host copy-out (for tests / CLI): unpad the device words."""
    return np.asarray(arr).astype("<u4").tobytes()[:size]
