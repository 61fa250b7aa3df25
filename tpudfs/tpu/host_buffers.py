"""Host round buffers that are refilled after ``jax.device_put``: the one
home of the reuse rule, for the combiner's pool and the sweep's ring.

A recycled buffer's pages stay mapped (a fresh 16-32 MiB allocation a round
page-faults its way in), but refilling one is sound only under two
conditions, and both are stated here:

1. **device_put copied it.** An ALIASED device array references the host
   memory forever, so the next refill corrupts blocks still held, and no
   wait can help. Accelerators copy host to device. PJRT's CPU client
   zero-copy-aliases a host buffer whose data pointer is 64-byte aligned
   and copies any other, so :func:`alloc` hands the CPU backend buffers at
   ``ptr % 64 == 4``, and :func:`may_recycle` probes that exact allocation
   once per backend: where a jaxlib aliases anyway, nothing is recycled.
2. **The transfer out of it completed**, on every backend. The CPU client
   copies by completion, not at dispatch, and an accelerator may read the
   host buffer until the device array is ready: the owner calls
   ``jax.block_until_ready`` on everything it shipped out of a buffer
   before it refills it (the combiner's ``combiner.release_wait``, the
   sweep's ``sweep.gate`` and ``sweep.drain``).
"""

from __future__ import annotations

import logging

import jax
import numpy as np

logger = logging.getLogger(__name__)

#: platform -> the probe's verdict
_recyclable: dict[str, bool] = {}


def _platform(device) -> str:
    return getattr(device, "platform", "cpu")


def alloc(device, nbytes: int) -> np.ndarray:
    """A uint8 host buffer to ``device_put`` to ``device`` from. On the CPU
    backend its data pointer sits at ``ptr % 64 == 4``; slices at multiples
    of 64 bytes (a 512-byte row, a block) stay misaligned too."""
    if _platform(device) != "cpu":
        return np.empty(nbytes, dtype=np.uint8)
    raw = np.empty(nbytes + 68, dtype=np.uint8)
    off = (4 - raw.ctypes.data) % 64
    return raw[off : off + nbytes]


def may_recycle(device) -> bool:
    """Whether a buffer of :func:`alloc` may be refilled at all once its
    transfers completed (rule 1). False means fresh buffers every round."""
    platform = _platform(device)
    if platform != "cpu":
        return True
    verdict = _recyclable.get(platform)
    if verdict is None:
        verdict = _recyclable[platform] = _device_put_copies(device)
    return verdict


def _device_put_copies(device) -> bool:
    """device_put a buffer of ``alloc``, overwrite it, and see whether the
    device array kept its values. A probe that fails counts as aliasing."""
    try:
        buf = alloc(device, 256 << 10)  # a real round's size
        buf[:] = 7
        dev = jax.device_put(buf, device)
        jax.block_until_ready(dev)
        buf[:] = 0
        got = np.asarray(dev)
        return bool(got[0] == 7 and got[-1] == 7)
    except Exception:
        logger.debug("host-buffer aliasing probe failed; buffers will not "
                     "be recycled", exc_info=True)
        return False
