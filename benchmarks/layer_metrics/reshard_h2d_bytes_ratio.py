"""Bytes a restore under another layout uploaded host to device over the
saved bytes its chips needed: ``CheckpointManager.stats``
``reshard_h2d_bytes / reshard_unique_bytes``, delta over the traced part of
the window. 1.00 is the design point (every needed block crosses the bus
once); a block that several chips need and that is uploaded to each, or a
block read again after a failed check, reads above it."""


def read(win):
    h2d = win.trace_delta("ckpt.reshard_h2d_bytes")
    unique = win.trace_delta("ckpt.reshard_unique_bytes")
    if not h2d or not unique:
        return None
    return h2d / unique
