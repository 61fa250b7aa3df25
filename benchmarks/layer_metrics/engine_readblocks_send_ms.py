"""Milliseconds the chunkserver engine spends sending a ``ReadBlocks``
frame (its ``send_frame``, paced by the client's receive): ``read_stages``
``rbs_send_ns / rbs_frames``, delta over the window, all chunkservers. To
be read beside ``readblocks_payload_ms``."""

from benchmarks import engine_read_stages


def setup(ctx):
    engine_read_stages.attach(ctx)


def read(win):
    return engine_read_stages.ms_per(win, ("rbs_send_ns",), "rbs_frames")
