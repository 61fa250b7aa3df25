"""Milliseconds a ``ReadBlocks`` frame takes the chunkserver engine before
its response header is built (the size estimate and every slot's pread):
``read_stages`` ``rbs_read_ns / rbs_frames``, delta over the window, all
chunkservers. The engine's part of ``readblocks_header_ms``; the rest of
that wait is the wire and the client's loop."""

from benchmarks import engine_read_stages


def setup(ctx):
    engine_read_stages.attach(ctx)


def read(win):
    return engine_read_stages.ms_per(win, ("rbs_read_ns",), "rbs_frames")
