"""Milliseconds the chunkserver engine spends on one ``ReadBlock``, read
and send (for a ranged read: stat, pread and the sidecar verify of the
chunks it touches, then the send): ``read_stages`` ``(rb_read_ns +
rb_send_ns) / rb_calls``, delta over the window, all chunkservers. The
engine's whole part of a ranged read's wait on the client."""

from benchmarks import engine_read_stages


def setup(ctx):
    engine_read_stages.attach(ctx)


def read(win):
    return engine_read_stages.ms_per(win, ("rb_read_ns", "rb_send_ns"),
                                     "rb_calls")
