"""Share of a sweep call spent waiting for transfers to leave the ring's
buffers: ``sweep.gate`` (before a slot is reused) + ``sweep.drain`` (at the
end) time over ``hbm.sweep`` time, whole calls that ended in the traced part
of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.share_of_parents_pct(
        win, "hbm.sweep", "sweep.gate", "sweep.drain")
