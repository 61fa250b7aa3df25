"""Share of a sweep call spent waiting for the native pump's next round
(pread + host CRC): ``sweep.pump_wait`` time over ``hbm.sweep`` time, whole
calls that ended in the traced part of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.share_of_parents_pct(
        win, "hbm.sweep", "sweep.pump_wait")
