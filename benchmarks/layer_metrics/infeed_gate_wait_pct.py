"""Share of the records' fetch time spent waiting at the overload
governor's gate: ``infeed.gate_wait`` time over ``infeed.fetch`` time, whole
fetches that ended in the traced part of the window. Near 0 while Grain's
threads are no more than the gate admits; it grows when the governor has
narrowed the gate."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.share_of_parents_pct(
        win, "infeed.fetch", "infeed.gate_wait")
