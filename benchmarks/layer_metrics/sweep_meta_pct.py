"""Share of a sweep call spent before the first byte moves: the metadata
fan-out (``sweep.metadata``) + eligibility and path resolution
(``sweep.resolve``) over ``hbm.sweep`` time, whole calls that ended in the
traced part of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.share_of_parents_pct(
        win, "hbm.sweep", "sweep.metadata", "sweep.resolve")
