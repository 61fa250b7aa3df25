"""Milliseconds a block waits in the read combiner before a round takes
it: mean ``combiner.queued`` span (staged by its reader -> taken by the
read stage) over the traced part of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "combiner.queued")
