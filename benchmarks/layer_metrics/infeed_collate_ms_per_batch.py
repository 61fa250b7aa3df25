"""Milliseconds the host staging copy of one batch takes: mean
``infeed.collate`` span (the batch's records stacked into one array a
leaf, in the thread that feeds the device) over the traced part of the
window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "infeed.collate")
