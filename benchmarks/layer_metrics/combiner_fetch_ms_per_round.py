"""Milliseconds the read combiner's read stage spends filling one round
(one ``ReadBlocks`` frame from the round's origin, or one local pread):
``combiner.fetch`` time over rounds, in the traced part of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.ms_per_round(win, "combiner.fetch")
