"""Milliseconds one record's fetch takes on its Grain prefetch thread: mean
``infeed.fetch`` span (the wait at the governor's gate, the hop onto the
client's loop, one ranged ``ReadBlock`` a block the record touches) over the
traced part of the window. Sixteen run side by side, so this is a record's
latency, not the pipeline's period."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "infeed.fetch")
