"""Milliseconds the fan-in of one erasure-coded block's shards takes: mean
``ec.fetch_shards`` span (every slot asked, the survivors' answers in) over
the traced part of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "ec.fetch_shards")
