"""Milliseconds the host spends launching the assembly of one restore's
shards: the ``ckpt.assemble`` spans (buffer, one gather a round, the
shard's assembly program; any host bounce) under each ``ckpt.restore`` span
that ended in the traced part of the window, summed per restore, mean over
restores. The launch and the host's work, not the device time."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    restores, launches = program_spans.with_children(
        win, "ckpt.restore", "ckpt.assemble")
    return program_spans.ms(launches) / len(restores) if restores else None
