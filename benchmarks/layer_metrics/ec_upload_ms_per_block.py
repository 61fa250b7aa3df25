"""Milliseconds the host spends bringing one degraded block's survivors to
the device: ``ec.assemble`` (stacking the k shards as words) +
``ec.device_put`` time over the degraded blocks they served, in the traced
part of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    stacked = program_spans.ended_in_part(win, "ec.assemble", degraded=True)
    put = program_spans.ended_in_part(win, "ec.device_put", degraded=True)
    if not stacked:
        return None
    return (program_spans.ms(stacked) + program_spans.ms(put)) / len(stacked)
