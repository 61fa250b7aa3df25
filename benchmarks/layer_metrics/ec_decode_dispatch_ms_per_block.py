"""Milliseconds the host takes to dispatch one block's reconstruction
(the decode program is asynchronous: this is the launch, not the device
time): mean ``ec.decode_dispatch`` span over the traced part of the
window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "ec.decode_dispatch")
