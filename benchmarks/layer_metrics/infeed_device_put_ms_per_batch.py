"""Milliseconds one batch takes from host memory into HBM: mean
``infeed.device_put`` span (``jax.device_put`` of the batch and the wait for
the transfer, in ``device_iterator``'s own thread) over the traced part of
the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "infeed.device_put")
