"""Milliseconds the read combiner's upload stage works on one round:
``combiner.device_put`` + ``combiner.crc_dispatch`` +
``combiner.release_wait`` time over rounds, in the traced part of the
window (its wait for the read stage, ``combiner.upload_wait``, is not in)."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.ms_per_round(
        win, "combiner.device_put", "combiner.crc_dispatch",
        "combiner.release_wait")
