"""Milliseconds one file read spends waiting for its metadata: the
``client.get_file_info`` spans under each ``hbm.read_file`` span that ended
in the traced part of the window, summed per read, mean over reads."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    reads, metas = program_spans.with_children(
        win, "hbm.read_file", "client.get_file_info")
    return program_spans.ms(metas) / len(reads) if reads else None
