"""Milliseconds from a ``ReadBlocks`` request written to its response
header parsed, on the client: mean ``blockport.wait_header`` span. The
engine sends nothing before it has read every block of the frame, so this
is the engine's share of a round (queueing on its side included)."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "blockport.wait_header",
                                 method="ReadBlocks")
