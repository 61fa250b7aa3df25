"""Milliseconds the client takes to receive one ``ReadBlocks`` payload
into the round buffer (the wire and the event loop's copies): mean
``blockport.recv_payload`` span in the traced part of the window."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    return program_spans.mean_ms(win, "blockport.recv_payload",
                                 method="ReadBlocks")
