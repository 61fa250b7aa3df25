"""Milliseconds one restore under another layout spends planning on the
host: the ``ckpt.plan`` span (manifest + target -> blocks to read onto each
chip, blocks to move chip to chip, each chip's assembly) under each
``ckpt.restore`` span that ended in the traced part of the window, mean
over restores. Restores that plan nothing (no target) are not counted."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    restores, plans = program_spans.with_children(win, "ckpt.restore",
                                                  "ckpt.plan")
    planned = {p.parent_id for p in plans}
    if not planned:
        return None
    return program_spans.ms(plans) / len(
        [r for r in restores if r.span_id in planned])
