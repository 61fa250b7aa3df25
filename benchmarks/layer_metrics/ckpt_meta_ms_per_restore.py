"""Milliseconds one restore spends on metadata before and beside its
blocks: ``ckpt.latest_step`` (the manifest listing), ``ckpt.manifest`` (the
manifest's own read) and ``ckpt.combined_crc`` (one a shard) under each
``ckpt.restore`` span that ended in the traced part of the window, summed
per restore, mean over restores."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    restores, metas = program_spans.with_children(
        win, "ckpt.restore", "ckpt.latest_step", "ckpt.manifest",
        "ckpt.combined_crc")
    return program_spans.ms(metas) / len(restores) if restores else None
