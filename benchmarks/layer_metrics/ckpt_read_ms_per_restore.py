"""Milliseconds from the first shard's read being issued to the last
shard's blocks being confirmed verified: first ``ckpt.read_shard`` start to
last ``ckpt.confirm`` end under each ``ckpt.restore`` span that ended in
the traced part of the window, mean over restores. The shards read side by
side, so this is the restore's read phase, not a sum."""

from benchmarks import program_spans


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    restores, stages = program_spans.with_children(
        win, "ckpt.restore", "ckpt.read_shard", "ckpt.confirm")
    spans = []
    for restore in restores:
        mine = [s for s in stages if s.parent_id == restore.span_id]
        reads = [s.start_ns for s in mine if s.name == "ckpt.read_shard"]
        confirms = [s.end_ns for s in mine if s.name == "ckpt.confirm"]
        if reads and confirms:
            spans.append(max(confirms) - min(reads))
    return sum(spans) / len(spans) / 1e6 if spans else None
