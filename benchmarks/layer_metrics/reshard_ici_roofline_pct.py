"""Share of the ICI roofline the chip-to-chip move of a restore under
another layout reaches: the least time a chip could take for its part of
the move (``reshard_work.ici_min_seconds``: the bytes held by more than one
chip beyond their first copy, spread over the chips, at one chip's ICI
rate) for each move that ended in the traced part (one ``ckpt.redistribute``
span a restore), over the device time of every execution of the move
program in the trace, a chip's mean: whole module events by the pinned
name. ICI-bound."""

from benchmarks import program_spans

#: module name the move appears under in the trace
PROGRAM = "jit_ckpt_reshard_ici"


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    from benchmarks import reshard_work, trace_reduce

    moves = program_spans.ended_in_part(win, "ckpt.redistribute")
    if not moves or win.trace is None:
        return None
    seconds = sum(secs for name, (_n, secs) in trace_reduce.program_times(
        win.trace, win.lo_ns, win.hi_ns).items() if name.startswith(PROGRAM))
    if seconds <= 0:
        return None
    cfg = win.ctx.cfg
    least = len(moves) * reshard_work.ici_min_seconds(cfg, win.peaks)
    return 100.0 * least / (seconds / reshard_work.chips(cfg))
