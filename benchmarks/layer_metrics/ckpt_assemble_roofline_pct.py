"""Share of the HBM roofline the assembly of restored shards reaches: the
least time the chip could take for the shards assembled while the trace ran
(``ckpt_work.assemble_min_seconds``: each shard's aligned payload read once
and written once) over the device time of every execution of the assembly
programs in the trace: whole module events by the pinned name (the gather
of rounds into the buffer and the cut into tensors both start with it), so
the number reads the same work whatever implements the assembly later.
HBM-bound."""

from benchmarks import program_spans

#: module names the assembly programs appear under in the trace
PROGRAM = "jit_ckpt_assemble"


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    from benchmarks import ckpt_work, trace_reduce

    assembled = program_spans.ended_in_part(win, "ckpt.assemble")
    if not assembled or win.trace is None:
        return None
    seconds = sum(secs for name, (_n, secs) in trace_reduce.program_times(
        win.trace, win.lo_ns, win.hi_ns).items()
        if name.startswith(PROGRAM))
    if seconds <= 0:
        return None
    least = ckpt_work.assemble_min_seconds(
        [r.attrs["bytes"] for r in assembled], win.peaks)
    return 100.0 * least / seconds
