"""Share of the HBM roofline the assembly of a restore under another layout
reaches: the least time each chip could take for the assemblies that ended
in the traced part (one ``ckpt.assemble`` span a chip a restore, attr
``device``; ``reshard_work.assemble_min_seconds``: the chip's aligned shards
read once and written once) over the device time of every execution of the
assembly programs in the trace, summed over chips: whole module events by
the pinned name. HBM-bound."""

from benchmarks import program_spans

#: module name the assembly appears under in the trace
PROGRAM = "jit_ckpt_reshard_assemble"


def setup(ctx):
    program_spans.attach(ctx)


def read(win):
    from benchmarks import reshard_work, trace_reduce

    assembled = [r for r in program_spans.ended_in_part(win, "ckpt.assemble")
                 if "device" in r.attrs]
    if not assembled or win.trace is None:
        return None
    seconds = sum(secs for name, (_n, secs) in trace_reduce.program_times(
        win.trace, win.lo_ns, win.hi_ns).items() if name.startswith(PROGRAM))
    if seconds <= 0:
        return None
    chip_of = {d.id: i for i, d in enumerate(win.ctx.devices)}
    least = sum(reshard_work.assemble_min_seconds(
        win.ctx.cfg, chip_of.get(r.attrs["device"], 0), win.peaks)
        for r in assembled)
    return 100.0 * least / seconds
