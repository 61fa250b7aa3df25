"""Mean device time of one execution of the replicate program
(``IciReplicator``'s jitted shard_map: ppermute hops, on-device CRC verify,
psum acks) on one chip, from the ``XLA Modules`` line of the device trace."""

#: module names the replicate program appears under in the trace
PROGRAMS = ("jit_step",)


def read(win):
    from benchmarks import trace_reduce

    calls, seconds = 0, 0.0
    for name, (n, secs) in trace_reduce.program_times(
            win.trace, win.lo_ns, win.hi_ns).items():
        if name.startswith(PROGRAMS):
            calls += n
            seconds += secs
    if not calls:
        return None
    return seconds / calls * 1e3
