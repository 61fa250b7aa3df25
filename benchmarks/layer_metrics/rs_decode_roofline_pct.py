"""Share of the HBM roofline the on-device Reed-Solomon reconstruction
reaches: the least time the chip could take for the blocks decoded while
the trace ran (``rs_work.decode_min_seconds``: k survivor shards read, the
missing data shards written) over the device time of every execution of the
decode program in the trace: whole module events by the program's pinned
name, so the number reads the same work whatever implements the decode
later. HBM-bound."""

#: module name the decode program appears under in the trace
PROGRAM = "jit_rs_decode_block"


def read(win):
    from benchmarks import rs_work, trace_reduce

    degraded = win.trace_delta("hbm.ec_degraded_blocks")
    missing = win.trace_delta("hbm.ec_missing_data_shards")
    if not degraded or not missing or win.trace is None:
        return None
    seconds = sum(secs for name, (_n, secs) in trace_reduce.program_times(
        win.trace, win.lo_ns, win.hi_ns).items()
        if name.startswith(PROGRAM))
    if seconds <= 0:
        return None
    cfg = win.ctx.cfg
    least = rs_work.decode_min_seconds(degraded, missing, cfg["block_bytes"],
                                       cfg["ec"][0], win.peaks)
    return 100.0 * least / seconds
