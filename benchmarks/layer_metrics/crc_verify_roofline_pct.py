"""Share of the HBM roofline the on-device verification reaches: the least
time the chip could take for the bytes verified while the trace ran (each
byte read once from HBM: ``peaks.crc_verify_min_seconds``) over the device
time of every execution of the verification programs in the trace — whole
programs, not one custom call, so the number reads the same work whatever
implements the verify later. HBM-bound."""

#: module names the verification programs appear under in the trace
PROGRAMS = ("jit_batch_block_crc_device", "jit_block_crc_device")


def read(win):
    from benchmarks import trace_reduce

    blocks = win.trace_delta("combiner.blocks")
    seconds = sum(secs for name, (_n, secs) in trace_reduce.program_times(
        win.trace, win.lo_ns, win.hi_ns).items()
        if name.startswith(PROGRAMS))
    if not blocks or seconds <= 0:
        return None
    least = blocks * win.ctx.cfg["block_bytes"] \
        / win.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
