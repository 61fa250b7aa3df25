"""Share of the erasure-coded blocks read that had lost a data shard and
were reconstructed on the device: ``HbmReader.ec_degraded_blocks /
ec_blocks``, delta over the traced part of the window."""


def read(win):
    blocks = win.trace_delta("hbm.ec_blocks")
    degraded = win.trace_delta("hbm.ec_degraded_blocks")
    if not blocks or degraded is None:
        return None
    return 100.0 * degraded / blocks
