"""Block reads a record takes: the source's ``stats()`` ``range_reads /
records``, delta over the traced part of the window. ``range_reads`` is
counted where a read is issued (every ``ReadBlock`` the source's client
sent, hedges and fallbacks included, and every block read off a colocated
replica's disk), so it reads 1.0 when no record straddles a block and
nothing was retried, and moves only when fewer or more reads are sent."""


def read(win):
    reads = win.trace_delta("infeed.range_reads")
    records = win.trace_delta("infeed.records")
    if reads is None or not records:
        return None
    return reads / records
