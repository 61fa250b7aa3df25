"""Milliseconds a record's fetch runs on the source's client loop, from its
first step there to the read's return (its ranged ``ReadBlock`` calls, the
engine's read inside them, and the loop's other work between): the
source's ``stats()`` ``loop_ns / records``, delta over the traced part of
the window."""


def read(win):
    loop_ns = win.trace_delta("infeed.loop_ns")
    records = win.trace_delta("infeed.records")
    if loop_ns is None or not records:
        return None
    return loop_ns / records / 1e6
