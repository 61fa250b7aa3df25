"""Milliseconds a record's fetch waits between its hand-off on a Grain
prefetch thread and its first step on the source's client loop: the
source's ``stats()`` ``hop_ns / records``, delta over the traced part of
the window (a fetch's hop back, with the wait for the GIL, is the rest of
``infeed_fetch_ms_per_record`` after the gate, this and
``infeed_on_loop_ms_per_record``)."""


def read(win):
    hop_ns = win.trace_delta("infeed.hop_ns")
    records = win.trace_delta("infeed.records")
    if hop_ns is None or not records:
        return None
    return hop_ns / records / 1e6
