"""The program's own stage spans (``tpudfs.common.telemetry``) in a traced
run: ``attach`` turns the program's tracing on from a per-layer reader's
``setup`` hook (the harness runs those only with ``--trace 1``, so the
timed run has tracing off), keeps every record for the readers and lays
each onto the harness's own span rows, so ``breakdown.idle_gaps`` names
the program's stages. The first ``read`` of the run turns tracing off.

A program without the facility (the parent of the PR that brought it) is
left alone: ``attach`` returns ``None`` and every reader finds nothing.
"""

from __future__ import annotations

import time

from benchmarks.spans import CURRENT_OP

KEY = "program_spans"
#: wall-clock ns riding on the counters the harness takes at the window's
#: and the traced part's ends: the one place a reader learns where the
#: traced part lies on the clock the spans use
WALL_NS = "program_spans.wall_ns"


class ProgramSpans:
    def __init__(self, ctx, telemetry):
        self.telemetry = telemetry
        self.records: list = []
        self.rows = ctx.spans.rows
        #: the traced part of the window, wall-clock ns; set by the first
        #: read, which also turns the program's tracing off
        self.bounds: tuple[int, int] | None = None

    def sink(self, record) -> None:
        self.records.append(record)
        op = CURRENT_OP.get() if record.request_id is not None else None
        self.rows.append((record.name, op, record.start_ns, record.end_ns))


def attach(ctx) -> ProgramSpans | None:
    """Idempotent per run; every reader of program spans calls it from
    ``setup(ctx)``."""
    run = ctx.setup_readings.get(KEY)
    if run is not None:
        return run
    from tpudfs.common import telemetry

    if not hasattr(telemetry, "enable"):
        return None
    run = ctx.setup_readings[KEY] = ProgramSpans(ctx, telemetry)
    counters = ctx.local_counters
    ctx.local_counters = lambda: {**counters(), WALL_NS: time.time_ns()}
    telemetry.enable(sink=run.sink)
    return run


def traced_part(win) -> tuple[list, int, int] | None:
    """(every record of the run, start, end) with the traced part of the
    window in wall-clock ns: from the profiler's start to its stop or the
    window's close, whichever came first (the traced part of a sweep or a
    write cell runs on into the check, which is not the window's)."""
    run = win.ctx.setup_readings.get(KEY)
    if run is None or WALL_NS not in win.trace_before \
            or WALL_NS not in win.trace_after:
        return None
    if run.bounds is None:
        run.telemetry.disable()
        t1_ns = time.time_ns() - int((time.perf_counter() - win.t1) * 1e9)
        run.bounds = (win.trace_before[WALL_NS],
                      min(win.trace_after[WALL_NS], t1_ns))
    return run.records, *run.bounds


def ended_in_part(win, name: str, **attrs) -> list:
    """The ``name`` spans that ended inside the traced part of the window
    and carry ``attrs``."""
    part = traced_part(win)
    if part is None:
        return []
    records, lo, hi = part
    return [r for r in records if r.name == name and lo <= r.end_ns <= hi
            and all(r.attrs.get(k) == v for k, v in attrs.items())]


def ms(records: list) -> float:
    return sum(r.end_ns - r.start_ns for r in records) / 1e6


def mean_ms(win, name: str, **attrs) -> float | None:
    found = ended_in_part(win, name, **attrs)
    return ms(found) / len(found) if found else None


def ms_per_round(win, *names: str) -> float | None:
    """Time in the ``names`` stages of the read combiner over the rounds
    they served (sub-rounds of one round share its number)."""
    found = [r for name in names for r in ended_in_part(win, name)]
    rounds = {r.attrs.get("round") for r in found}
    return ms(found) / len(rounds) if found else None


def with_children(win, parent: str, *names: str) -> tuple[list, list]:
    """The ``parent`` spans that ended inside the traced part, and their
    ``names`` children whenever those ended: whole calls, so both cover
    the same work."""
    parents = ended_in_part(win, parent)
    ids = {r.span_id for r in parents}
    children = [r for r in traced_part(win)[0]
                if r.parent_id in ids and r.name in names] if ids else []
    return parents, children


def share_of_parents_pct(win, parent: str, *names: str) -> float | None:
    parents, children = with_children(win, parent, *names)
    whole = ms(parents)
    return 100.0 * ms(children) / whole if whole > 0 else None
