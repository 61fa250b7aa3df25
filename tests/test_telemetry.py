"""The span facility of ``tpudfs.common.telemetry``: off means off,
records nest and carry the request id, the buffer is bounded, and the
operator's switch (``dfs_cli --trace-out``) writes a trace Perfetto loads."""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from tpudfs.common import telemetry

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def tracing():
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.drain()


def _no_clock():
    raise AssertionError("the clock was read with tracing off")


def test_off_returns_the_shared_noop_and_reads_no_clock(monkeypatch):
    monkeypatch.setattr(telemetry, "time", SimpleNamespace(time_ns=_no_clock))
    first = telemetry.span("a")
    with first as entered, telemetry.span("b", blocks=3, request=None) as b:
        b.set(bytes=1)
    queued = telemetry.span("c")
    queued.end(round=1)
    assert first is entered is b is queued
    assert telemetry.drain() == [] and telemetry.dropped() == 0


def test_a_span_still_open_when_tracing_stops_is_not_recorded():
    telemetry.enable()
    late = telemetry.span("late")
    telemetry.disable()
    late.end()
    assert telemetry.drain() == []


async def test_records_nest_and_share_the_request_across_await_and_thread(
        tracing):
    def in_thread():
        with telemetry.span("thread", bytes=7):
            return telemetry._request_id.get()

    async def child():
        with telemetry.span("task"):
            await asyncio.sleep(0)

    with telemetry.span("root", blocks=2) as root:
        rid = telemetry.current_request_id()
        await asyncio.gather(child(), child())
        assert await asyncio.to_thread(in_thread) == rid
    assert telemetry._request_id.get() is None  # a root's request ends with it
    by_name: dict = {}
    for r in telemetry.drain():
        by_name.setdefault(r.name, []).append(r)
    (top,) = by_name["root"]
    assert top.parent_id is None and top.request_id == rid
    assert top.span_id == root.span_id and top.attrs == {"blocks": 2}
    inner = by_name["task"] + by_name["thread"]
    assert len(inner) == 3
    assert all(r.parent_id == top.span_id and r.request_id == rid
               and top.start_ns <= r.start_ns <= r.end_ns <= top.end_ns
               for r in inner)
    assert by_name["thread"][0].attrs == {"bytes": 7}


async def test_a_root_span_keeps_the_request_its_context_already_has(tracing):
    token = telemetry.set_request_id("feedfacefeedface")
    try:
        with telemetry.span("op"):
            assert telemetry.current_request_id() == "feedfacefeedface"
    finally:
        telemetry._request_id.reset(token)
    (record,) = telemetry.drain()
    assert record.request_id == "feedfacefeedface"


async def test_a_background_stage_detaches_from_its_starters_context(
        tracing):
    """A task started inside a request inherits that request's context; a
    stage that serves every request says ``request=None`` and neither the
    request nor the enclosing span leaks into it or its children."""
    async def stage():
        with telemetry.span("stage", request=None, round=1):
            with telemetry.span("inside"):
                assert telemetry._request_id.get() is None

    with telemetry.span("reader"):
        queued = telemetry.span("queued")
        task = asyncio.create_task(stage())
        await task
        queued.end(round=1)
        telemetry.span("hung", request=None, parent=queued).end()
    records = {r.name: r for r in telemetry.drain()}
    assert records["hung"].parent_id == records["queued"].span_id
    assert records["hung"].request_id is None
    assert records["stage"].request_id is None
    assert records["stage"].parent_id is None
    assert records["inside"].request_id is None
    assert records["inside"].parent_id == records["stage"].span_id
    assert records["queued"].parent_id == records["reader"].span_id
    assert records["queued"].request_id == records["reader"].request_id
    assert records["queued"].attrs == {"round": 1}


def test_the_buffer_is_bounded_and_counts_what_it_drops(
        monkeypatch, tracing):
    monkeypatch.setattr(telemetry, "BUFFER_CAP", 5)
    for i in range(8):
        with telemetry.span("s", i=i):
            pass
    assert telemetry.dropped() == 3
    assert [r.attrs["i"] for r in telemetry.drain()] == [0, 1, 2, 3, 4]
    assert telemetry.drain() == []
    telemetry.enable()  # a new recording starts clean
    assert telemetry.dropped() == 0


def test_a_sink_takes_the_records_instead_of_the_buffer():
    seen: list = []
    telemetry.enable(sink=seen.append)
    try:
        with telemetry.span("s"):
            pass
    finally:
        telemetry.disable()
    assert [r.name for r in seen] == ["s"] and telemetry.drain() == []


def test_chrome_trace_keeps_every_track_properly_nested():
    def rec(name, start, end, sid, parent=None):
        return telemetry.SpanRecord(name, sid, parent, "r", start, end, {})

    records = [rec("read", 0, 100, 1), rec("meta", 5, 20, 2, 1),
               rec("queued_a", 20, 60, 3, 1), rec("queued_b", 25, 70, 4, 1),
               rec("fetch", 30, 90, 5)]
    events = telemetry.chrome_trace(records)["traceEvents"]
    assert {e["ph"] for e in events} == {"X"} and len(events) == 5
    assert events[0]["ts"] == 0 and events[0]["dur"] == 0.1  # microseconds
    lanes: dict = {}
    for e in events:
        lanes.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    for spans in lanes.values():
        for i, (a0, a1) in enumerate(spans):
            for b0, b1 in spans[i + 1:]:
                assert b0 >= a1 or b1 <= a1  # apart, or one inside the other
    assert len(lanes) == 3  # queued_b and fetch each cross a span before


def test_telemetry_imports_without_jax():
    """Server processes import it; they must never map JAX."""
    code = ("import sys; import tpudfs.common.telemetry as t; "
            "t.enable(); t.span('x').end(); "
            "assert len(t.drain()) == 1; assert 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   timeout=120)


async def test_cli_trace_out_writes_a_chrome_trace(tmp_path):
    """The operator's switch: one ``get`` through a live cluster, traced."""
    from tpudfs.testing.inproc import InprocCluster

    cluster = InprocCluster(str(tmp_path / "cluster"))
    await cluster.start()
    try:
        await cluster.ready()
        client = cluster.client(block_size=64 * 1024)
        data = os.urandom(3 * 64 * 1024)
        await client.create_file("/traced/f", data)
        out, got = tmp_path / "trace.json", tmp_path / "got.bin"
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "tpudfs.client.cli",
            "--masters", ",".join(cluster.masters), "--trace-out", str(out),
            "get", "/traced/f", str(got), cwd=REPO,
            env={**os.environ, "TPUDFS_LOCAL_READS": "0"})
        assert await asyncio.wait_for(proc.wait(), 120) == 0
        assert got.read_bytes() == data
    finally:
        await cluster.stop()
    events = json.loads(out.read_text())["traceEvents"]
    names = {e["name"] for e in events}
    assert "client.get_file_info" in names
    assert {"blockport.wait_header", "blockport.recv_payload"} <= names
    payload = sum(e["args"]["bytes"] for e in events
                  if e["name"] == "blockport.recv_payload")
    assert payload == len(data)
