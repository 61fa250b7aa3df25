"""The program's stage spans in a traced run, at a tiny size on the CPU (see
benchmark_tiny.py): each read cell reports the per-layer metrics that read
them, ``idle_gaps`` names the program's stages, a write cell finds none and
still passes, and a run with ``--trace 0`` leaves tracing off. Counts and
presence only: a CPU run gives no times."""

from __future__ import annotations

import json
from types import SimpleNamespace

import pytest
from benchmark_tiny import REPO, make_tiny_root, run, stub_chip

from benchmarks import harness, program_spans, trace_reduce
from benchmarks.layer_metrics import read_queue_wait_ms
from tpudfs.common import native, telemetry

NEW = {m["name"]: m["workloads"]
       for m in json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]
       if m["source"] == "program_span"}
REMOTE = {n for n, cells in NEW.items() if cells == ["ha_remote_read"]}
SWEEP = {n for n, cells in NEW.items() if cells == ["ha_colocated_sweep"]}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory)


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    stub_chip(monkeypatch)


@pytest.fixture
def ticking_device(monkeypatch):
    """A CPU trace has no device plane, so the harness has no idle gap to
    give away. Stand one in: an op of 1 us every 2 ms of the traced part.
    The rule that names each gap, and the rows it names them from, stay
    the harness's own."""
    real = trace_reduce.idle_by_host_activity

    def with_ticks(trace, lo_ns, hi_ns, spans, n=10):
        ticks, t = [], lo_ns
        while t < hi_ns:
            ticks.append((t, t + 1e3, "%tick"))
            t += 2e6
        device = trace_reduce.DeviceTrace("/device:TPU:0", ops=ticks)
        return real(trace_reduce.Trace([device], trace.mark_ns),
                    lo_ns, hi_ns, spans, n=100)

    monkeypatch.setattr(trace_reduce, "idle_by_host_activity", with_ticks)


def _program_names(line: dict) -> set[str]:
    return {name for name, _s in line["breakdown"]["idle_gaps"]
            if name.split(".")[0] in ("client", "hbm", "blockport",
                                      "combiner", "sweep")}


def test_the_nine_metrics_and_their_cells():
    assert len(REMOTE) == 6 and len(SWEEP) == 3 and len(NEW) == 9


async def test_traced_remote_read_reports_its_stage_metrics(
        tiny_root, ticking_device):
    line = await run(tiny_root, "ha_remote_read", trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert REMOTE <= set(line["metrics"]), sorted(line["metrics"])
    assert all(line["metrics"][n]["value"] > 0 for n in REMOTE)
    assert not SWEEP & set(line["metrics"])
    assert len(_program_names(line)) >= 3, line["breakdown"]["idle_gaps"]
    assert not telemetry._enabled  # the first reader turned tracing off


async def test_traced_sweep_reports_its_stage_shares(
        tiny_root, ticking_device):
    lib = native.get_lib()
    if lib is None or not hasattr(lib, "tpudfs_sweep_start"):
        pytest.skip("the native library with the sweep pump is not loaded: "
                    "the sweep takes the per-block path and has no stages")
    line = await run(tiny_root, "ha_colocated_sweep", trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    if not line["window"]["counters"].get("sweep.blocks"):
        pytest.skip("the pump served no block here (device_put aliases "
                    "host buffers on this backend)")
    assert SWEEP <= set(line["metrics"]), sorted(line["metrics"])
    shares = [line["metrics"][n]["value"] for n in SWEEP]
    assert all(v > 0 for v in shares) and sum(shares) <= 100.0
    assert not REMOTE & set(line["metrics"])
    assert len(_program_names(line)) >= 3, line["breakdown"]["idle_gaps"]


async def test_a_write_cell_finds_none_and_an_untraced_run_reads_no_clock(
        tiny_root, monkeypatch):
    line = await run(tiny_root, "ha_stress_write", trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert not set(NEW) & set(line["metrics"]) and line["metrics"]
    assert not telemetry._enabled

    def no_clock():
        raise AssertionError("a site read the clock with tracing off")

    monkeypatch.setattr(telemetry, "time", SimpleNamespace(time_ns=no_clock))
    line = await run(tiny_root, "ha_remote_read")
    assert line["correct"] is True and line["failed"] == 0
    assert telemetry.drain() == [] and not telemetry._enabled
    assert "program_spans.wall_ns" not in line["window"]["counters"]


def test_a_program_without_the_facility_is_left_alone(monkeypatch):
    """The parent of the PR that brought the spans: ``attach`` does
    nothing, a reader finds nothing, nobody raises."""
    monkeypatch.delattr(telemetry, "enable")
    ctx = harness.Context(
        cell={}, cfg={}, mix={}, seed=1, seconds=1.0, trace=True, devices=[],
        bringup=None, rpc=None, spans=harness.Spans(), workdir=REPO,
        rng=None, local_counters=dict)
    assert program_spans.attach(ctx) is None and ctx.local_counters() == {}
    win = harness.Window(ctx, [], 0.0, 1.0, {}, {}, {}, {}, None, 0, 1, {})
    assert read_queue_wait_ms.read(win) is None
