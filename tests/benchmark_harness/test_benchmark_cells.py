"""Every cell rehearsed end to end at a tiny size on the CPU: the last
line's shape with ``--trace 0`` and ``--trace 1`` (see benchmark_tiny.py)."""

from __future__ import annotations

import pytest
from benchmark_tiny import CELLS, LINE_KEYS, make_tiny_root, run, stub_chip


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory)


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    stub_chip(monkeypatch)


@pytest.mark.parametrize("cell", sorted(CELLS))
async def test_last_line_shape_end_to_end(tiny_root, cell):
    line = await run(tiny_root, cell)
    assert LINE_KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert len(line["metrics"]) >= 2
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == CELLS[cell][2]
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert line["window"]["compared"]["device_blocks"] > 0
    assert line["window"]["compared"]["replica_reads"] > 0


@pytest.mark.parametrize("cell", ["ha_remote_read", "ici_ring_write"])
async def test_last_line_shape_traced(tiny_root, cell):
    line = await run(tiny_root, cell, trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert "setup_s" not in line["metrics"] and line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # No TPU plane in a CPU trace: the readers of the device trace find
    # nothing and are left out, never reported as 0.
    assert "crc_verify_roofline_pct" not in line["metrics"]
    assert "ici_round_ms" not in line["metrics"]
