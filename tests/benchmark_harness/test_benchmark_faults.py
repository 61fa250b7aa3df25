"""The comparison that decides ``correct`` shown to fail, at a tiny size on
the CPU (see benchmark_tiny.py): the control of every cell comes out NOT
correct, and so does a run with each fault the timed path can have planted
underneath it. The served deployment runs in-process here
(``benchmark_tiny.InprocChain``); test_benchmark_cells.py starts the real one."""

from __future__ import annotations

import pytest
from benchmark_tiny import CELLS, make_tiny_root, run, stub_chip

from benchmarks import harness, sabotage


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory, ha_bringup="inproc_chain")


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    stub_chip(monkeypatch)


@pytest.mark.parametrize("cell", sorted(CELLS))
async def test_control_comes_out_not_correct(tiny_root, cell):
    kind = harness.load_cell(cell, tiny_root)["mix"]["kind"]
    line = await run(tiny_root, cell, fault=sabotage.CONTROLS[kind]())
    assert line["correct"] is False
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    want = "replica_bytes_wrong" if kind == "closed_loop_put" \
        else "device_bytes_wrong"
    assert want in failing, line["checks"]


FAULTS = [
    ("ha_remote_read", sabotage.AnswerAltered, "device_bytes_wrong"),
    ("ha_remote_read", sabotage.HalfLeftOut, "ops_failed"),
    ("ha_colocated_sweep", sabotage.AnswerAltered, "device_bytes_wrong"),
    ("ha_colocated_sweep", sabotage.HalfLeftOut, "ops_failed"),
    ("ha_stress_write", sabotage.AnswerAltered, "replica_bytes_wrong"),
    ("ha_stress_write", sabotage.StateUnchanged, "meta_missing"),
    ("ici_ring_write", sabotage.AnswerAltered, "meta_crc_wrong"),
    ("ici_ring_write", sabotage.StateUnchanged, "meta_missing"),
    ("ici_ring_write", sabotage.ExchangeLeftOut, "replica_bytes_wrong"),
]


@pytest.mark.parametrize(
    "cell,fault,number", FAULTS,
    ids=[f"{c}-{f.__name__}" for c, f, _ in FAULTS])
async def test_fault_in_the_timed_path_fails_correct(tiny_root, cell, fault,
                                                      number):
    line = await run(tiny_root, cell, fault=fault())
    assert line["correct"] is False
    assert line["checks"][number]["value"] > 0, line["checks"]


@pytest.mark.parametrize("cell", ["ha_remote_read", "ha_colocated_sweep"])
async def test_block_gone_from_one_replica_is_recovered(tiny_root, cell):
    """The fall-back of a fused round is part of the timed path: it gives
    the right bytes and compiles nothing inside the window."""
    line = await run(tiny_root, cell,
                     fault=sabotage.BlockGoneFromOneReplica())
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0
