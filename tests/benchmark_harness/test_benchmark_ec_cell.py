"""``ec_degraded_read`` rehearsed at a tiny size on the CPU (see
benchmark_tiny.py for the stub of the chip): the last line's shape with
``--trace 0`` and ``--trace 1``, ``correct`` true, the control NOT correct,
the draw of victims, the plain reference against the program's encoder, the
bytes behind ``rs_decode_roofline_pct`` and every new reader silent where
there is nothing to read.

The nine chunkservers run in this process (``InprocNine``, as
``InprocChain`` stands in for the fault tests): "killing" one stops its
service and its heartbeats, and the masters' own liveness check (cutoff
shortened here, run by hand) drops it, as the 15 s cutoff does on the chip.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import pytest
from benchmark_tiny import LINE_KEYS, REPO, InprocChain, run, stub_chip

from benchmarks import (deployments, harness, peaks, reference, reference_rs,
                        rs_work, sabotage, trace_reduce)
from benchmarks.layer_metrics import (
    ec_decode_dispatch_ms_per_block,
    ec_degraded_block_pct,
    ec_shard_fetch_ms_per_block,
    ec_upload_ms_per_block,
    rs_decode_roofline_pct,
)
from benchmarks.traffic import closed_loop_read_hbm_degraded as degraded
from tpudfs.common import erasure
from tpudfs.master import placement

KIB = 1024
CELL = "ec_degraded_read"
CONFIG = "ec-3m9cs-rs63"
MIX = "degraded_read_16x64m"
NEW = {"ec_degraded_block_pct": ec_degraded_block_pct,
       "ec_shard_fetch_ms_per_block": ec_shard_fetch_ms_per_block,
       "ec_upload_ms_per_block": ec_upload_ms_per_block,
       "ec_decode_dispatch_ms_per_block": ec_decode_dispatch_ms_per_block,
       "rs_decode_roofline_pct": rs_decode_roofline_pct}


class InprocNine(InprocChain):
    """The nine servers of ``ec-3m9cs-rs63`` in this process."""

    name = "inproc_nine"

    def __init__(self, cfg: dict, workdir: Path):
        super().__init__(cfg, workdir)
        self.stopped: list[str] = []

    async def kill(self, addr: str) -> None:
        for cs, hb in zip(self.cluster.chunkservers, self.cluster.heartbeats):
            if cs.address == addr:
                hb.stop()
                await cs.stop()
                self.stopped.append(addr)
                return
        raise KeyError(addr)

    async def seen(self) -> int:
        """The masters' own liveness check, with a cutoff a test can wait
        for; what it queues for the healer is kept for the test."""
        for m in self.cluster.masters.values():
            m.liveness_cutoff_ms = 1200
            await m.run_liveness_check()
        return max(len(m.state.chunk_servers)
                   for m in self.cluster.masters.values())


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("tiny-ec-root")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmarks" / "configs").mkdir(parents=True)
    (root / "benchmarks" / "workloads").mkdir()
    cfg = json.loads((REPO / "benchmarks" / "configs"
                      / f"{CONFIG}.json").read_text())
    cfg.update(masters=1, block_bytes=64 * KIB, bringup=InprocNine.name,
               dataset={"files": 4, "file_bytes": 256 * KIB})
    (root / "benchmarks" / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(cfg))
    mix = json.loads((REPO / "benchmarks" / "workloads"
                      / f"{MIX}.json").read_text())
    mix.update(clients=3, check_files=2, check_replica_blocks=4,
               trace_seconds=1, noticed_wait_s=20)
    (root / "benchmarks" / "workloads" / f"{MIX}.json").write_text(
        json.dumps(mix))
    bench["configs"] = [{"name": CONFIG,
                         "file": f"benchmarks/configs/{CONFIG}.json"}]
    bench["workloads"] = [{"name": CELL, "config": CONFIG, "traffic": MIX,
                           "chips": 1}]
    bench["end_to_end"] = [{"name": n, "unit": "x"} for n in
                           ("hbm_read_GBps", "read_p95_ms", "setup_s")]
    # Every reader the benchmark has, as benchmark_tiny does for its cells.
    bench["per_layer"] = [
        {"name": f.stem, "unit": "x"} for f in sorted(
            (REPO / "benchmarks" / "layer_metrics").glob("*.py"))
        if f.stem != "__init__"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(autouse=True)
def no_chip_nine_inproc(monkeypatch):
    stub_chip(monkeypatch)
    monkeypatch.setitem(deployments.BRINGUPS, InprocNine.name, InprocNine)

    async def kill(ctx, addr):
        await ctx.bringup.kill(addr)

    async def seen(ctx):
        return await ctx.bringup.seen()

    monkeypatch.setattr(degraded, "kill_chunkserver", kill)
    monkeypatch.setattr(degraded, "chunkservers_seen", seen)


async def test_last_line_shape_end_to_end(tiny_root):
    line = await run(tiny_root, CELL)
    assert LINE_KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"hbm_read_GBps", "read_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert len(line["checks"]) == 10
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    compared = line["window"]["compared"]
    assert compared["device_blocks"] > 0 and compared["meta_blocks"] > 0
    # 4 sampled blocks, 7 surviving slots each, every one asked.
    assert compared["replica_reads"] == 4 * 7
    # Two holders of one rack's data slots are gone: every block of the
    # dataset lost a data shard, nothing was rebuilt.
    assert compared["dataset_blocks"] == 16
    assert compared["degraded_blocks"] == 16
    assert 16 <= compared["missing_data_shards"] <= 32
    assert compared["survivor_sets"] >= 1
    counters = line["window"]["counters"]
    assert counters["hbm.ec_degraded_blocks"] == counters["hbm.ec_blocks"] > 0
    assert counters["hbm.ec_missing_data_shards"] \
        >= counters["hbm.ec_degraded_blocks"]
    assert counters["hbm.ec_shard_bytes"] > 0


async def test_last_line_shape_traced(tiny_root):
    line = await run(tiny_root, CELL, trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert "setup_s" not in line["metrics"]
    spans = set(NEW) - {"rs_decode_roofline_pct"}
    assert spans <= set(line["metrics"]), sorted(line["metrics"])
    assert all(line["metrics"][n]["value"] > 0 for n in spans)
    assert line["metrics"]["ec_degraded_block_pct"]["value"] == 100.0
    # No TPU plane in a CPU trace: a share of a roofline is left out, never
    # reported as 0; the replicated cells' readers find nothing here.
    assert "rs_decode_roofline_pct" not in line["metrics"]
    assert "crc_verify_roofline_pct" not in line["metrics"]
    assert "combiner_blocks_per_round" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    names = {name for name, _s in line["breakdown"]["idle_gaps"]}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}, names


async def test_control_comes_out_not_correct(tiny_root):
    kind = harness.load_cell(CELL, tiny_root)["mix"]["kind"]
    assert kind == degraded.KIND
    line = await run(tiny_root, CELL, fault=sabotage.CONTROLS[kind]())
    assert line["correct"] is False
    assert line["checks"]["device_bytes_wrong"]["value"] > 0, line["checks"]


async def test_healer_queues_nothing_and_block_lists_keep_nine_slots(
        tiny_root, monkeypatch):
    """Two of nine gone and no spare: the master's healer has nowhere to
    rebuild a shard, so the degraded state is the steady state and a
    reader's block list keeps all nine slots, the dead ones included."""
    plans = []
    real = placement.heal_under_replicated

    def recorded(state):
        plan = real(state)
        plans.append((len(state.chunk_servers), len(plan.queues)))
        return plan

    monkeypatch.setattr(placement, "heal_under_replicated", recorded)
    line = await run(tiny_root, CELL)
    assert line["correct"] is True, line["checks"]
    assert (7, 0) in plans and all(q == 0 for _n, q in plans), plans
    assert line["checks"]["meta_replicas_short"]["value"] == 0


@pytest.mark.parametrize("seed", [0, 1, 7, 12345, 2**31 - 1, 2**31,
                                  2**31 + 12345, 2600000211, 3200000001,
                                  4000000000, 4294967295, 4294967296 + 5])
def test_victims_hold_data_slots_of_one_rack_of_the_first_block(seed):
    slots = [f"cs{i}" for i in range(9)]
    dead = degraded.draw_victims(seed, slots, 6, 3, 2)
    a, b = (slots.index(v) for v in dead)
    assert 0 <= a < b < 6 and b == a + 3
    assert dead == degraded.draw_victims(seed, slots, 6, 3, 2)


def test_the_draw_reaches_every_rack_and_refuses_what_cannot_be():
    slots = [f"cs{i}" for i in range(9)]
    firsts = {degraded.draw_victims(s, slots, 6, 3, 2)[0]
              for s in range(64)}
    assert firsts == {"cs0", "cs1", "cs2"}
    with pytest.raises(ValueError, match="no 3 data slots"):
        degraded.draw_victims(1, slots, 6, 3, 3)


# ------------------------------------------------------ the plain reference


@pytest.mark.parametrize("k,m,nbytes", [(6, 3, 64 * KIB), (6, 3, 1000),
                                        (4, 2, 50_000), (3, 2, 777)])
def test_reference_rs_agrees_with_the_program_shard_for_shard(k, m, nbytes):
    data = reference.seeded_bytes(2**31 + 9, k, nbytes)
    ours = reference_rs.encode(data, k, m)
    assert ours == erasure.encode(data, k, m)
    assert len(ours) == k + m
    assert {len(s) for s in ours} == {reference_rs.shard_len(nbytes, k)}
    lost = list(ours)
    lost[0] = lost[k - 1] = None
    if m > 2:
        lost[k] = None
    assert reference_rs.decode(lost, k, m, nbytes) == data


def test_reference_rs_refuses_too_few_survivors():
    shards = reference_rs.encode(b"x" * 600, 6, 3)
    with pytest.raises(ValueError, match="5 shards survive"):
        reference_rs.decode([None] * 4 + shards[4:], 6, 3, 600)


# ----------------------------------------------------------- the yardstick


def test_decode_min_bytes_counts_survivors_in_and_missing_out():
    # RS(6,3) over 1 MiB: shards of ceil(2**20 / 6) = 174 763 bytes.
    assert rs_work.shard_bytes(1 << 20, 6) == 174_763
    assert rs_work.decode_min_bytes(1, 1, 1 << 20, 6) == 7 * 174_763
    assert rs_work.decode_min_bytes(10, 17, 1 << 20, 6) == 77 * 174_763
    assert rs_work.decode_min_bytes(0, 0, 1 << 20, 6) == 0
    v5e = peaks.peaks_for("TPU v5 lite")
    assert rs_work.decode_min_seconds(100, 200, 1 << 20, 6, v5e) \
        == pytest.approx(800 * 174_763 / 819e9)


class _Win:
    """What a reader reads, with nothing of the program in it."""

    trace = None
    lo_ns, hi_ns = 0, math.inf
    peaks = peaks.peaks_for("TPU v5 lite")
    trace_before: dict = {}
    trace_after: dict = {}

    class ctx:
        cfg = {"block_bytes": 1 << 20, "ec": [6, 3]}
        setup_readings: dict = {}

    counters: dict = {}

    def trace_delta(self, key):
        return self.counters.get(key)


def test_decode_roofline_reader_on_a_trace_with_the_program():
    """100 blocks, 150 data shards reconstructed, in 100 executions of 20 us
    each: (600 + 150) x 174 763 B / 819 GB/s = 160 us of 2 000 us = 8%."""
    win = _Win()
    win.counters = {"hbm.ec_degraded_blocks": 100,
                    "hbm.ec_missing_data_shards": 150}
    modules = [(i * 1e5, i * 1e5 + 2e4, "jit_rs_decode_block(1234)")
               for i in range(100)]
    modules.append((5e4, 6e4, "jit_block_crc_device(99)"))  # not counted
    win.trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace("/device:TPU:0", modules=modules)], 0.0)
    want = 100.0 * (750 * 174_763 / 819e9) / (100 * 2e-5)
    assert rs_decode_roofline_pct.read(win) == pytest.approx(want)
    assert 7.9 < want < 8.1


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_reader_finds_nothing_without_spans_counters_or_a_tpu_plane(
        name):
    win = _Win()
    assert NEW[name].read(win) is None
    # Counters but no device plane (a CPU trace): still nothing, never 0.
    win.counters = {"hbm.ec_blocks": 0, "hbm.ec_degraded_blocks": 0,
                    "hbm.ec_missing_data_shards": 0}
    win.trace = trace_reduce.Trace([], 0.0)
    assert NEW[name].read(win) is None
    win.counters = {"hbm.ec_blocks": 8, "hbm.ec_degraded_blocks": 8,
                    "hbm.ec_missing_data_shards": 9}
    if name == "ec_degraded_block_pct":
        assert NEW[name].read(win) == 100.0
    else:
        assert NEW[name].read(win) is None


def test_benchmark_json_lists_the_cell_its_metrics_and_the_control():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"hbm_read_GBps", "read_p95_ms", *NEW}
    loaded = harness.load_cell(CELL)
    assert loaded["mix"]["kind"] == degraded.KIND
    assert degraded.KIND in sabotage.CONTROLS
    assert jax.devices()[0].platform == "cpu"  # rehearsal only
