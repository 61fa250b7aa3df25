"""The chunkserver engine's read clocks and the infeed's hop onto its client
loop in traced runs, at a tiny size on the CPU (see benchmark_tiny.py): each
of the five readers reports in every cell ``BENCHMARK.json`` lists it for
and finds nothing in the others, ``ha_colocated_sweep`` included; a program
without the clocks (the parent of the PR that brought them) is left alone.
Counts and presence only: a CPU run gives no times."""

from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest
from benchmark_tiny import REPO, InprocChain, make_tiny_root, run, stub_chip
from test_benchmark_ckpt_cell import TINY_TABLE
from test_benchmark_ec_cell import InprocNine
from test_benchmark_infeed_cell import TINY_DATASET

from benchmarks import deployments, engine_read_stages, harness
from benchmarks.layer_metrics import (
    engine_readblock_ms,
    engine_readblocks_read_ms,
    engine_readblocks_send_ms,
    infeed_hop_ms_per_record,
    infeed_on_loop_ms_per_record,
)
from benchmarks.traffic import closed_loop_read_hbm_degraded as degraded
from tests.test_infeed_wds import infeed_threads

KIB = 1024
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = {"engine_readblocks_read_ms": engine_readblocks_read_ms,
       "engine_readblocks_send_ms": engine_readblocks_send_ms,
       "engine_readblock_ms": engine_readblock_ms,
       "infeed_hop_ms_per_record": infeed_hop_ms_per_record,
       "infeed_on_loop_ms_per_record": infeed_on_loop_ms_per_record}
LISTED = {m["name"]: m["workloads"] for m in BENCH["per_layer"]
          if m["name"] in NEW}
#: the cells the file makes of its own (the ha-3m5cs-r3 ones come from
#: benchmark_tiny): config, mix, the bring-up, and the cuts
OWN = {
    "ec_degraded_read": (
        "ec-3m9cs-rs63", "degraded_read_16x64m", InprocNine.name,
        {"masters": 1, "block_bytes": 64 * KIB,
         "dataset": {"files": 4, "file_bytes": 256 * KIB}},
        {"clients": 3, "check_files": 2, "check_replica_blocks": 4,
         "trace_seconds": 1, "noticed_wait_s": 20}),
    "ckpt_restore_hbm": (
        "ckpt-3m5cs-r3", "restore_latest_4shards", InprocChain.name,
        {"masters": 1, "chunkservers": 3, "block_bytes": 64 * KIB,
         "dataset": TINY_TABLE},
        {"batch_reads": 4, "check_tensors": 6, "check_replica_blocks": 4,
         "trace_seconds": 1}),
    "wds_infeed_hbm": (
        "wds-3m5cs-r3", "grain_shuffled_b400", InprocChain.name,
        {"masters": 1, "chunkservers": 3, "block_bytes": 64 * KIB,
         "dataset": TINY_DATASET},
        {"check_replica_blocks": 4, "trace_seconds": 1}),
}


def _own_root(tmp_path_factory, cell: str) -> Path:
    """A root with ``cell`` alone, cut as its own rehearsal file cuts it,
    and every reader the benchmark has."""
    config, mix_name, bringup, cfg_cut, mix_cut = OWN[cell]
    root = tmp_path_factory.mktemp(f"tiny-{cell}")
    (root / "benchmarks" / "configs").mkdir(parents=True)
    (root / "benchmarks" / "workloads").mkdir()
    cfg_file = f"benchmarks/configs/{config}.json"
    cfg = json.loads((REPO / cfg_file).read_text())
    cut = dict(cfg_cut)
    if cell != "ec_degraded_read":  # the EC file replaces its dataset
        cut["dataset"] = {**cfg["dataset"], **cfg_cut["dataset"]}
    cfg.update(cut, bringup=bringup)
    (root / cfg_file).write_text(json.dumps(cfg))
    mix_file = f"benchmarks/workloads/{mix_name}.json"
    mix = json.loads((REPO / mix_file).read_text())
    mix.update(mix_cut)
    (root / mix_file).write_text(json.dumps(mix))
    bench = dict(BENCH)
    bench["configs"] = [{"name": config, "file": cfg_file}]
    bench["workloads"] = [{"name": cell, "config": config,
                           "traffic": mix_name, "chips": 1}]
    bench["end_to_end"] = [{"name": n, "unit": "x"} for n in
                           ("hbm_read_GBps", "read_p95_ms", "setup_s")]
    bench["per_layer"] = [
        {"name": f.stem, "unit": "x"} for f in sorted(
            (REPO / "benchmarks" / "layer_metrics").glob("*.py"))
        if f.stem != "__init__"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def ha_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory)


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    stub_chip(monkeypatch)
    monkeypatch.setitem(deployments.BRINGUPS, InprocNine.name, InprocNine)

    async def kill(ctx, addr):
        await ctx.bringup.kill(addr)

    async def seen(ctx):
        return await ctx.bringup.seen()

    monkeypatch.setattr(degraded, "kill_chunkserver", kill)
    monkeypatch.setattr(degraded, "chunkservers_seen", seen)


def test_benchmark_json_appends_the_five_entries():
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == list(NEW)
    frames = ["ha_remote_read", "ec_degraded_read", "ckpt_restore_hbm"]
    assert LISTED == {"engine_readblocks_read_ms": frames,
                      "engine_readblocks_send_ms": frames,
                      "engine_readblock_ms": ["wds_infeed_hbm"],
                      "infeed_hop_ms_per_record": ["wds_infeed_hbm"],
                      "infeed_on_loop_ms_per_record": ["wds_infeed_hbm"]}
    layers = {"chunkserver engine", "client"}
    for m in BENCH["per_layer"][-5:]:
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("ms", "lower", "program_counter", "hbm_read_GBps")
        assert m["layer"] in layers
    # Both layers are ones the benchmark already names.
    assert layers <= {m["layer"] for m in BENCH["per_layer"][:-5]}
    assert sum(m["source"] == "program_span" for m in BENCH["per_layer"]) \
        == 9


@pytest.mark.parametrize("cell", ["ha_remote_read", "ha_colocated_sweep",
                                  *OWN])
async def test_traced_cell_reports_the_readers_it_lists(
        cell, ha_root, tmp_path_factory):
    root = ha_root if cell.startswith("ha_") \
        else _own_root(tmp_path_factory, cell)
    line = await run(root, cell, trace=True, seconds=2.0)
    if cell == "wds_infeed_hbm":  # the kind's close ends its pipeline
        assert not [t for t in infeed_threads() if not t.startswith(
            "tpudfs-ec")]
    assert line["correct"] is True, line["checks"]
    listed = {name for name, cells in LISTED.items() if cell in cells}
    got = {name for name in NEW if name in line["metrics"]}
    assert listed <= got, (sorted(got), sorted(listed))
    assert all(line["metrics"][n]["value"] > 0 for n in listed)
    # Elsewhere the frames' and the infeed's readers find nothing. A cell
    # whose reads include some ReadBlock (a restore's manifest, a fall-back)
    # gives engine_readblock_ms something; the benchmark runs it only in
    # the cell it lists.
    assert got - listed <= {"engine_readblock_ms"}, sorted(got - listed)
    if cell == "ha_colocated_sweep":
        assert not got  # the pump preads: no engine on the path
    counters = line["window"]["counters"]
    if listed & {"engine_readblocks_read_ms", "engine_readblock_ms"}:
        # The window's counters carry the engines' clocks in a traced run.
        assert any(k.startswith(engine_read_stages.PREFIX) for k in counters)


async def test_an_untraced_run_asks_no_server_for_its_clocks(ha_root):
    line = await run(ha_root, "ha_remote_read")
    assert line["correct"] is True
    assert not any(k.startswith(engine_read_stages.PREFIX)
                   for k in line["window"]["counters"])


# ----------------------------------------------------- the readers alone


def _win(before: dict, after: dict, trace_before=None, trace_after=None):
    ctx = SimpleNamespace(setup_readings={})
    return harness.Window(ctx, [], 0.0, 1.0, before, after,
                          trace_before or {}, trace_after or {}, None, 0, 1,
                          {})


def _stages(**vals) -> dict:
    return {engine_read_stages.PREFIX + k: v for k, v in vals.items()}


def test_engine_readers_divide_the_clocks_by_their_calls():
    before = _stages(rb_calls=10, rb_read_ns=1e6, rb_send_ns=0,
                     rbs_frames=2, rbs_read_ns=4e6, rbs_send_ns=2e6)
    after = _stages(rb_calls=14, rb_read_ns=2e6, rb_send_ns=1e6,
                    rbs_frames=6, rbs_read_ns=24e6, rbs_send_ns=10e6)
    win = _win(before, after)
    assert engine_readblocks_read_ms.read(win) == 5.0
    assert engine_readblocks_send_ms.read(win) == 2.0
    assert engine_readblock_ms.read(win) == 0.5


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_whose_denominator_did_not_move_says_nothing(name):
    still = _stages(rb_calls=3, rb_read_ns=9, rb_send_ns=9, rbs_frames=3,
                    rbs_read_ns=9, rbs_send_ns=9)
    infeed = {"infeed.records": 5, "infeed.hop_ns": 7, "infeed.loop_ns": 7}
    assert NEW[name].read(_win(still, still, infeed, infeed)) is None
    # ... nor where nothing of it is there at all: the parent's program.
    assert NEW[name].read(_win({}, {})) is None


def test_infeed_readers_divide_by_the_records_of_the_traced_part():
    before = {"infeed.records": 100, "infeed.hop_ns": 0,
              "infeed.loop_ns": 5e6}
    after = {"infeed.records": 300, "infeed.hop_ns": 60e6,
             "infeed.loop_ns": 205e6}
    win = _win({}, {}, before, after)
    assert infeed_hop_ms_per_record.read(win) == 0.3
    assert infeed_on_loop_ms_per_record.read(win) == 1.0


async def test_a_program_without_the_clocks_is_left_alone():
    """The parent: its ``Stats`` has no ``read_stages``, so the wrapped
    counters are the bring-up's own, and ``attach`` wraps once."""
    asked = []

    class Rpc:
        async def call(self, addr, service, method, req, timeout=10.0):
            asked.append((addr, method))
            return {"cache_hits": 0, "cache_misses": 0}

    class Bringup:
        endpoints = {"chunkservers": ["a:1", "b:2"]}

        async def counters(self, rpc):
            return {"cs.cache_hits": 0}

    ctx = SimpleNamespace(setup_readings={}, bringup=Bringup())
    engine_read_stages.attach(ctx)
    engine_read_stages.attach(ctx)
    assert await ctx.bringup.counters(Rpc()) == {"cs.cache_hits": 0}
    assert asked == [("a:1", "Stats"), ("b:2", "Stats")]
