"""``ckpt_restore_hbm`` rehearsed at a tiny size on the CPU (see
benchmark_tiny.py for the stub of the chip): the last line's shape with
``--trace 0`` and ``--trace 1``, ``correct`` true, the control NOT correct,
what the reference says of the deployment's real tensor table, the bytes
behind ``ckpt_assemble_roofline_pct`` and every new reader silent where
there is nothing to read.

The cluster runs in this process (``InprocChain``); the tensor table is cut
to a few hundred KiB in 64 KiB blocks, with the published names, states and
dtypes, so every shard still holds bf16 and f32 tensors that straddle
blocks and ends in a short block.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import pytest
from benchmark_tiny import LINE_KEYS, REPO, InprocChain, run, stub_chip

from benchmarks import (ckpt_work, harness, peaks, reference_ckpt, sabotage,
                        trace_reduce)
from benchmarks.layer_metrics import (
    ckpt_assemble_ms_per_restore,
    ckpt_assemble_roofline_pct,
    ckpt_device_assembled_pct,
    ckpt_meta_ms_per_restore,
    ckpt_read_ms_per_restore,
)
from benchmarks.traffic import closed_loop_restore_hbm as restore_kind

KIB = 1024
CELL = "ckpt_restore_hbm"
CONFIG = "ckpt-3m5cs-r3"
MIX = "restore_latest_4shards"
NEW = {"ckpt_meta_ms_per_restore": ckpt_meta_ms_per_restore,
       "ckpt_read_ms_per_restore": ckpt_read_ms_per_restore,
       "ckpt_assemble_ms_per_restore": ckpt_assemble_ms_per_restore,
       "ckpt_device_assembled_pct": ckpt_device_assembled_pct,
       "ckpt_assemble_roofline_pct": ckpt_assemble_roofline_pct}
TINY_TABLE = {
    "parameters": {"self_attn.q_proj.weight": [96, 256],
                   "self_attn.kv_a_layernorm.weight": [64],
                   "mlp.gate.weight": [8, 256]},
    "experts_held": 2,
    "expert_parameters": {"mlp.experts.{e}.gate_proj.weight": [44, 256],
                          "mlp.experts.{e}.down_proj.weight": [256, 44]},
}


def _real_cfg() -> dict:
    return json.loads((REPO / "benchmarks" / "configs"
                       / f"{CONFIG}.json").read_text())


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("tiny-ckpt-root")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmarks" / "configs").mkdir(parents=True)
    (root / "benchmarks" / "workloads").mkdir()
    cfg = _real_cfg()
    cfg.update(masters=1, chunkservers=3, block_bytes=64 * KIB,
               bringup=InprocChain.name,
               dataset={**cfg["dataset"], **TINY_TABLE})
    (root / "benchmarks" / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(cfg))
    mix = json.loads((REPO / "benchmarks" / "workloads"
                      / f"{MIX}.json").read_text())
    mix.update(batch_reads=4, check_tensors=6, check_replica_blocks=4,
               trace_seconds=1)
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
def no_chip(monkeypatch):
    stub_chip(monkeypatch)


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
    # 6 drawn + smallest + largest + a bf16 and an f32 straddler, as a set.
    assert 6 <= compared["device_tensors"] <= 10
    assert compared["files"] == 4 and compared["meta_blocks"] >= 8
    assert compared["replica_reads"] == 4 * 3
    counters = line["window"]["counters"]
    restores = line["attempted"]
    assert counters["ckpt.restored_shards"] == 4 * restores
    assert "ckpt.tensor_bytes_host_bounce" not in counters  # stayed 0
    cfg = harness.load_cell(CELL, tiny_root)["cfg"]
    state = sum(reference_ckpt.nbytes(*t)
                for t in reference_ckpt.table(cfg).values())
    assert counters["ckpt.tensor_bytes_device"] == state * restores
    assert counters["combiner.rounds"] > 0
    assert counters["combiner.blocks"] >= 8 * restores


async def test_last_line_shape_traced(tiny_root):
    line = await run(tiny_root, CELL, trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert "setup_s" not in line["metrics"]
    spans = set(NEW) - {"ckpt_assemble_roofline_pct"}
    assert spans <= set(line["metrics"]), sorted(line["metrics"])
    assert all(line["metrics"][n]["value"] > 0 for n in spans)
    assert line["metrics"]["ckpt_device_assembled_pct"]["value"] == 100.0
    # No TPU plane in a CPU trace: a share of a roofline is left out, never
    # reported as 0; the EC cell's readers find nothing here.
    assert "ckpt_assemble_roofline_pct" not in line["metrics"]
    assert "crc_verify_roofline_pct" not in line["metrics"]
    assert "ec_degraded_block_pct" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


async def test_control_comes_out_not_correct(tiny_root):
    kind = harness.load_cell(CELL, tiny_root)["mix"]["kind"]
    assert kind == restore_kind.KIND
    line = await run(tiny_root, CELL, fault=sabotage.CONTROLS[kind]())
    assert line["correct"] is False
    assert line["checks"]["device_bytes_wrong"]["value"] > 0, line["checks"]


# ------------------------------------------------------ the plain reference


def test_the_real_table_is_the_published_one():
    cfg = _real_cfg()
    table = reference_ckpt.table(cfg)
    assert len(table) == 141
    assert sum(reference_ckpt.nbytes(*t) for t in table.values()) \
        == 1_405_680_644
    params = {n: t for n, t in table.items() if n.startswith("params/")}
    assert len(params) == 35
    assert {t[0] for t in params.values()} == {"bfloat16"}
    elements = sum(math.prod(shape) for _d, shape in params.values())
    assert elements == 100_405_760
    assert table["step"] == ("int32", ())
    assert table["master/model.layers.1.self_attn.q_proj.weight"] \
        == ("float32", (3072, 2048))
    sizes = sorted(reference_ckpt.nbytes(*t) for t in table.values())
    assert sizes[0] == 4 and sizes[1] == 1024 and sizes[-1] == 25_165_824


def test_the_real_deal_gives_four_shards_of_336_blocks_with_both_dtypes():
    cfg = _real_cfg()
    table = reference_ckpt.table(cfg)
    shards = reference_ckpt.deal(cfg)
    assert sorted(n for names in shards for n in names) == sorted(table)
    ends = []
    for shard, names in enumerate(shards):
        placed, end = reference_ckpt.layout(cfg, shard)
        ends.append(end)
        assert [n for n, _o, _s in placed] == sorted(names)
        assert all(off % 512 == 0 for _n, off, _s in placed)
        assert -(-end // cfg["block_bytes"]) == 336
        assert end % cfg["block_bytes"]  # the last block is short
        assert {table[n][0] for n in names} >= {"bfloat16", "float32"}
    assert sum(ends) == 1_405_680_644  # nothing but the tensors' bytes
    assert reference_ckpt.straddlers(cfg, "bfloat16")
    assert reference_ckpt.straddlers(cfg, "float32")


def test_a_staged_step_holds_other_bytes_than_the_published_one(tiny_root):
    cfg = harness.load_cell(CELL, tiny_root)["cfg"]
    seed = 2**31 + 77
    name = reference_ckpt.deal(cfg)[0][0]
    assert reference_ckpt.tensor(seed, cfg, name) \
        == reference_ckpt.tensor(seed, cfg, name)
    assert reference_ckpt.tensor(seed, cfg, name)[2] \
        != reference_ckpt.tensor(seed, cfg, name, "torn")[2]
    assert reference_ckpt.shard_payload(seed, cfg, 0) \
        != reference_ckpt.shard_payload(seed, cfg, 0, "torn")


# ----------------------------------------------------------- the yardstick


def test_assemble_min_bytes_counts_the_aligned_payload_in_and_out():
    assert ckpt_work.aligned(351_293_444) == 351_293_952
    assert ckpt_work.assemble_min_bytes([512]) == 1024
    assert ckpt_work.assemble_min_bytes([1, 513]) == 2 * (512 + 1024)
    assert ckpt_work.assemble_min_bytes([]) == 0
    v5e = peaks.peaks_for("TPU v5 lite")
    assert ckpt_work.assemble_min_seconds([351_293_444] * 4, v5e) \
        == pytest.approx(8 * 351_293_952 / 819e9)


class _Win:
    """What a reader reads, with nothing of the program in it."""

    trace = None
    lo_ns, hi_ns = 0, math.inf
    t1 = 0.0
    peaks = peaks.peaks_for("TPU v5 lite")
    trace_before: dict = {}
    trace_after: dict = {}

    def __init__(self):
        class ctx:
            setup_readings: dict = {}

        self.ctx = ctx
        self.counters: dict = {}

    def trace_delta(self, key):
        return self.counters.get(key)


class _Span:
    def __init__(self, name, start_ns, end_ns, span_id=0, parent_id=None,
                 **attrs):
        self.name, self.start_ns, self.end_ns = name, start_ns, end_ns
        self.span_id, self.parent_id, self.attrs = span_id, parent_id, attrs


def _with_spans(win, records):
    """The traced part as ``program_spans`` keeps it, already closed."""
    from benchmarks import program_spans

    class run:
        bounds = (0, 10**12)

    run.records = records
    win.ctx.setup_readings = {program_spans.KEY: run}
    win.trace_before = {program_spans.WALL_NS: 0}
    win.trace_after = {program_spans.WALL_NS: 10**12}
    return win


def test_assemble_roofline_reader_on_a_trace_with_the_programs():
    """Two shards of 1 MiB assembled; the gather ran 8 times 10 us and the
    assembly twice 30 us: 4 MiB / 819 GB/s = 5.12 us of 140 us = 3.66%."""
    win = _with_spans(_Win(), [
        _Span("ckpt.assemble", 100, 200, shard=0, bytes=1 << 20),
        _Span("ckpt.assemble", 300, 400, shard=1, bytes=(1 << 20) - 7),
        _Span("ckpt.confirm", 50, 90, shard=0)])
    modules = [(i * 1e5, i * 1e5 + 1e4, "jit_ckpt_assemble_gather(77)")
               for i in range(8)]
    modules += [(1e6, 1e6 + 3e4, "jit_ckpt_assemble(1234)"),
                (2e6, 2e6 + 3e4, "jit_ckpt_assemble(99)"),
                (3e6, 3e6 + 5e4, "jit_batch_block_crc_device(5)")]  # not it
    win.trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace("/device:TPU:0", modules=modules)], 0.0)
    want = 100.0 * (4 * (1 << 20) / 819e9) / 140e-6
    assert ckpt_assemble_roofline_pct.read(win) == pytest.approx(want)
    assert 3.6 < want < 3.7


def test_span_readers_sum_per_restore():
    restore = [_Span("ckpt.restore", 0, 100_000_000, span_id=1),
               _Span("ckpt.restore", 0, 60_000_000, span_id=2)]
    kids = [
        _Span("ckpt.latest_step", 0, 2_000_000, parent_id=1),
        _Span("ckpt.manifest", 2_000_000, 5_000_000, parent_id=1),
        _Span("ckpt.combined_crc", 50_000_000, 51_000_000, parent_id=1),
        _Span("ckpt.read_shard", 5_000_000, 60_000_000, parent_id=1),
        _Span("ckpt.read_shard", 6_000_000, 70_000_000, parent_id=1),
        _Span("ckpt.confirm", 60_000_000, 62_000_000, parent_id=1),
        _Span("ckpt.confirm", 70_000_000, 75_000_000, parent_id=1),
        _Span("ckpt.assemble", 75_000_000, 79_000_000, parent_id=1),
        _Span("ckpt.latest_step", 0, 4_000_000, parent_id=2),
        _Span("ckpt.read_shard", 4_000_000, 40_000_000, parent_id=2),
        _Span("ckpt.confirm", 40_000_000, 44_000_000, parent_id=2),
        _Span("ckpt.assemble", 44_000_000, 46_000_000, parent_id=2),
        _Span("ckpt.assemble", 1, 2, parent_id=99),  # nobody's child
    ]
    win = _with_spans(_Win(), restore + kids)
    assert ckpt_meta_ms_per_restore.read(win) == pytest.approx((6 + 4) / 2)
    assert ckpt_read_ms_per_restore.read(win) == pytest.approx((70 + 40) / 2)
    assert ckpt_assemble_ms_per_restore.read(win) \
        == pytest.approx((4 + 2) / 2)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_reader_finds_nothing_without_spans_counters_or_a_tpu_plane(
        name):
    win = _Win()
    assert NEW[name].read(win) is None
    # Spans and counters of another cell, and a CPU trace: still nothing.
    win = _with_spans(_Win(), [_Span("hbm.read_file", 0, 10, span_id=5)])
    win.counters = {"combiner.blocks": 64}
    win.trace = trace_reduce.Trace([], 0.0)
    assert NEW[name].read(win) is None
    # Restores seen but no device plane: the counter's share reads, the
    # roofline's does not, never 0.
    win = _with_spans(_Win(), [
        _Span("ckpt.assemble", 0, 10, bytes=4096, parent_id=None)])
    win.counters = {"ckpt.tensor_bytes_device": 4096,
                    "ckpt.tensor_bytes_host_bounce": 0}
    win.trace = trace_reduce.Trace([], 0.0)
    if name == "ckpt_device_assembled_pct":
        assert NEW[name].read(win) == 100.0
    else:
        assert NEW[name].read(win) is None
    win.counters = {"ckpt.tensor_bytes_device": 0,
                    "ckpt.tensor_bytes_host_bounce": 0}
    assert ckpt_device_assembled_pct.read(win) is None


def test_benchmark_json_lists_the_cell_its_metrics_and_the_control():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"hbm_read_GBps", "read_p95_ms", *NEW}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["source"] != "program_span"
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["dataset"]
    assert config["source"] == _real_cfg()["source"] \
        and len(config["source"]) <= 200
    loaded = harness.load_cell(CELL)
    assert loaded["mix"]["kind"] == restore_kind.KIND
    assert loaded["mix"]["clients"] == 1 and not loaded["mix"]["local_reads"]
    assert loaded["mix"]["batch_reads"] == 16
    assert restore_kind.KIND in sabotage.CONTROLS
    assert jax.devices()[0].platform == "cpu"  # rehearsal only


def test_the_reference_imports_nothing_of_the_program():
    for module in ("reference_ckpt.py", "ckpt_work.py"):
        text = (REPO / "benchmarks" / module).read_text()
        assert "tpudfs" not in text.replace("``tpudfs``", "") \
            and "native" not in text.replace("``native/``", "")
