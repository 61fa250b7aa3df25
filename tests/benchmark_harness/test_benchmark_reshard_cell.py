"""``ckpt_reshard_2x2`` rehearsed at a tiny size on four of the CPU's
virtual devices (see benchmark_tiny.py for the stub of the chips): the
last line's shape with ``--trace 0`` and ``--trace 1``, ``correct`` true,
the control NOT correct, what the reference says of the deployment's real
tensor table and layouts, the work behind the two rooflines, and every new
reader silent where there is nothing to read.

The cluster runs in this process (``InprocChain``); the tensor table is cut
to a few hundred KiB in 64 KiB blocks with the published names, states,
dtypes and target specs, so every kind of split is there: experts of two
ranks split by rows, a column split whose half is 44 bytes, 2-chip halves,
replicated tensors and ``step``.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import pytest
from benchmark_tiny import LINE_KEYS, REPO, InprocChain, run, stub_chip

from benchmarks import (harness, peaks, reference_reshard, reshard_work,
                        sabotage, trace_reduce)
from benchmarks.layer_metrics import (
    ckpt_read_ms_per_restore,
    reshard_assemble_roofline_pct,
    reshard_h2d_bytes_ratio,
    reshard_ici_roofline_pct,
    reshard_plan_ms_per_restore,
)
from benchmarks.traffic import closed_loop_reshard_restore_hbm as kind

KIB = 1024
CELL = "ckpt_reshard_2x2"
CONFIG = "ckpt-reshard-3m5cs-r3"
MIX = "restore_reshard_ep2tp2"
NEW = {"reshard_plan_ms_per_restore": reshard_plan_ms_per_restore,
       "reshard_h2d_bytes_ratio": reshard_h2d_bytes_ratio,
       "reshard_ici_roofline_pct": reshard_ici_roofline_pct,
       "reshard_assemble_roofline_pct": reshard_assemble_roofline_pct}
TINY_TABLE = {
    "parameters": {"self_attn.q_proj.weight": [96, 256],
                   "self_attn.o_proj.weight": [128, 96],
                   "self_attn.kv_a_layernorm.weight": [64]},
    "experts_published": 16,
    "experts_held": 4,
    "expert_parameters": {"mlp.experts.gate_proj.weight": [44, 256],
                          "mlp.experts.down_proj.weight": [256, 44]},
}


def _real_cfg() -> dict:
    return json.loads((REPO / "benchmarks" / "configs"
                       / f"{CONFIG}.json").read_text())


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("tiny-reshard-root")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmarks" / "configs").mkdir(parents=True)
    (root / "benchmarks" / "workloads").mkdir()
    cfg = _real_cfg()
    cfg.update(masters=1, chunkservers=3, block_bytes=64 * KIB,
               bringup=InprocChain.name,
               dataset={**cfg["dataset"], **TINY_TABLE},
               assumed={**cfg["assumed"], "experts_per_rank": 2,
                        "files_per_rank": 2})
    (root / "benchmarks" / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(cfg))
    mix = json.loads((REPO / "benchmarks" / "workloads"
                      / f"{MIX}.json").read_text())
    mix.update(batch_reads=4, check_tensors=4, check_replica_blocks=4,
               trace_seconds=1)
    (root / "benchmarks" / "workloads" / f"{MIX}.json").write_text(
        json.dumps(mix))
    bench["configs"] = [{"name": CONFIG,
                         "file": f"benchmarks/configs/{CONFIG}.json"}]
    bench["workloads"] = [{"name": CELL, "config": CONFIG, "traffic": MIX,
                           "chips": 4}]
    bench["end_to_end"] = [{"name": n, "unit": "x"} for n in
                           ("hbm_read_GBps", "read_p95_ms", "setup_s")]
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
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"hbm_read_GBps", "read_p95_ms",
                                    "setup_s"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    compared = line["window"]["compared"]
    assert compared["device_shards"] == 4 * compared["device_tensors"]
    assert compared["device_tensors"] >= 11  # the kinds + those drawn
    assert compared["files"] == 4 and compared["replica_reads"] == 4 * 3
    counters = line["window"]["counters"]
    restores = line["attempted"]
    cfg = harness.load_cell(CELL, tiny_root)["cfg"]
    files = sum(reference_reshard.layout(cfg, s)[1] for s in range(4))
    assert counters["ckpt.reshard_h2d_bytes"] \
        == counters["ckpt.reshard_unique_bytes"] == files * restores
    assert counters["ckpt.reshard_ici_bytes"] \
        >= reshard_work.duplicated_bytes(cfg) * restores
    held = sum(reference_reshard.nbytes(e[0], tuple(
        s.stop - s.start for s in reference_reshard.device_index(cfg, n, c)))
        for n, e in reference_reshard.table(cfg).items() for c in range(4))
    assert counters["ckpt.tensor_bytes_device"] == held * restores
    assert "ckpt.tensor_bytes_host_bounce" not in counters  # stayed 0
    assert counters["ckpt.restored_shards"] == 4 * restores
    assert counters["combiner.blocks"] > 0


async def test_last_line_shape_traced(tiny_root):
    line = await run(tiny_root, CELL, trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    metrics = line["metrics"]
    assert {"reshard_plan_ms_per_restore", "reshard_h2d_bytes_ratio",
            "ckpt_read_ms_per_restore"} <= set(metrics), sorted(metrics)
    assert metrics["reshard_h2d_bytes_ratio"]["value"] == 1.0
    assert metrics["reshard_plan_ms_per_restore"]["value"] > 0
    # No TPU plane in a CPU trace: the rooflines are left out, never 0.
    assert "reshard_ici_roofline_pct" not in metrics
    assert "reshard_assemble_roofline_pct" not in metrics
    assert "ckpt_assemble_roofline_pct" not in metrics
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


async def test_control_comes_out_not_correct(tiny_root):
    mix_kind = harness.load_cell(CELL, tiny_root)["mix"]["kind"]
    assert mix_kind == kind.KIND
    line = await run(tiny_root, CELL, fault=sabotage.CONTROLS[mix_kind]())
    assert line["correct"] is False
    assert line["checks"]["device_bytes_wrong"]["value"] > 0, line["checks"]
    assert line["checks"]["compiles_in_window"]["value"] == 0
    assert line["checks"]["device_blocks_missing"]["value"] == 0


# ------------------------------------------------------ the plain reference


def test_the_real_table_and_layouts():
    cfg = _real_cfg()
    table = reference_reshard.table(cfg)
    assert len(table) == 57
    assert reference_reshard.unique_bytes(cfg) == 2_374_564_868
    params = {n: e for n, e in table.items() if n.startswith("params/")}
    assert sum(math.prod(reference_reshard.host_shape(e))
               for e in params.values()) == 169_611_776
    gate = table["params/model.layers.1.mlp.experts.gate_proj.weight"]
    assert gate == ("bfloat16", (64, 1408, 2048),
                    ((0, 16), (0, 1408), (0, 2048)))
    shards = reference_reshard.shards(cfg)
    assert [rank for rank, _n in shards] == [0] * 4 + [1] * 4
    sizes = [reference_reshard.layout(cfg, s)[1] for s in range(8)]
    assert sum(sizes) == 2_374_564_868
    # rank 1 saved its experts only; rank 0 its experts and the rest once
    assert all(reference_reshard.is_expert(cfg, n)
               for _r, names in shards[4:] for n in names)
    assert reshard_work.chip_bytes(cfg) == [712_048_128] * 4
    assert reshard_work.duplicated_bytes(cfg) == 473_625_612
    q = "master/model.layers.1.self_attn.q_proj.weight"
    assert [reference_reshard.device_index(cfg, q, c)[0]
            for c in range(4)] == [slice(0, 1536), slice(1536, 3072)] * 2
    down = "adam_m/model.layers.1.mlp.experts.down_proj.weight"
    assert reference_reshard.device_index(cfg, down, 3) \
        == (slice(8, 16), slice(0, 2048), slice(704, 1408))


def test_a_staged_step_holds_other_bytes(tiny_root):
    cfg = harness.load_cell(CELL, tiny_root)["cfg"]
    seed = 2**31 + 78
    assert reference_reshard.shard_payload(seed, cfg, 0) \
        != reference_reshard.shard_payload(seed, cfg, 0, "torn")


# ----------------------------------------------------------- the yardstick


class _Win:
    trace = None
    lo_ns, hi_ns = 0, math.inf
    t1 = 0.0
    peaks = peaks.peaks_for("TPU v5 lite")
    trace_before: dict = {}
    trace_after: dict = {}

    def __init__(self, cfg=None):
        class ctx:
            setup_readings: dict = {}
            devices = [type("D", (), {"id": i})() for i in range(4)]

        ctx.cfg = cfg or _real_cfg()
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
    from benchmarks import program_spans

    class _run:
        bounds = (0, 10**12)

    _run.records = records
    win.ctx.setup_readings = {program_spans.KEY: _run}
    win.trace_before = {program_spans.WALL_NS: 0}
    win.trace_after = {program_spans.WALL_NS: 10**12}
    return win


def _modules(name, count, us, devices=4):
    return [trace_reduce.DeviceTrace(f"/device:TPU:{d}", modules=[
        (i * 1e6, i * 1e6 + us * 1e3, f"{name}(7)") for i in range(count)])
        for d in range(devices)]


def test_rooflines_on_a_trace_with_the_programs():
    """Two restores: the move ran twice on each of 4 chips, 10 ms each;
    each chip assembled twice, 5 ms each."""
    cfg = _real_cfg()
    win = _with_spans(_Win(cfg), [
        *(_Span("ckpt.redistribute", 10, 20, bytes=1) for _ in range(2)),
        *(_Span("ckpt.assemble", 30, 40, device=d, bytes=1)
          for d in range(4) for _ in range(2)),
        _Span("ckpt.assemble", 30, 40, shard=0, bytes=1)])  # a plain one
    win.trace = trace_reduce.Trace(
        _modules("jit_ckpt_reshard_ici", 2, 10_000)
        + _modules("jit_ckpt_reshard_assemble", 2, 5_000)
        + _modules("jit_ckpt_assemble_gather", 9, 100), 0.0)
    v5e = peaks.peaks_for("TPU v5 lite")
    ici = 100 * 2 * (473_625_612 / 4 / 200e9) / (8 * 0.010 / 4)
    assert reshard_ici_roofline_pct.read(win) == pytest.approx(ici)
    hbm = 100 * 8 * (2 * 712_048_128 / v5e["hbm_bytes_per_s"]) / (8 * 0.005)
    assert reshard_assemble_roofline_pct.read(win) == pytest.approx(hbm)
    assert 0 < ici < 100 and 0 < hbm < 100


def test_plan_and_ratio_readers():
    restores = [_Span("ckpt.restore", 0, 10**8, span_id=1),
                _Span("ckpt.restore", 0, 10**8, span_id=2),
                _Span("ckpt.restore", 0, 10**8, span_id=3)]  # no target
    kids = [_Span("ckpt.plan", 0, 4_000_000, parent_id=1),
            _Span("ckpt.plan", 0, 2_000_000, parent_id=2)]
    win = _with_spans(_Win(), restores + kids)
    assert reshard_plan_ms_per_restore.read(win) == pytest.approx(3.0)
    win.counters = {"ckpt.reshard_h2d_bytes": 1100,
                    "ckpt.reshard_unique_bytes": 1000}
    assert reshard_h2d_bytes_ratio.read(win) == pytest.approx(1.1)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_reader_finds_nothing_without_spans_counters_or_a_tpu_plane(
        name):
    win = _Win()
    assert NEW[name].read(win) is None
    # A plain restore's spans and counters, a CPU trace: still nothing.
    win = _with_spans(_Win(), [
        _Span("ckpt.restore", 0, 10, span_id=1),
        _Span("ckpt.assemble", 0, 10, parent_id=1, shard=0, bytes=4096)])
    win.counters = {"ckpt.tensor_bytes_device": 4096}
    win.trace = trace_reduce.Trace([], 0.0)
    assert NEW[name].read(win) is None
    # A restore under another layout on a CPU trace: the rooflines stay out.
    win = _with_spans(_Win(), [
        _Span("ckpt.redistribute", 0, 10, bytes=9),
        _Span("ckpt.assemble", 0, 10, device=0, bytes=9)])
    win.trace = trace_reduce.Trace([], 0.0)
    if name.endswith("roofline_pct"):
        assert NEW[name].read(win) is None


def test_benchmark_json_lists_the_cell_its_metrics_and_the_control():
    """Found by name; each list holds the cell (other cells may share it).
    The four readers above are not entries yet: a per-layer entry appended
    after the engine read clocks' five moves them off the end of the list,
    where ``test_benchmark_engine_reads.py`` looks for them. Listed or not,
    an entry of theirs names this cell alone."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 4)
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"hbm_read_GBps", "read_p95_ms",
            "ckpt_read_ms_per_restore"} <= listed
    assert listed <= {"hbm_read_GBps", "read_p95_ms", *NEW,
                      "ckpt_read_ms_per_restore"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["source"] != "program_span"
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["dataset"]
    assert config["source"] == _real_cfg()["source"] \
        and len(config["source"]) <= 200
    loaded = harness.load_cell(CELL)
    assert loaded["mix"]["kind"] == kind.KIND
    assert loaded["mix"]["clients"] == 1 and not loaded["mix"]["local_reads"]
    assert loaded["mix"]["batch_reads"] == 16
    assert loaded["mix"]["keep_resident"] == 1
    assert kind.KIND in sabotage.CONTROLS
    assert ckpt_read_ms_per_restore.read(_Win()) is None
    assert jax.devices()[0].platform == "cpu"  # rehearsal only


def test_the_reference_imports_nothing_of_the_program():
    for module in ("reference_reshard.py", "reshard_work.py"):
        text = (REPO / "benchmarks" / module).read_text()
        assert "tpudfs" not in text.replace("``tpudfs``", "") \
            and "native" not in text.replace("``native/``", "")
