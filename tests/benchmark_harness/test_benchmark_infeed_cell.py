"""``wds_infeed_hbm`` rehearsed at a tiny size on the CPU (see
benchmark_tiny.py for the stub of the chip): the last line's shape with
``--trace 0`` and ``--trace 1``, ``correct`` true, the control NOT correct,
what the reference says of the deployment's real dataset, the on-device
digest against the reference's, and every new reader silent where there is
nothing to read.

The cluster runs in this process (``InprocChain``); the dataset is cut to
3 shards x 40 samples of 10 000 B in 64 KiB blocks and batches of 16, so a
window holds a few epochs and records still straddle blocks.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest
from benchmark_tiny import LINE_KEYS, REPO, InprocChain, run, stub_chip
from test_benchmark_ckpt_cell import _Span, _Win, _with_spans

from benchmarks import harness, reference_wds, sabotage, trace_reduce
from benchmarks.layer_metrics import (
    infeed_collate_ms_per_batch,
    infeed_device_put_ms_per_batch,
    infeed_fetch_ms_per_record,
    infeed_gate_wait_pct,
    infeed_range_reads_per_record,
)
from benchmarks.traffic import closed_loop_infeed_hbm as infeed_kind
from tests.test_infeed_wds import infeed_threads
from tpudfs.common import telemetry

KIB = 1024
CELL = "wds_infeed_hbm"
CONFIG = "wds-3m5cs-r3"
MIX = "grain_shuffled_b400"
NEW = {"infeed_fetch_ms_per_record": infeed_fetch_ms_per_record,
       "infeed_gate_wait_pct": infeed_gate_wait_pct,
       "infeed_range_reads_per_record": infeed_range_reads_per_record,
       "infeed_collate_ms_per_batch": infeed_collate_ms_per_batch,
       "infeed_device_put_ms_per_batch": infeed_device_put_ms_per_batch}
TINY_DATASET = {"shards": 3, "samples_per_shard": 40, "record_bytes": 10_000,
                "batch_size": 16}


def _real_cfg() -> dict:
    return json.loads((REPO / "benchmarks" / "configs"
                       / f"{CONFIG}.json").read_text())


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("tiny-infeed-root")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmarks" / "configs").mkdir(parents=True)
    (root / "benchmarks" / "workloads").mkdir()
    cfg = _real_cfg()
    cfg.update(masters=1, chunkservers=3, block_bytes=64 * KIB,
               bringup=InprocChain.name,
               dataset={**cfg["dataset"], **TINY_DATASET})
    (root / "benchmarks" / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(cfg))
    mix = json.loads((REPO / "benchmarks" / "workloads"
                      / f"{MIX}.json").read_text())
    mix.update(check_replica_blocks=4, trace_seconds=1)
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


@pytest.fixture(autouse=True)
def no_infeed_thread_outlives_its_test():
    """The kind's ``close`` ends the pipeline it started: its threads would
    load every test after this file in its worker."""
    yield
    assert infeed_threads() == []


async def test_last_line_shape_end_to_end(tiny_root):
    line = await run(tiny_root, CELL, seconds=2.0)
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
    # Every record since the pipeline started: the warm-up's two batches
    # and the window's, 16 a batch; the two resident batches whole.
    assert compared["records"] == 16 * (2 + line["attempted"])
    assert compared["epochs"] == compared["records"] // 120
    assert compared["resident_records"] == 32
    assert compared["files"] == 2 and compared["meta_blocks"] >= 2 * 7
    assert compared["replica_reads"] == 4 * 3
    counters = line["window"]["counters"]
    # The prefetch was ahead of the consumer by up to Grain's buffer and
    # the hand-off when the window opened, and is again when it closes.
    assert counters["infeed.records"] >= 16 * line["attempted"] - 500 - 64
    assert counters["infeed.range_reads"] >= counters["infeed.records"] > 0
    assert not telemetry._enabled  # a plain run never turned the spans on


async def test_last_line_shape_traced(tiny_root):
    line = await run(tiny_root, CELL, trace=True, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert "setup_s" not in line["metrics"]
    assert set(NEW) <= set(line["metrics"]), sorted(line["metrics"])
    assert all(line["metrics"][n]["value"] > 0
               for n in set(NEW) - {"infeed_gate_wait_pct"})
    assert 0 <= line["metrics"]["infeed_gate_wait_pct"]["value"] < 100
    assert 1.0 <= line["metrics"]["infeed_range_reads_per_record"][
        "value"] <= 2.0
    # An accepted metric has something to read here: the bus the batch's
    # transfer is held against. (``cs_cache_hit_pct``, which lists the cell
    # too, reads the served processes' ``Stats``: this bring-up has none.)
    assert line["metrics"]["h2d_raw_GBps"]["value"] > 0
    # Other cells' readers find nothing here, and say nothing.
    for name in ("crc_verify_roofline_pct", "ec_degraded_block_pct",
                 "ckpt_device_assembled_pct", "combiner_blocks_per_round",
                 "read_queue_wait_ms", "sweep_pump_wait_pct"):
        assert name not in line["metrics"], name
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not telemetry._enabled  # the first reader turned tracing off


async def test_control_comes_out_not_correct(tiny_root):
    kind = harness.load_cell(CELL, tiny_root)["mix"]["kind"]
    assert kind == infeed_kind.KIND
    line = await run(tiny_root, CELL, fault=sabotage.CONTROLS[kind](),
                     seconds=1.0)
    assert line["correct"] is False
    checks = line["checks"]
    # Two rows a batch since the control took hold (the window's batches),
    # and the two resident batches' again; nothing else is wrong.
    assert checks["device_bytes_wrong"]["value"] \
        == 2 * line["attempted"] + 2 * 2, checks
    assert all(c["value"] == 0 for name, c in checks.items()
               if name != "device_bytes_wrong"), checks


# ------------------------------------------------------ the plain reference


def test_the_real_dataset_is_the_sources_shape():
    cfg = _real_cfg()
    ds, src = cfg["dataset"], cfg["source_as_recalled"]
    assert ds["record_bytes"] == src["record_length_bytes"] == 114_660
    assert ds["samples_per_shard"] == src["num_samples_per_file"] == 1251
    assert ds["batch_size"] == src["batch_size"] == 400
    assert ds["shards"] == 8 and src["num_files_train"] == 1024
    assert reference_wds.samples(cfg) == 10_008
    assert cfg["reduced"] == ["dataset"] and "8 of" in cfg["reduced_why"]
    assert ds["record_bytes"] % 4 == 0  # the digest's words
    assert any("no end-to-end CRC" in g for g in cfg["guarantees"])
    for key in ("container", "shuffle", "read_threads",
                "prefetch_buffer_records", "computation_time_s", "decode",
                "workers", "ec"):
        assert f"{key}_why" in cfg["assumed"], key
    # ha-3m5cs-r3's cluster to the letter.
    ha = json.loads((REPO / "benchmarks" / "configs"
                     / "ha-3m5cs-r3.json").read_text())
    for key in ("bringup", "masters", "chunkservers", "racks", "replication",
                "block_bytes", "engine", "chips"):
        assert cfg[key] == ha[key], key


def test_a_real_sample_is_116_224_bytes_of_tar_and_a_shard_139_blocks():
    cfg = _real_cfg()
    cut = {**cfg, "dataset": {**cfg["dataset"], "samples_per_shard": 3}}
    tar = reference_wds.shard_tar(77, cut, 1)
    assert len(tar) == 10_240 * math.ceil((3 * 116_224 + 1024) / 10_240)
    assert tar[:12] == b"00000003.img"  # shard 1 starts at key 3
    assert tar[512:512 + 114_660] == reference_wds.image(77, cut, 3)
    label = int(reference_wds.labels(77, cut)[3])
    at = 512 + 114_688
    assert tar[at:at + 12] == b"00000003.cls"
    assert tar[at + 512:at + 512 + len(str(label))] == str(label).encode()
    whole = 10_240 * math.ceil((1251 * 116_224 + 1024) / 10_240)
    assert whole == 145_397_760
    assert math.ceil(whole / cfg["block_bytes"]) == 139
    assert whole % cfg["block_bytes"] == 694_272
    assert 400 * (114_660 + 4) == 45_865_600


def test_samples_are_a_function_of_seed_and_key():
    cfg = _real_cfg()
    big = 2**31 + 99
    assert reference_wds.image(big, cfg, 5) == reference_wds.image(big, cfg, 5)
    assert reference_wds.image(big, cfg, 5) != reference_wds.image(big, cfg, 6)
    assert reference_wds.image(big, cfg, 5) != reference_wds.image(1, cfg, 5)
    labels = reference_wds.labels(big, cfg)
    assert labels.shape == (10_008,) and labels.dtype == np.int32
    assert 0 <= labels.min() and labels.max() <= 999
    assert len(set(labels.tolist())) > 900


# ----------------------------------------------------------- the yardstick


def test_the_device_digest_is_the_references():
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 256, (6, 1000), dtype=np.uint8)
    want = reference_wds.digest(rows)
    got = np.asarray(infeed_kind.infeed_digest(jax.numpy.asarray(rows)))
    assert got.dtype == np.uint32 and np.array_equal(got, want)
    # By hand, on one row of two words.
    two = np.array([[1, 0, 0, 0, 0, 0, 0, 128]], dtype=np.uint8)
    assert reference_wds.digest(two).tolist() \
        == [[1 + 2 * 0x80000000 & 0xFFFFFFFF, 1 ^ 0x80000000]]
    # A byte that changed place within its word, two words exchanged, one
    # bit anywhere: each moves the digest.
    for change in (lambda r: r[:, [1, 0] + list(range(2, 1000))],
                   lambda r: r[:, list(range(4, 8)) + list(range(4))
                               + list(range(8, 1000))],
                   lambda r: r ^ np.eye(1, 1000, 777, dtype=np.uint8) * 4):
        assert not np.array_equal(reference_wds.digest(change(rows)), want)


def test_the_digest_program_lowers_under_its_module_name():
    text = infeed_kind.infeed_digest.lower(
        jax.ShapeDtypeStruct((4, 1000), np.uint8)).as_text()
    assert "module @jit_infeed_digest " in text, text[:200]


def test_span_readers_take_means_and_the_gates_share():
    win = _with_spans(_Win(), [
        _Span("infeed.fetch", 0, 8_000_000, span_id=1),
        _Span("infeed.gate_wait", 0, 1_000_000, parent_id=1),
        _Span("infeed.fetch", 0, 12_000_000, span_id=2),
        _Span("infeed.gate_wait", 0, 4_000_000, parent_id=2),
        _Span("infeed.gate_wait", 0, 9_000_000, parent_id=None),  # index's
        _Span("infeed.collate", 0, 30_000_000, records=400),
        _Span("infeed.collate", 0, 10_000_000, records=400),
        _Span("infeed.device_put", 0, 14_000_000),
    ])
    win.counters = {"infeed.range_reads": 111, "infeed.records": 100}
    assert infeed_fetch_ms_per_record.read(win) == pytest.approx(10.0)
    assert infeed_gate_wait_pct.read(win) == pytest.approx(25.0)
    assert infeed_collate_ms_per_batch.read(win) == pytest.approx(20.0)
    assert infeed_device_put_ms_per_batch.read(win) == pytest.approx(14.0)
    assert infeed_range_reads_per_record.read(win) == pytest.approx(1.11)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_reader_finds_nothing_without_spans_or_counters(name):
    win = _Win()
    assert NEW[name].read(win) is None
    # Spans and counters of another cell, and a CPU trace: still nothing.
    win = _with_spans(_Win(), [_Span("hbm.read_file", 0, 10, span_id=5)])
    win.counters = {"combiner.blocks": 64, "infeed.records": 0,
                    "infeed.range_reads": 0}
    win.trace = trace_reduce.Trace([], 0.0)
    assert NEW[name].read(win) is None


def test_benchmark_json_lists_the_cell_its_metrics_and_the_control():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    assert bench["workloads"][-1] is cell  # appended, nothing moved
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"hbm_read_GBps", "read_p95_ms", "h2d_raw_GBps",
                      "cs_cache_hit_pct", *NEW}
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["source"] != "program_span"
    config = bench["configs"][-1]
    assert config["name"] == CONFIG and config["reduced"] == ["dataset"]
    assert config["source"] == _real_cfg()["source"] \
        and len(config["source"]) <= 200
    loaded = harness.load_cell(CELL)
    assert loaded["mix"]["kind"] == infeed_kind.KIND
    assert loaded["mix"]["consumers"] == 1
    assert not loaded["mix"]["local_reads"]
    assert infeed_kind.KIND in sabotage.CONTROLS
    assert jax.devices()[0].platform == "cpu"  # rehearsal only


def test_the_kind_fails_at_import_on_a_program_without_the_pipeline(
        monkeypatch):
    """The parent of the PR that brought the cell: no ``HANDOFF_DEPTH``, so
    the kind's module does not import and the run ends before any set-up."""
    import importlib

    from tpudfs.tpu import grain_infeed

    monkeypatch.delattr(grain_infeed, "HANDOFF_DEPTH")
    with pytest.raises(ImportError, match="HANDOFF_DEPTH"):
        importlib.reload(infeed_kind)
    monkeypatch.undo()
    importlib.reload(infeed_kind)
    assert sabotage.CONTROLS[infeed_kind.KIND] \
        is infeed_kind.RowsExchangedAfterFetch


def test_the_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmarks" / "reference_wds.py").read_text()
    assert "tpudfs" not in text.replace("``tpudfs``", "") \
        and "native" not in text.replace("``native/``", "")
