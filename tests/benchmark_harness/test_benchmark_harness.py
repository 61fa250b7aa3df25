"""The benchmark's own yardstick, on the CPU: the plain reference's CRC32C
against known vectors, the trace reduction on a small recorded v5e trace,
the peaks table, the window arithmetic, and BENCHMARK.json against its
files and the contract's character rules."""

from __future__ import annotations

import json
import math
import re
from pathlib import Path

import pytest

from benchmarks import harness, peaks, reference, trace_reduce
from benchmarks.layer_metrics import crc_verify_roofline_pct

REPO = Path(__file__).resolve().parent.parent.parent
HERE = Path(__file__).resolve().parent
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------- reference


@pytest.mark.parametrize("data,crc", [
    (b"", 0x00000000),
    (b"123456789", 0xE3069283),
    (bytes(32), 0x8A9136AA),                # RFC 3720 B.4
    (b"\xff" * 32, 0x62A8AB43),
    (bytes(range(32)), 0x46DD794E),
    (bytes(range(31, -1, -1)), 0x113FDB5C),
])
def test_reference_crc32c_known_vectors(data, crc):
    assert reference.crc32c(data) == crc


def test_reference_block_crcs_agree_with_the_bytewise_loop():
    data = reference.seeded_bytes(2**31 + 7, 3, 3 * 2048 + 700)
    got = reference.crc32c_blocks(data, 2048)
    assert got == [reference.crc32c(data[o:o + 2048])
                   for o in range(0, len(data), 2048)]
    assert len(got) == 4


def test_seeded_bytes_repeat_and_differ_by_stream_and_large_seed():
    a = reference.seeded_bytes(2**31 + 5, 100, 4096)
    assert a == reference.seeded_bytes(2**31 + 5, 100, 4096)
    assert a != reference.seeded_bytes(2**31 + 5, 101, 4096)
    assert a != reference.seeded_bytes(2**31 + 6, 100, 4096)
    assert len(reference.seeded_bytes(1, 1, 1001)) == 1001


def test_expected_file_states_sizes_crcs_and_replicas():
    data = reference.seeded_bytes(9, 1, 2048 + 512)
    want = reference.expected_file(data, 2048, 3)
    assert want["size"] == 2560 and want["block_sizes"] == [2048, 512]
    assert want["replicas"] == 3 and len(want["block_crcs"]) == 2


# -------------------------------------------------------- trace reduction


@pytest.fixture(scope="module")
def tiny_trace() -> trace_reduce.Trace:
    """Recorded on one v5e (my chip run, PR 24): three times device_put of
    16 MiB then jit_batch_block_crc_device over it, 10 ms apart."""
    return trace_reduce.load(str(HERE / "tiny_v5e.xplane.pb"))


def test_trace_has_one_device_the_mark_and_the_program_by_name(tiny_trace):
    assert [d.name for d in tiny_trace.devices] == ["/device:TPU:0"]
    assert tiny_trace.mark_ns is not None
    times = trace_reduce.program_times(tiny_trace, 0, math.inf)
    assert set(times) == {"jit_batch_block_crc_device"}
    calls, seconds = times["jit_batch_block_crc_device"]
    assert calls == 3 and 0.00055 < seconds < 0.0006


def test_busy_is_the_union_of_op_intervals_not_their_sum(tiny_trace):
    dev = tiny_trace.devices[0]
    busy = trace_reduce.busy_seconds(tiny_trace, 0, math.inf)
    summed = sum(b - a for a, b, _ in dev.ops) / 1e9
    modules = sum(b - a for a, b, _ in dev.modules) / 1e9
    assert 0 < busy <= summed and busy <= modules * 1.001
    # Clipped to the first program only: a third of the whole.
    first_end = dev.modules[0][1]
    part = trace_reduce.busy_seconds(tiny_trace, 0, first_end)
    assert abs(part - busy / 3) < busy * 0.02


def test_idle_gaps_go_to_the_span_that_covers_them(tiny_trace):
    dev = tiny_trace.devices[0]
    lo, hi = dev.modules[0][0], dev.modules[-1][1]
    gap = (dev.modules[0][1], dev.modules[1][0])
    rows = trace_reduce.idle_by_host_activity(
        tiny_trace, lo, hi, [("in_sleep", gap[0], gap[1])])
    names = dict((n, s) for n, s in rows)
    assert set(names) == {"in_sleep", "no_span"}
    assert abs(names["in_sleep"] - (gap[1] - gap[0]) / 1e9) < 1e-4
    idle = (hi - lo) / 1e9 - trace_reduce.busy_seconds(tiny_trace, lo, hi)
    assert abs(sum(names.values()) - idle) < 1e-6
    top = trace_reduce.top_ops(tiny_trace, lo, hi)
    assert top[0][0] == "%_crc_pallas.1 custom-call [tpu_custom_call]"
    assert len(top) <= 10


def test_union_and_clip():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace_reduce.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def test_crc_roofline_reader_on_the_recorded_trace(tiny_trace):
    """3 x 16 blocks of 1 MiB verified in 3 x 0.191 ms: 10.7% of 819 GB/s;
    a reader that finds no program in the trace returns nothing."""

    class Win:
        trace = tiny_trace
        lo_ns, hi_ns = 0, math.inf
        peaks = peaks.peaks_for("TPU v5 lite")

        class ctx:
            cfg = {"block_bytes": 1 << 20}

        def trace_delta(self, key):
            return 48

    got = crc_verify_roofline_pct.read(Win())
    assert 10.0 < got < 11.5
    Win.hi_ns = 1.0  # nothing ran before the first nanosecond
    assert crc_verify_roofline_pct.read(Win()) is None


# ------------------------------------------------------------------ peaks


def test_peaks_table_refuses_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.crc_verify_min_seconds(819_000_000, "TPU v5 lite") \
        == pytest.approx(1e-3)


# ------------------------------------------------------ window arithmetic


def test_rate_counts_only_finished_successes_over_the_whole_window():
    ops = [harness.Op(0.0, 1.0, True, 100), harness.Op(0.5, 2.5, True, 100),
           harness.Op(1.0, 1.5, False, 0), harness.Op(1.0, 2.0, True, 50)]
    assert harness.rate(ops, 0.0, 2.0) == 75.0


def test_p95_is_over_all_operations_and_a_failure_sorts_last():
    ops = [harness.Op(0, i / 1000, True, 1) for i in range(1, 101)]
    assert harness.p95_ms(ops) == pytest.approx(95.0)
    ops[0] = harness.Op(0, 0.001, False, 0)
    assert harness.p95_ms(ops) == pytest.approx(96.0)
    assert harness.p95_ms(
        [harness.Op(0, 1, False, 0)] * 10) == harness.MISSED_MS


def test_require_devices_exits_without_a_tpu():
    with pytest.raises(SystemExit, match="not 'tpu'"):
        harness.require_devices(1)


# --------------------------------------------------------- BENCHMARK.json


def test_benchmark_json_keys_paths_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks", "tests/benchmark_harness"]
    assert BENCH["command"][1] == "benchmarks/run.py"
    assert isinstance(BENCH["run_seconds"], int) \
        and 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_name_and_unit_uses_only_the_allowed_characters():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        listed = [x["name"] for x in BENCH[key]]
        assert len(listed) == len(set(listed))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200
    files = [p.relative_to(REPO).as_posix() for root in BENCH["paths"]
             for p in (REPO / root).rglob("*")
             if p.is_file() and "__pycache__" not in p.parts]
    assert all(re.fullmatch(r"[A-Za-z0-9_.\-/]+", f) for f in files), files


def test_every_file_named_in_benchmark_json_exists():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and "assumed" in cfg
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        mix = json.loads((REPO / "benchmarks" / "workloads"
                          / f"{w['traffic']}.json").read_text())
        assert (REPO / "benchmarks" / "traffic"
                / f"{mix['kind']}.py").exists()
        assert configs[w["config"]]  # every cell's configuration is listed
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(configs)
    for m in BENCH["per_layer"]:
        assert (REPO / "benchmarks" / "layer_metrics"
                / f"{m['name']}.py").exists()


def test_metrics_name_cells_that_exist_and_layers_moves_hold():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        reported = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reported
    for cell in cells:  # setup_s, one more end-to-end, one per-layer
        assert any(cell in m.get("workloads", cells)
                   for m in BENCH["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in BENCH["per_layer"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(cells) // 2)
