"""chip_smoke.py's phases rehearsed on the CPU's virtual devices at a tiny
size (on-chip-measurement §2, rehearsals 1 and 2), so a wrong path, argument
or sharding rule costs no chip time. The script itself has no CPU mode:
the last test holds it to that.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIB = 1024


def test_phase_a_device_programs_match_host_references():
    out = chip_smoke.phase_a(
        jax.devices()[0], seed=3, block_bytes=64 * KIB, batch_blocks=4,
        rs_block_bytes=300_001, ici_bytes=64 * KIB)
    names = [p["program"] for p in out["programs"]]
    assert names == [
        "crc32c_chunks_device", "block_crc_device",
        "batch_block_crc_device(4)", "verify_block_device",
        "rs_encode_device RS(6,3)",
        "rs_decode_device RS(6,3) missing (4,6)",
        "replicated_write_step(1 devices, R=3)"]
    # Interpret mode here: the text check is reported, never satisfied.
    assert not any(p["tpu_custom_call"] for p in out["programs"])
    assert all(p["argument_bytes"] > 0 for p in out["programs"])


def test_missing_kernel_fails_the_run_on_the_chip():
    """run_checked's guard: a TPU device whose compiled text carries no
    tpu_custom_call is an error, not a line in the report."""

    class FakeTpu:
        platform = "tpu"

    jitted = jax.jit(lambda x: x + 1)
    with pytest.raises(AssertionError, match="no tpu_custom_call"):
        chip_smoke.run_checked("plain_add", jitted, (jax.numpy.ones(8),),
                               device=FakeTpu())


def test_h2d_probe_reports_both_sides_of_the_first_d2h():
    out = chip_smoke.h2d_around_first_d2h(
        jax.devices()[0], seed=3, buffers=4, nbytes=64 * KIB)
    assert len(out["h2d_GBps_before_first_d2h"]) == 3
    assert len(out["h2d_GBps_after_first_d2h"]) == 3
    assert out["median_before"] > 0 and out["median_after"] > 0


def test_phase_ring_each_device_holds_its_ring_predecessors():
    out = chip_smoke.phase_ring(jax.devices()[:4], seed=3,
                                ici_bytes=64 * KIB)
    assert out["acks"] == 4 and out["replication"] == 3


def test_phase_ec_degraded_gather_is_bit_exact():
    out = chip_smoke.phase_ec(jax.devices()[:4], seed=3, ici_bytes=64 * KIB)
    assert out["acks"] == 4 and out["rs"] == [2, 2]


def _phases(capsys) -> dict:
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    return {ln["phase"]: ln for ln in lines}


def test_run_four_chips_cross_chip_path_and_its_tcp_twin(capsys):
    """The whole ``--chips 4`` run as ``main`` drives it: ring, EC, the live
    collective write and the TCP chain it is compared with — nothing else."""
    report = chip_smoke.Report(None, chip_smoke.CompileClock())
    chip_smoke.run_four_chips(
        jax.devices()[:4], report, seed=3, sizes={"ici_bytes": 64 * KIB},
        live={"puts": 8, "block_bytes": 64 * KIB})
    out = _phases(capsys)
    assert list(out) == ["X.ring_replication", "X.ec_scatter_gather",
                         "X.live_collective_write",
                         "X.tcp_chain_comparison", "X.ici_vs_tcp"]
    ici, tcp = out["X.live_collective_write"], out["X.tcp_chain_comparison"]
    assert ici["path"] == "ici-write-group" and ici["rounds"] >= 1
    assert ici["ici_blocks"] == 8 and ici["ici_fallbacks"] == 0
    assert tcp["path"] == "tcp-chain" and "rounds" not in tcp
    assert ici["bytes"] == tcp["bytes"] == 8 * 64 * KIB
    assert ici["block_crcs_sha256"] == tcp["block_crcs_sha256"]
    assert all("seconds" in out[p] and "compile_seconds" in out[p]
               for p in list(out)[:4])
    assert out["X.ici_vs_tcp"]["same_bytes_crcs_replicas"]


def test_run_one_chip_served_path_through_start_cluster(capsys, tmp_path):
    """The whole default run as ``main`` drives it: the served write ->
    guarantee checks -> both reads into device memory go through
    scripts/start_cluster.py (one master and three chunkservers here: the
    replication set)."""
    report = chip_smoke.Report(tmp_path / "out.jsonl",
                               chip_smoke.CompileClock())
    chip_smoke.run_one_chip(
        jax.devices()[0], report, seed=3, files=2,
        cache={"dir": str(tmp_path / "no-cache"), "entries_at_start": 0},
        h2d={"buffers": 4, "nbytes": 64 * KIB},
        a_sizes={"block_bytes": 64 * KIB, "batch_blocks": 4,
                 "rs_block_bytes": 300_001, "ici_bytes": 64 * KIB},
        b_sizes={"file_bytes": 2 << 20, "block_bytes": 512 * KIB,
                 "masters": 1, "chunkservers": 3})
    out = _phases(capsys)
    assert list(out) == ["C.h2d_around_first_d2h", "A.device_programs",
                         "B.served_path", "C.compile_cache"]
    assert (tmp_path / "out.jsonl").read_text().count("\n") == 4
    b = out["B.served_path"]
    assert b["seconds"] > b["write"]["seconds"] > 0
    assert b["cluster"]["native_engine_on_every_chunkserver"]
    assert b["cluster"]["server_processes_jax_free"] >= 5
    assert b["guarantees"]["replica_reads_with_recorded_crc"] == 3 * 8
    assert b["read_remote"]["blocks_held"] == 8
    assert b["read_remote"]["rounds"] > 0
    assert b["read_colocated"]["verify"] == "host-crc32c(sweep-pump)"
    assert b["read_colocated"]["sweep_blocks"] == 8
    assert out["C.compile_cache"]["compiles"] > 0


@pytest.mark.parametrize("chips", [1, 4])
def test_main_wiring_and_contract_last_line(chips, monkeypatch, capsys,
                                            tmp_path):
    """``main`` past the device check, with the phases stubbed: the option
    selects exactly one orchestrator, and the last line is the contract's
    object with the device as JAX reports it."""
    import tpudfs.tpu

    calls = []
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda n: jax.devices()[:max(n, 1)])
    monkeypatch.setattr(tpudfs.tpu, "place_compile_cache",
                        lambda: str(tmp_path / "cache"))
    monkeypatch.setattr(chip_smoke, "OUT_DIR", tmp_path / "out")
    monkeypatch.setattr(chip_smoke, "run_one_chip",
                        lambda dev, report, **kw: calls.append(("one", kw)))
    monkeypatch.setattr(
        chip_smoke, "run_four_chips",
        lambda ring, report, **kw: calls.append(("four", len(ring))))
    chip_smoke.main(["--chips", str(chips), "--seed", "5"])
    lines = capsys.readouterr().out.splitlines()
    first = jax.devices()[0]
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": chips}}
    if chips == 4:
        assert calls == [("four", 4)]
    else:
        (name, kw), = calls
        assert name == "one" and kw["seed"] == 5
        assert kw["files"] * chip_smoke.FILE_BYTES == 2 << 30
    assert (tmp_path / "out" / f"chip_smoke_{chips}chip.jsonl").exists()


def test_place_compile_cache_env_wins_else_the_checkout(monkeypatch):
    import tpudfs.tpu

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert tpudfs.tpu.place_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == before  # set nothing
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = tpudfs.tpu.place_compile_cache()
        assert placed == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_free_port_leaves_room_for_the_ops_twin():
    """Servers bind rpc port + 1000 for ops HTTP; a pick above 64535 (the
    chip machine's ephemeral range reaches 65535) killed one cluster start
    in five there."""
    import socket

    from tpudfs.testing import procs

    assert not procs.ops_twin_free(65536 - procs.OPS_PORT_OFFSET)
    with socket.socket() as held:
        held.bind(("127.0.0.1", 0))
        assert not procs.ops_twin_free(
            held.getsockname()[1] - procs.OPS_PORT_OFFSET)
    for _ in range(50):
        assert procs.free_port() + procs.OPS_PORT_OFFSET <= 65535


def test_script_off_the_chip_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not 'tpu'" in proc.stderr
