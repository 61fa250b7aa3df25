"""Shared by the two rehearsal files: every cell the benchmark's files
define, cut to a size a test can hold, run on the CPU's virtual devices
with the harness's look for a chip stubbed HERE (``run.py`` has no option
for it). The served deployment is the smallest that keeps 3 replicas
(1 master + 3 chunkservers as OS processes); the ring runs on 4 virtual
devices.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax

from benchmarks import deployments, harness, peaks

REPO = Path(__file__).resolve().parent.parent.parent
KIB = 1024

TINY_CONFIGS = {
    "ha-3m5cs-r3": {"masters": 1, "chunkservers": 3, "block_bytes": 64 * KIB,
                    "dataset": {"files": 4, "file_bytes": 256 * KIB}},
    "ici-ring4-r3": {"masters": 1, "block_bytes": 64 * KIB},
}
TINY_MIXES = {
    "remote_read_16x64m": {"clients": 3, "check_files": 2,
                           "check_replica_blocks": 4,
                           "trace_seconds": 1},
    "colocated_sweep_epochs": {"check_files": 2, "check_replica_blocks": 4,
                               "trace_seconds": 1},
    "stress_write_10x1m": {"clients": 3, "file_bytes": 64 * KIB,
                           "payloads": 4, "check_puts": 6,
                           "trace_seconds": 1},
}
#: cells the benchmark's files define, proved on the chip or not yet
CELLS = {
    "ha_remote_read": ("ha-3m5cs-r3", "remote_read_16x64m", 1),
    "ha_colocated_sweep": ("ha-3m5cs-r3", "colocated_sweep_epochs", 1),
    "ici_ring_write": ("ici-ring4-r3", "stress_write_10x1m", 4),
    "ha_stress_write": ("ha-3m5cs-r3", "stress_write_10x1m", 1),
}
#: every end-to-end metric a traffic kind can report, whether or not the
#: repo's BENCHMARK.json lists its cell yet
END_TO_END = {"hbm_read_GBps": "GB/s", "read_p95_ms": "ms",
              "write_MBps": "MB/s", "write_p95_ms": "ms", "setup_s": "s"}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


class InprocChain(deployments.InprocIciRing):
    """Stand-in for ``served_processes`` where a file makes many runs (the
    fault tests): the same masters, chunkservers and client in this
    process, no write group, so puts ride the TCP chain. Starting nine
    server processes per run would starve the other workers' tests."""

    name = "inproc_chain"

    async def ready(self, devices: list, rpc) -> None:
        from tpudfs.testing.inproc import InprocCluster

        self.cluster = InprocCluster(
            str(self.root / "cluster"), n_masters=self.cfg["masters"],
            n_cs=self.cfg["chunkservers"])
        await self.cluster.start()
        await self.cluster.ready()

    def local_counters(self) -> dict:
        return {}


def make_tiny_root(tmp_path_factory, ha_bringup: str = "served_processes"
                   ) -> Path:
    """A root with the repo's BENCHMARK.json and data files cut to a size
    a test can hold; every cell the files define is listed, so the ones
    PERF.md keeps for later are rehearsed too."""
    root = tmp_path_factory.mktemp("tiny-root")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmarks" / "configs").mkdir(parents=True)
    (root / "benchmarks" / "workloads").mkdir()
    bench["configs"] = []
    for name, cut in TINY_CONFIGS.items():
        file = f"benchmarks/configs/{name}.json"
        cfg = json.loads((REPO / file).read_text())
        cfg.update(cut)
        if cfg["bringup"] == "served_processes":
            cfg["bringup"] = ha_bringup
        (root / file).write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "file": file})
    for name, cut in TINY_MIXES.items():
        file = f"benchmarks/workloads/{name}.json"
        mix = json.loads((REPO / file).read_text())
        mix.update(cut)
        (root / file).write_text(json.dumps(mix))
    bench["workloads"] = [
        {"name": cell, "config": config, "traffic": traffic, "chips": chips}
        for cell, (config, traffic, chips) in CELLS.items()]
    # Every metric in every cell: a reader that finds nothing says so.
    bench["end_to_end"] = [{"name": n, "unit": u}
                           for n, u in END_TO_END.items()]
    bench["per_layer"] = [
        {"name": f.stem, "unit": "x"} for f in sorted(
            (REPO / "benchmarks" / "layer_metrics").glob("*.py"))
        if f.stem != "__init__"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def stub_chip(monkeypatch) -> None:
    """The stub: the CPU's virtual devices stand in for the chips, and the
    peaks table gets a row for them (it refuses an unknown kind)."""
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    # No persistent cache for the test worker's other files to inherit.
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)
    monkeypatch.setitem(deployments.BRINGUPS, InprocChain.name, InprocChain)
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])


async def run(tiny_root, cell: str, *, trace: bool = False, fault=None,
              seed: int = 2**31 + 12345, seconds: float = 1.0) -> dict:
    line = await harness.run_cell(cell, seed, seconds, trace,
                                  time.perf_counter(), sabotage=fault,
                                  root=tiny_root)
    json.dumps(line)  # the line is printable as it stands
    return line
