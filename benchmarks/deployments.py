"""The two bring-ups a configuration can name under ``"bringup"``.

``served_processes``: ``scripts/start_cluster.py`` as a child process —
config server, Raft masters and chunkservers as separate OS processes that
never import JAX (checked from ``/proc/<pid>/maps``), native C++ engine on
every chunkserver. ``inproc_ici_ring``: masters and chunkservers as asyncio
services in THIS process (``tpudfs.testing.inproc.InprocCluster``), one
chunkserver per device of one ``IciWriteGroup``.

Both give the harness the same few things: a ``Client`` on the benchmark's
own ``RpcClient``, counters (``local_counters`` costs no RPC), the block store
under a chunkserver, and a teardown that leaves no process behind.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
CS_SERVICE = "ChunkServerService"


def build_native() -> None:
    """Build (a no-op when fresh) and load the native library in THIS
    process before any server starts, so five chunkservers never race a
    compiler in a fresh checkout."""
    from tpudfs.common import native

    if native.build_and_load() is None or not native.has_dataplane():
        raise RuntimeError("native library did not build or load; the "
                           "served path needs its C++ data plane")


class ServedProcesses:
    name = "served_processes"

    def __init__(self, cfg: dict, workdir: Path):
        self.cfg = cfg
        self.root = workdir
        self.ready_file = workdir / "ready.json"
        self.endpoints: dict = {}
        self.launcher: subprocess.Popen | None = None

    def launch(self) -> None:
        """Start the launcher and return at once: the servers come up
        while this process starts JAX."""
        build_native()
        topology = self.root / "topology.json"
        topology.write_text(json.dumps({
            "name": self.cfg["name"],
            "shards": [{"id": "shard-0", "masters": self.cfg["masters"]}],
            "chunkservers": self.cfg["chunkservers"],
            "racks": self.cfg.get("racks", 3),
            "s3": False,  # no cell talks to the gateway
        }))
        with open(self.root / "launcher.err", "w") as err:
            self.launcher = subprocess.Popen(
                [sys.executable, str(REPO / "scripts" / "start_cluster.py"),
                 "--topology", str(topology),
                 "--data-dir", str(self.root / "cluster"),
                 "--ready-file", str(self.ready_file)],
                cwd=str(REPO), stdout=subprocess.DEVNULL, stderr=err)

    async def ready(self, devices: list, rpc) -> None:
        deadline = time.monotonic() + 180.0
        while True:
            if self.ready_file.exists():
                text = self.ready_file.read_text()
                if text.endswith("}"):
                    self.endpoints = json.loads(text)
                    break
            if self.launcher.poll() is not None:
                raise RuntimeError("start_cluster.py exited early: "
                                   + self._logs())
            if time.monotonic() > deadline:
                raise RuntimeError("cluster not ready in time" + self._logs())
            await asyncio.sleep(0.1)
        self.assert_servers_jax_free()
        for addr in self.endpoints["chunkservers"]:
            hello = await rpc.call(addr, CS_SERVICE, "DataPort", {},
                                   timeout=10.0)
            if not hello.get("native") or not hello.get("port"):
                raise RuntimeError(
                    f"chunkserver {addr} answers DataPort {hello}: the "
                    "native C++ engine is not serving")

    def _logs(self) -> str:
        out = [(self.root / "launcher.err").read_text()[-2000:]]
        for log in sorted((self.root / "cluster" / "logs").glob("*.log")):
            text = log.read_text(errors="replace")
            if "READY" not in text:
                out.append(f"\n--- {log.name} ---\n{text[-2000:]}")
        return "".join(out)

    def assert_servers_jax_free(self) -> None:
        """No server process maps libtpu or jaxlib: the chip has exactly
        one owner, this process."""
        for pid in self.endpoints["pids"]:
            for line in Path(f"/proc/{pid}/maps").read_text().splitlines():
                mapped = line.split(None, 5)[-1] if "/" in line else ""
                name = os.path.basename(mapped)
                if "/jaxlib/" in mapped or (
                        name.startswith("libtpu")
                        and not name.startswith("libtpudfs")):
                    raise AssertionError(
                        f"server pid {pid} maps {mapped}: a second process "
                        "could take the chip")

    def client(self, rpc, *, local_reads: bool):
        from tpudfs.client.client import Client

        return Client(self.endpoints["shards"]["shard-0"],
                      [self.endpoints["config_server"]], rpc_client=rpc,
                      block_size=self.cfg["block_bytes"],
                      local_reads=local_reads)

    def local_counters(self) -> dict:
        return {}

    async def counters(self, rpc) -> dict:
        """Sums over chunkservers of the program's own ``Stats``."""
        out: dict[str, float] = {}
        for stats in await asyncio.gather(*(
                rpc.call(addr, CS_SERVICE, "Stats", {}, timeout=10.0)
                for addr in self.endpoints["chunkservers"])):
            for key in ("cache_hits", "cache_misses"):
                out[f"cs.{key}"] = out.get(f"cs.{key}", 0) + stats[key]
            for group in ("stream_stages", "write_stages"):
                for key, val in (stats.get(group) or {}).items():
                    out[f"cs.{group}.{key}"] = \
                        out.get(f"cs.{group}.{key}", 0) + val
        return out

    def store_of(self, addr: str):
        """The block store under chunkserver ``addr``, opened from this
        process (controls and tests plant faults through it)."""
        from tpudfs.chunkserver.blockstore import BlockStore

        for name, proc in self.endpoints["procs"].items():
            if proc["addr"] == addr and name.startswith("cs"):
                return BlockStore(self.root / "cluster" / name)
        raise KeyError(addr)

    async def stop(self) -> None:
        if self.launcher is not None and self.launcher.poll() is None:
            self.launcher.terminate()
            try:
                await asyncio.to_thread(self.launcher.wait, 20)
            except subprocess.TimeoutExpired:
                self.launcher.kill()
                self.launcher.wait()
        for pid in self.endpoints.get("pids") or []:
            with contextlib.suppress(ProcessLookupError):
                os.kill(pid, signal.SIGKILL)
        for pid in self.endpoints.get("pids") or []:
            # The servers are the launcher's children, not ours: wait for
            # the kernel to have let each go before the run says it ended.
            for _ in range(200):
                if not Path(f"/proc/{pid}").exists():
                    break
                await asyncio.sleep(0.05)


async def read_replica(rpc, addr: str, block_id: str) -> bytes:
    """One named replica's copy of a block, asked of its chunkserver
    directly (the same RPC under either bring-up)."""
    resp = await rpc.call(addr, CS_SERVICE, "ReadBlock",
                          {"block_id": block_id, "offset": 0, "length": 0},
                          timeout=60.0)
    if "data_parts" in resp:
        return b"".join(bytes(p) for p in resp["data_parts"])
    return bytes(resp["data"])


class InprocIciRing:
    name = "inproc_ici_ring"

    def __init__(self, cfg: dict, workdir: Path):
        self.cfg = cfg
        self.root = workdir
        self.cluster = None
        self.group = None

    def launch(self) -> None:
        build_native()

    async def ready(self, devices: list, rpc) -> None:
        from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE
        from tpudfs.testing.inproc import InprocCluster
        from tpudfs.tpu.ici_replication import make_mesh
        from tpudfs.tpu.write_group import IciWriteGroup

        n = self.cfg["chunkservers"]
        if len(devices) != n:
            raise RuntimeError(f"{self.cfg['name']} wants one chunkserver "
                               f"per device: {n} != {len(devices)}")
        self.cluster = InprocCluster(str(self.root / "cluster"),
                                     n_masters=self.cfg["masters"], n_cs=n)
        await self.cluster.start()
        self.group = IciWriteGroup(
            make_mesh(devices),
            [cs.address for cs in self.cluster.chunkservers],
            replication=self.cfg["replication"])
        for i, cs in enumerate(self.cluster.chunkservers):
            cs.attach_ici_group(self.group, i)
        await asyncio.to_thread(
            self.group.warm, self.cfg["block_bytes"] // CHECKSUM_CHUNK_SIZE)
        await self.cluster.ready()

    def client(self, rpc, *, local_reads: bool):
        from tpudfs.client.client import Client

        return Client(list(self.cluster.masters), rpc_client=rpc,
                      block_size=self.cfg["block_bytes"],
                      local_reads=local_reads)

    async def counters(self, rpc) -> dict:
        return self.local_counters()

    def local_counters(self) -> dict:
        s = self.group.stats
        return {
            "ici.rounds": s.rounds, "ici.blocks": s.blocks,
            "ici.bytes": s.bytes, "ici.round_failures": s.round_failures,
            "ici.persist_failures": s.persist_failures,
            "ici.fallbacks": sum(cs.ici_fallbacks
                                 for cs in self.cluster.chunkservers),
        }

    def store_of(self, addr: str):
        for cs in self.cluster.chunkservers:
            if cs.address == addr:
                return cs.store
        raise KeyError(addr)

    async def stop(self) -> None:
        if self.group is not None:
            await self.group.stop()
        if self.cluster is not None:
            await self.cluster.stop()


BRINGUPS = {cls.name: cls for cls in (ServedProcesses, InprocIciRing)}
