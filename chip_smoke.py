"""chip_smoke.py — the quickest proof that tpudfs still starts on the chip.

Run with no arguments on a machine with ONE TPU chip::

    python chip_smoke.py            # phases A, B, C on one chip
    python chip_smoke.py --chips 4  # ONLY the cross-chip phase + its TCP twin

One process holds the chip — this one (``Client`` + ``HbmReader``, the
Pallas kernels, the ``ppermute`` programs). Config server, masters and
chunkservers are separate OS processes that never import JAX; the script
asserts that from ``/proc/<pid>/maps`` instead of assuming it.

There is no CPU mode: the first thing ``main`` does is check
``jax.devices()[0].platform == "tpu"`` and exit non-zero otherwise, and no
phase's failure is caught. The phases are plain functions taking their
devices and sizes, which is how ``tests/test_chip_smoke.py`` rehearses
them on the CPU's virtual devices at a tiny size.

Output: one JSON object per phase on its own line (seconds, compile
seconds, memory), mirrored to ``chiprun_out/chip_smoke_<N>chip.jsonl``; the
last line of stdout is ``{"ok": true, "device": {...}}`` as JAX reports the
device.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
OUT_DIR = REPO / "chiprun_out"
MIB = 1 << 20

#: Phase B's served dataset: 32 files x 64 MiB at 1 MiB blocks = 2 GiB
#: logical / 2 048 blocks (BASELINE.json's 1 MiB metric block, config 2's
#: 3 masters + 5 chunkservers).
FILE_BYTES = 64 * MIB
BLOCK_BYTES = MIB
MASTERS, CHUNKSERVERS = 3, 5
WRITE_CONCURRENCY = 16
REMOTE_SWEEP_CONCURRENCY = 16
BATCH_READS = 16


# ----------------------------------------------------------------- reporting


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache hits count
    with their retrieval time, so a warm run reads lower)."""

    def __init__(self) -> None:
        import jax.monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.seconds = 0.0
        self.count = 0

        def on_event(event: str, duration: float, **_kw) -> None:
            if event == BACKEND_COMPILE_EVENT:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


class Report:
    """Prints one JSON line per phase and mirrors it to ``out_path``."""

    def __init__(self, out_path: Path | None, clock: CompileClock):
        self.out_path = out_path
        self.clock = clock
        if out_path is not None:
            out_path.parent.mkdir(parents=True, exist_ok=True)
            out_path.write_text("")

    def emit(self, phase: str, **fields) -> dict:
        line = {"phase": phase, **fields}
        text = json.dumps(line)
        print(text, flush=True)
        if self.out_path is not None:
            with open(self.out_path, "a") as f:
                f.write(text + "\n")
        return line

    def run(self, phase: str, fn) -> dict:
        """Runs one phase and emits what it returns with ``seconds`` and
        ``compile_seconds`` added. An exception propagates — nothing here
        turns a failed phase into a printed result."""
        t0 = time.perf_counter()
        c0 = self.clock.seconds
        fields = fn()
        return self.emit(phase, **fields,
                         seconds=round(time.perf_counter() - t0, 3),
                         compile_seconds=round(self.clock.seconds - c0, 3))


def device_memory(device) -> dict | None:
    """bytes_in_use / peak_bytes_in_use as the backend reports them (the
    CPU backend reports none)."""
    stats = device.memory_stats()
    if not stats:
        return None
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def run_checked(name: str, jitted, args: tuple, *, device,
                expect_kernel: bool = True,
                expect_text: tuple[str, ...] = ()) -> tuple:
    """Compile ``jitted`` for ``args``, read the compiled text and memory
    analysis, then run THAT executable. On the chip a missing
    ``tpu_custom_call`` (interpret mode, or a jnp fallback) fails the run;
    off the chip (the rehearsal tests) the text is only reported."""
    import jax

    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    has_kernel = "tpu_custom_call" in text
    if device.platform == "tpu":
        if expect_kernel and not has_kernel:
            raise AssertionError(
                f"{name}: no tpu_custom_call in the compiled program — the "
                "Pallas kernel did not compile to Mosaic (interpret mode?)")
        for needle in expect_text:
            if needle not in text:
                raise AssertionError(f"{name}: no {needle} in compiled text")
    mem = compiled.memory_analysis()
    t0 = time.perf_counter()
    out = compiled(*args)
    jax.block_until_ready(out)
    run_s = time.perf_counter() - t0
    info = {
        "program": name,
        "tpu_custom_call": has_kernel,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "peak_bytes_in_use": (device_memory(device) or {}).get(
            "peak_bytes_in_use"),
    }
    return out, info


def seeded_bytes(seed: int, stream: int, nbytes: int) -> bytes:
    return np.random.default_rng([seed, stream]).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


# ------------------------------------- Phase A: device programs vs host refs


def phase_a(device, *, seed: int, block_bytes: int = BLOCK_BYTES,
            batch_blocks: int = 32, rs_block_bytes: int = 64 * MIB,
            ici_bytes: int = 8 * MIB) -> dict:
    """CRC32C, RS(6,3) and the replication step on ONE device against the
    host references (``tpudfs.common.checksum`` / ``erasure``)."""
    import jax

    from tpudfs.common import erasure
    from tpudfs.common.checksum import crc32c, crc32c_chunks
    from tpudfs.tpu.crc32c_pallas import (
        batch_block_crc_device,
        block_crc_device,
        bytes_to_words,
        crc32c_chunks_device,
        verify_block_device,
    )
    from tpudfs.tpu.rs_pallas import (
        pad_shard_len,
        rs_decode_device,
        rs_encode_device,
    )

    programs: list[dict] = []

    def checked(name, jitted, *args, **kw):
        out, info = run_checked(name, jitted, args, device=device, **kw)
        programs.append(info)
        return out

    # (1) CRC32C: per-chunk, whole-block fold, batched fold.
    batch = seeded_bytes(seed, 1, batch_blocks * block_bytes)
    block0 = batch[:block_bytes]
    words0 = jax.device_put(bytes_to_words(block0), device)
    chunk_crcs = checked("crc32c_chunks_device",
                         jax.jit(crc32c_chunks_device), words0)
    want_chunks = crc32c_chunks(block0).astype(np.uint32)
    assert np.array_equal(np.asarray(chunk_crcs), want_chunks), \
        "crc32c_chunks_device != host crc32c_chunks"
    block_crc = checked("block_crc_device", block_crc_device, words0)
    assert int(block_crc) == crc32c(block0), \
        "block_crc_device != host crc32c"
    words_all = jax.device_put(bytes_to_words(batch), device)
    batch_crcs = checked(
        f"batch_block_crc_device({batch_blocks})",
        jax.jit(lambda w: batch_block_crc_device(w, batch_blocks)),
        words_all)
    view = memoryview(batch)
    want_batch = [crc32c(view[i * block_bytes:(i + 1) * block_bytes])
                  for i in range(batch_blocks)]
    assert np.asarray(batch_crcs).tolist() == want_batch, \
        "batch_block_crc_device != host crc32c per block"

    # (2) The verdict is not trivially true: one flipped bit must show.
    verify = jax.jit(verify_block_device)
    expected = jax.device_put(want_chunks, device)
    intact = checked("verify_block_device", verify, words0, expected)
    assert bool(intact), "verify_block_device rejected an intact block"
    flipped = bytearray(block0)
    flipped[len(flipped) // 2] ^= 0x10
    bad_words = jax.device_put(bytes_to_words(bytes(flipped)), device)
    assert not bool(verify(bad_words, expected)), \
        "verify_block_device passed a block with a flipped bit"

    # (3) RS(6,3) over the shards of one client-default block, then a
    # decode with data shard 4 and parity shard 6 missing.
    k, m = 6, 3
    rs_block = seeded_bytes(seed, 2, rs_block_bytes)
    host_shards = erasure.encode(rs_block, k, m)
    slen = len(host_shards[0])
    padded = pad_shard_len(slen)

    def stack(rows: list[bytes]) -> np.ndarray:
        out = np.zeros((len(rows), padded), dtype=np.uint8)
        for i, row in enumerate(rows):
            out[i, :slen] = np.frombuffer(row, dtype=np.uint8)
        return out

    data_dev = jax.device_put(stack(host_shards[:k]), device)
    parity = checked("rs_encode_device RS(6,3)",
                     jax.jit(lambda d: rs_encode_device(d, k, m)), data_dev)
    parity = np.asarray(parity)
    for i in range(m):
        assert parity[i, :slen].tobytes() == host_shards[k + i], \
            f"rs_encode_device parity {i} != erasure.encode"
    del data_dev
    missing = (4, 6)
    present = tuple(i for i in range(k + m) if i not in missing)
    holes = [None if i in missing else s for i, s in enumerate(host_shards)]
    want_full = erasure.reconstruct(holes, k, m)
    avail = jax.device_put(
        stack([host_shards[i] for i in present[:k]]), device)
    recon = checked(
        "rs_decode_device RS(6,3) missing (4,6)",
        jax.jit(lambda a: rs_decode_device(a, k, m, present)), avail)
    recon = np.asarray(recon)
    for i in range(k):
        assert recon[i, :slen].tobytes() == want_full[i], \
            f"rs_decode_device data shard {i} != erasure.reconstruct"
    del avail, recon, parity

    # (4) The replication step on a mesh of this one device: every hop is
    # a self-permute, so the whole ppermute + verify + psum graph runs.
    programs.append(
        phase_ring([device], seed=seed, ici_bytes=ici_bytes)["program"])

    return {
        "sizes": {"block_bytes": block_bytes, "batch_blocks": batch_blocks,
                  "rs_block_bytes": rs_block_bytes, "rs": [k, m],
                  "rs_missing": list(missing), "ici_bytes": ici_bytes},
        "programs": programs,
        "device_memory": device_memory(device),
    }


# ------------------------------------------------ Phase B: the served path


def native_library_state() -> dict:
    """Builds (a no-op when fresh) and loads native/libtpudfs_native.so;
    says whether it was compiled here or came with the disk."""
    from tpudfs.common import native

    lib = REPO / "native" / "libtpudfs_native.so"
    sources = sorted((REPO / "native").glob("*.cc"))
    before = lib.stat().st_mtime if lib.exists() else None
    t0 = time.perf_counter()
    loaded = native.build_and_load()
    if loaded is None or not native.has_dataplane():
        raise RuntimeError("native library did not build or load; the "
                           "served path needs its C++ data plane")
    after = lib.stat().st_mtime
    return {
        "sources": [p.name for p in sources],
        "existed_before": before is not None,
        "built_here": before is None or after != before,
        "newer_than_sources": after >= max(p.stat().st_mtime
                                           for p in sources),
        "build_seconds": round(time.perf_counter() - t0, 3),
    }


class ServedCluster:
    """``scripts/start_cluster.py`` as a child process (the README's
    "Running" entry point), torn down by the pids it reports."""

    def __init__(self, root: Path, masters: int, chunkservers: int):
        from tpudfs.testing.procs import free_port

        self.root = root
        self.ready_file = root / "ready.json"
        self.endpoints: dict = {}
        with open(root / "launcher.err", "w") as err:
            self.launcher = subprocess.Popen(
                [sys.executable, str(REPO / "scripts" / "start_cluster.py"),
                 "--masters", str(masters),
                 "--chunkservers", str(chunkservers),
                 "--data-dir", str(root / "cluster"),
                 "--s3-port", str(free_port()),
                 "--ready-file", str(self.ready_file)],
                cwd=str(REPO), stdout=subprocess.DEVNULL, stderr=err,
            )

    def wait_ready(self, timeout: float = 180.0) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.ready_file.exists():
                text = self.ready_file.read_text()
                if text.endswith("}"):
                    self.endpoints = json.loads(text)
                    return self.endpoints
            if self.launcher.poll() is not None:
                raise RuntimeError(
                    "start_cluster.py exited early: "
                    + (self.root / "launcher.err").read_text()[-2000:]
                    + self._unready_logs())
            time.sleep(0.2)
        raise RuntimeError("cluster not ready in time" + self._unready_logs())

    def _unready_logs(self) -> str:
        """The tail of every server log that never printed READY (the
        workdir is removed at exit, so the error has to carry them)."""
        out = []
        for log in sorted((self.root / "cluster" / "logs").glob("*.log")):
            text = log.read_text(errors="replace")
            if "READY" not in text:
                out.append(f"\n--- {log.name} ---\n{text[-2000:]}")
        return "".join(out)

    def assert_servers_jax_free(self) -> int:
        """No server process maps libtpu or jaxlib: the chip has exactly
        one owner, this process."""
        for pid in self.endpoints["pids"]:
            for line in Path(f"/proc/{pid}/maps").read_text().splitlines():
                mapped = line.split(None, 5)[-1] if "/" in line else ""
                name = os.path.basename(mapped)
                # libtpudfs_native.so is this repo's own C++ library.
                if "/jaxlib/" in mapped or (
                        name.startswith("libtpu")
                        and not name.startswith("libtpudfs")):
                    raise AssertionError(
                        f"server pid {pid} maps {mapped}: a second process "
                        "could take the chip")
        return len(self.endpoints["pids"])

    def stop(self) -> None:
        """Stops the launcher (its exit hook stops the servers), then
        kills whatever is left of the pids it reported."""
        if self.launcher.poll() is None:
            self.launcher.terminate()
            try:
                self.launcher.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.launcher.kill()
                self.launcher.wait()
        for pid in self.endpoints.get("pids") or []:
            with contextlib.suppress(ProcessLookupError):
                os.kill(pid, signal.SIGKILL)


def held_block_crcs(blocks: list) -> list[int]:
    """A device-side whole-block CRC of every held block, computed NOW
    from what is resident in HBM (one batched program per fused round),
    fetched in one wave."""
    import jax

    from tpudfs.tpu.crc32c_pallas import (
        batch_block_crc_device,
        block_crc_device,
    )

    per_batch: dict[int, object] = {}
    singles: dict[int, object] = {}
    for i, b in enumerate(blocks):
        if b.batch is not None:
            if id(b.batch) not in per_batch:
                per_batch[id(b.batch)] = batch_block_crc_device(
                    b.batch.words, b.batch.nblocks)
        else:
            singles[i] = block_crc_device(b.array)
    jax.block_until_ready([*per_batch.values(), *singles.values()])
    host = {key: np.asarray(v) for key, v in per_batch.items()}
    out = []
    for i, b in enumerate(blocks):
        if b.batch is not None:
            out.append(int(host[id(b.batch)][b.batch_index]))
        else:
            out.append(int(np.asarray(singles[i])))
    return out


async def phase_b(device, *, seed: int, files: int, workdir: Path,
                  file_bytes: int = FILE_BYTES,
                  block_bytes: int = BLOCK_BYTES,
                  masters: int = MASTERS,
                  chunkservers: int = CHUNKSERVERS) -> dict:
    """Write through the served cluster, check the guarantees, then hold
    every block in HBM twice: remote fused reads, then the colocated
    sweep pump."""
    import jax

    from tpudfs.client.client import Client
    from tpudfs.common.checksum import crc32c
    from tpudfs.common.rpc import RpcClient
    from tpudfs.tpu.hbm_reader import HbmReader

    out: dict = {"native_library": native_library_state()}
    blocks_per_file = file_bytes // block_bytes
    nblocks = files * blocks_per_file
    cluster = ServedCluster(workdir, masters, chunkservers)
    rpc = RpcClient()
    try:
        t0 = time.perf_counter()
        ep = cluster.wait_ready()
        out["cluster"] = {
            "masters": len(ep["shards"]["shard-0"]),
            "chunkservers": len(ep["chunkservers"]),
            "server_processes_jax_free": cluster.assert_servers_jax_free(),
            "start_seconds": round(time.perf_counter() - t0, 3),
        }
        for addr in ep["chunkservers"]:
            hello = await rpc.call(addr, "ChunkServerService", "DataPort",
                                   {}, timeout=10.0)
            if not hello.get("native") or not hello.get("port"):
                raise AssertionError(
                    f"chunkserver {addr} answers DataPort {hello}: the "
                    "native C++ engine is not serving (silent drop to the "
                    "asyncio plane)")
        out["cluster"]["native_engine_on_every_chunkserver"] = True

        client = Client(ep["shards"]["shard-0"], [ep["config_server"]],
                        rpc_client=rpc, block_size=block_bytes,
                        local_reads=False)
        paths = [f"/smoke/f{i:04d}" for i in range(files)]
        host_crcs: list[list[int]] = [[] for _ in range(files)]
        wsem = asyncio.Semaphore(WRITE_CONCURRENCY)

        async def put(i: int) -> None:
            async with wsem:
                data = await asyncio.to_thread(
                    seeded_bytes, seed, 100 + i, file_bytes)
                view = memoryview(data)
                host_crcs[i] = [
                    crc32c(view[j * block_bytes:(j + 1) * block_bytes])
                    for j in range(blocks_per_file)]
                await client.create_file(paths[i], data)

        t0 = time.perf_counter()
        await asyncio.gather(*(put(i) for i in range(files)))
        write_s = time.perf_counter() - t0
        out["write"] = {
            "files": files, "file_bytes": file_bytes,
            "block_bytes": block_bytes, "blocks": nblocks,
            "replication": 3, "logical_bytes": files * file_bytes,
            "seconds": round(write_s, 3),
        }

        # ---- guarantees: an acknowledged file reads back bit-identical,
        # and each of the 3 replicas the master names returns the block
        # with the recorded CRC when asked directly.
        rng = np.random.default_rng([seed, 7])
        sample_files = sorted(rng.choice(files, min(4, files),
                                         replace=False).tolist())
        for i in sample_files:
            got = await client.get_file(paths[i])
            want = seeded_bytes(seed, 100 + i, file_bytes)
            assert got == want, f"{paths[i]} did not read back identical"
        metas = await asyncio.gather(
            *(client.get_file_info(p) for p in paths))
        sample_blocks = rng.choice(nblocks, min(64, nblocks), replace=False)
        replica_reads = 0
        for flat in sorted(sample_blocks.tolist()):
            i, j = divmod(flat, blocks_per_file)
            block = metas[i]["blocks"][j]
            locations = [a for a in block["locations"] if a]
            assert len(set(locations)) == 3, \
                f"block {block['block_id']} has replicas {locations}"
            assert int(block["checksum_crc32c"]) == host_crcs[i][j], \
                "master-recorded CRC differs from the host CRC of the write"
            for addr in locations:
                resp = await client._data_call(
                    addr, "ReadBlock",
                    {"block_id": block["block_id"], "offset": 0,
                     "length": 0}, timeout=60.0)
                data = resp["data"]
                assert len(data) == block_bytes and \
                    crc32c(data) == host_crcs[i][j], \
                    f"replica {addr} of {block['block_id']} is wrong"
                replica_reads += 1
        out["guarantees"] = {
            "files_read_back_identical": len(sample_files),
            "blocks_sampled": len(sample_blocks),
            "replica_reads_with_recorded_crc": replica_reads,
        }
        want_flat = [c for per in host_crcs for c in per]

        async def hold_all(what: str, reader, read) -> tuple[list, dict]:
            """Read every block into device memory with ``read``, confirm
            once, and compare a device CRC of each held block with the
            host CRC of what was written."""
            t0 = time.perf_counter()
            held = await read()
            jax.block_until_ready([x for b in held for x in b.sync_arrays])
            read_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            await reader.confirm(held)
            confirm_s = time.perf_counter() - t0
            assert len(held) == nblocks, \
                f"{what}: holding {len(held)} of {nblocks} blocks"
            assert all(b.verified for b in held), \
                f"{what}: unverified block after confirm"
            assert held_block_crcs(held) == want_flat, \
                f"{what}: device CRC of a held block differs from the " \
                "host CRC of what was written"
            return held, {
                "blocks_held": len(held),
                "resident_bytes": sum(b.size for b in held),
                "read_seconds": round(read_s, 3),
                "confirm_seconds": round(confirm_s, 3),
                "device_memory": device_memory(device),
            }

        # ---- (i) remote: fused ReadBlocks rounds, verified on device.
        reader = HbmReader(client, [device], batch_reads=BATCH_READS)
        rsem = asyncio.Semaphore(REMOTE_SWEEP_CONCURRENCY)

        async def read_file(path: str) -> list:
            async with rsem:
                return await reader.read_file_to_device_blocks(
                    path, verify="lazy")

        async def read_remote() -> list:
            per_file = await asyncio.gather(*(read_file(p) for p in paths))
            return [b for bs in per_file for b in bs]

        held, stats = await hold_all("remote", reader, read_remote)
        combiner = reader._combiners[device]
        if device.platform == "tpu":
            assert not combiner.host_verify, \
                "remote rounds were CRC'd on the host, not the device"
        assert combiner.rounds > 0, "no fused round ran"
        # Drop (i) before (ii): at most one full set resident.
        del held
        gc.collect()
        out["read_remote"] = {
            "path": f"local_reads=False, HbmReader(batch_reads={BATCH_READS})"
                    ", verify=lazy + one confirm",
            "verify": ("host-crc32c(combiner)" if combiner.host_verify
                       else "device-crc32c(batch_block_crc_device)"),
            "rounds": combiner.rounds, **stats,
            "device_memory_after_drop": device_memory(device),
        }

        # ---- (ii) colocated: the native sweep pump.
        client.local_reads = True
        reader = HbmReader(client, [device], batch_reads=BATCH_READS)
        held, stats = await hold_all(
            "colocated", reader, lambda: reader.sweep_paths_to_device(paths))
        pump_verified = all(
            b.batch is not None and b.batch.crcs is None
            and not b.batch_pending for b in held)
        del held
        gc.collect()
        out["read_colocated"] = {
            "path": "local_reads=True, sweep_paths_to_device + one confirm",
            "verify": ("host-crc32c(sweep-pump)" if pump_verified
                       else "mixed(per-block fallbacks present)"),
            "sweep_blocks": reader.sweep_blocks, **stats,
        }
        await client.close()
    finally:
        await rpc.close()
        cluster.stop()
    return out


# --------------------------------------------- Phase C: transfer + compiles


def h2d_around_first_d2h(device, *, seed: int, buffers: int = 64,
                         nbytes: int = MIB) -> dict:
    """H2D GB/s of ``buffers`` distinct host buffers before and after the
    process's FIRST device->host transfer. Must run before anything else
    reads a value back."""
    import jax

    def fresh(stream: int) -> list[np.ndarray]:
        rng = np.random.default_rng([seed, stream])
        return [rng.integers(0, 2**32, (nbytes // 512, 128), dtype=np.uint32)
                for _ in range(buffers)]

    def timed_put(bufs: list[np.ndarray]) -> float:
        t0 = time.perf_counter()
        arrs = [jax.device_put(b, device) for b in bufs]
        jax.block_until_ready(arrs)
        return buffers * nbytes / (time.perf_counter() - t0) / 1e9

    warm = jax.device_put(fresh(10)[0], device)  # first-transfer set-up
    jax.block_until_ready(warm)
    before = [timed_put(fresh(11 + r)) for r in range(3)]
    first_d2h = np.asarray(warm[:1])  # the process's first D2H
    assert first_d2h.shape == (1, 128)
    after = [timed_put(fresh(21 + r)) for r in range(3)]
    return {
        "buffers": buffers, "buffer_bytes": nbytes,
        "h2d_GBps_before_first_d2h": before,
        "h2d_GBps_after_first_d2h": after,
        "median_before": sorted(before)[1], "median_after": sorted(after)[1],
    }


# --------------------------------------------- --chips 4: the cross-chip path


def phase_ring(devices: list, *, seed: int, ici_bytes: int = 8 * MIB) -> dict:
    """3x chain replication over a ring of ``devices``: each device ends up
    holding its own batch and those of its two ring predecessors — not
    everything, and not all on the first device. On one device every hop
    is a self-permute and the three replicas coincide."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudfs.common.checksum import crc32c_chunks
    from tpudfs.tpu.crc32c_pallas import bytes_to_words
    from tpudfs.tpu.ici_replication import make_mesh, replicated_write_step

    n, R = len(devices), 3
    mesh = make_mesh(devices)
    sharding = NamedSharding(mesh, P("hosts"))
    data = seeded_bytes(seed, 40, n * ici_bytes)
    host_words = bytes_to_words(data)
    words = jax.device_put(host_words, sharding)
    crcs = jax.device_put(crc32c_chunks(data).astype(np.uint32), sharding)
    step = replicated_write_step(mesh, replication=R)
    out, info = run_checked(
        f"replicated_write_step({n} devices, R={R})", jax.jit(step),
        (words, crcs), device=devices[0],
        # One device has nobody to reduce the acks with.
        expect_text=("collective-permute", "all-reduce")[:1 + (n > 1)])
    assert bool(jnp.all(out["ok"])), "replica verify failed on device"
    assert int(out["acks"]) == n, f"acks {int(out['acks'])} != {n}"
    per_host = host_words.reshape(n, -1, host_words.shape[1])
    pos = {d: i for i, d in enumerate(mesh.devices.reshape(-1))}
    shards = out["replicas"].addressable_shards
    assert sorted(pos[s.device] for s in shards) == list(range(n)), \
        "replicas are not spread one shard per device"
    held = {}
    for s in shards:
        d = pos[s.device]
        local = np.asarray(s.data)  # (R, C, 128): what THIS device holds
        assert local.shape[0] == R, \
            f"device {d} holds {local.shape[0]} replica groups, not {R}"
        for r in range(R):
            assert np.array_equal(local[r], per_host[(d - r) % n]), \
                f"device {d} replica {r} is not host {(d - r) % n}'s batch"
        held[d] = local.tobytes()
    assert len(set(held.values())) == n, \
        "two devices hold identical replica sets"
    memory = [device_memory(d) for d in devices]
    if devices[0].platform == "tpu":
        assert all(m and m["bytes_in_use"] > 0 for m in memory), \
            f"a device reports no bytes in use: {memory}"
    return {"devices": n, "replication": R, "ici_bytes": ici_bytes,
            "acks": int(out["acks"]), "program": info,
            "device_memory": memory}


def phase_ec(devices: list, *, seed: int, ici_bytes: int = 8 * MIB,
             k: int = 2, m: int = 2, failed: int = 1) -> dict:
    """RS(k,m) shard scatter over the ring, one member's shards
    overwritten, degraded gather: reconstruction bit-exact."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudfs.tpu.crc32c_pallas import bytes_to_words
    from tpudfs.tpu.ici_replication import (
        EcShardGather,
        EcShardScatter,
        make_mesh,
    )

    n = len(devices)
    mesh = make_mesh(devices)
    sharding = NamedSharding(mesh, P("hosts"))
    data = seeded_bytes(seed, 41, n * ici_bytes)
    words = jax.device_put(bytes_to_words(data), sharding)
    scatter = EcShardScatter(mesh, k, m)
    (shards, ok, acks), scatter_info = run_checked(
        f"EcShardScatter RS({k},{m})", scatter._fn, (words,),
        device=devices[0], expect_text=("collective-permute",))
    assert int(acks) == n and bool(np.asarray(ok).all()), \
        f"EC scatter verified on {int(acks)}/{n} members"
    broken = np.asarray(shards).copy()
    rows = broken.shape[0] // n
    broken[failed * rows:(failed + 1) * rows] = 0xABABABAB
    gather = EcShardGather(mesh, k, m)
    recon, gather_info = run_checked(
        f"EcShardGather RS({k},{m})", gather._fn,
        (jax.device_put(broken, sharding), gather._matrices(failed)),
        device=devices[0], expect_kernel=False,
        expect_text=("collective-permute",))
    recon = np.asarray(recon).reshape(n, k, -1)
    per = -(-ici_bytes // k)
    shard_len = -(-per // 512) * 512
    for i in range(n):
        got = b"".join(recon[i, r].astype("<u4").tobytes()[:shard_len]
                       for r in range(k))[:ici_bytes]
        assert got == data[i * ici_bytes:(i + 1) * ici_bytes], \
            f"degraded gather mismatch on member {i}"
    return {"devices": n, "rs": [k, m], "ici_bytes": ici_bytes,
            "overwritten_member": failed, "acks": int(acks),
            "programs": [scatter_info, gather_info]}


async def phase_live_write(devices: list | None, *, seed: int, workdir: Path,
                           puts: int = 64,
                           block_bytes: int = BLOCK_BYTES) -> dict:
    """``puts`` one-block files through an in-process cluster of
    ``len(devices)`` chunkservers attached to one IciWriteGroup on those
    devices — or, with ``devices=None``, through the same cluster with no
    group attached (the TCP chain it is compared with). Every block must
    sit on three members' disks with the CRC of what was written."""
    from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE, crc32c
    from tpudfs.testing.inproc import InprocCluster

    n_cs = len(devices) if devices is not None else 4
    cluster = InprocCluster(str(workdir), n_masters=3, n_cs=n_cs)
    await cluster.start()
    group = None
    try:
        if devices is not None:
            from tpudfs.tpu.ici_replication import make_mesh
            from tpudfs.tpu.write_group import IciWriteGroup

            group = IciWriteGroup(
                make_mesh(devices),
                [cs.address for cs in cluster.chunkservers], replication=3)
            for i, cs in enumerate(cluster.chunkservers):
                cs.attach_ici_group(group, i)
            await asyncio.to_thread(
                group.warm, block_bytes // CHECKSUM_CHUNK_SIZE)
        await cluster.ready()
        client = cluster.client(block_size=block_bytes)
        payloads = [seeded_bytes(seed, 200 + i, block_bytes)
                    for i in range(puts)]
        sem = asyncio.Semaphore(16)

        async def put(i: int) -> None:
            async with sem:
                await client.create_file(f"/smoke/live/f{i:03d}",
                                         payloads[i])

        t0 = time.perf_counter()
        await asyncio.gather(*(put(i) for i in range(puts)))
        seconds = time.perf_counter() - t0
        by_addr = {cs.address: cs for cs in cluster.chunkservers}
        crcs = []
        for i in range(puts):
            want = crc32c(payloads[i])
            assert await client.get_file(f"/smoke/live/f{i:03d}") \
                == payloads[i]
            meta = await client.get_file_info(f"/smoke/live/f{i:03d}")
            (block,) = meta["blocks"]
            assert int(block["checksum_crc32c"]) == want
            locations = {a for a in block["locations"] if a}
            assert len(locations) == 3, \
                f"{block['block_id']} has replicas {sorted(locations)}"
            for addr in locations:
                on_disk = await asyncio.to_thread(
                    by_addr[addr].store.read, block["block_id"])
                assert crc32c(on_disk) == want, \
                    f"{block['block_id']} on {addr} has the wrong CRC"
            crcs.append(want)
        result = {
            "path": "ici-write-group" if group else "tcp-chain",
            "puts": puts, "block_bytes": block_bytes,
            "bytes": puts * block_bytes, "replicas_per_block": 3,
            "block_crcs_sha256": hashlib.sha256(
                np.asarray(crcs, dtype="<u4").tobytes()).hexdigest(),
            "put_seconds": round(seconds, 3),
            "ici_fallbacks": sum(cs.ici_fallbacks
                                 for cs in cluster.chunkservers),
        }
        if group is not None:
            assert group.stats.rounds >= 1, "no collective round ran"
            assert result["ici_fallbacks"] == 0, \
                f"{result['ici_fallbacks']} writes fell back to TCP"
            result.update(rounds=group.stats.rounds,
                          ici_blocks=group.stats.blocks,
                          round_failures=group.stats.round_failures)
        return result
    finally:
        if group is not None:
            await group.stop()
        await cluster.stop()


# --------------------------------------------------------------------- main


def require_tpu(chips: int) -> list:
    """The fatal device check: no option, environment variable or handler
    lets this script go on with another backend."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX reports platform "
                 f"{devices[0].platform!r}, not 'tpu' — this script only "
                 "runs on the chip (tests rehearse its phases on the CPU: "
                 "tests/test_chip_smoke.py)")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX reports {len(devices)}")
    return devices


def run_one_chip(device, report: Report, *, seed: int, files: int,
                 cache: dict, h2d: dict | None = None,
                 a_sizes: dict | None = None,
                 b_sizes: dict | None = None) -> None:
    """Phases C (transfer), A, B, C (compiles) on one device. The size
    arguments exist for the rehearsal tests; ``main`` passes none."""
    report.run("C.h2d_around_first_d2h", lambda: h2d_around_first_d2h(
        device, seed=seed, **(h2d or {})))
    report.run("A.device_programs", lambda: phase_a(
        device, seed=seed, **(a_sizes or {})))
    file_bytes = (b_sizes or {}).get("file_bytes", FILE_BYTES)
    need = 3 * files * file_bytes + (256 << 20)
    workdir = Path(tempfile.mkdtemp(prefix="tpudfs-smoke-"))
    try:
        free = shutil.disk_usage(workdir).free
        if free < need:
            sys.exit(f"chip_smoke: {workdir} has {free >> 20} MiB free, "
                     f"{need >> 20} MiB needed for {files} files of "
                     f"{file_bytes >> 20} MiB written 3x; pass a smaller "
                     "--gib")
        report.run("B.served_path", lambda: {
            "disk_free_bytes": free, "disk_needed_bytes": need,
            **asyncio.run(phase_b(device, seed=seed, files=files,
                                  workdir=workdir, **(b_sizes or {})))})
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    report.emit("C.compile_cache", **cache,
                compile_seconds_total=round(report.clock.seconds, 3),
                compiles=report.clock.count,
                entries_at_end=_cache_entries(cache["dir"]))


def run_four_chips(ring: list, report: Report, *, seed: int,
                   sizes: dict | None = None,
                   live: dict | None = None) -> None:
    """ONLY the cross-chip path and the TCP chain it is compared with."""
    report.run("X.ring_replication",
               lambda: phase_ring(ring, seed=seed, **(sizes or {})))
    report.run("X.ec_scatter_gather",
               lambda: phase_ec(ring, seed=seed, **(sizes or {})))
    workdir = Path(tempfile.mkdtemp(prefix="tpudfs-smoke-"))
    try:
        ici = report.run("X.live_collective_write", lambda: asyncio.run(
            phase_live_write(ring, seed=seed, workdir=workdir / "ici",
                             **(live or {}))))
        tcp = report.run("X.tcp_chain_comparison", lambda: asyncio.run(
            phase_live_write(None, seed=seed, workdir=workdir / "tcp",
                             **(live or {}))))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    for key in ("bytes", "block_crcs_sha256", "replicas_per_block"):
        assert ici[key] == tcp[key], \
            f"ICI write group and TCP chain differ in {key}"
    report.emit("X.ici_vs_tcp", same_bytes_crcs_replicas=True)


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for _ in os.scandir(path))
    except FileNotFoundError:
        return 0


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip phase and its TCP twin")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gib", type=float, default=2.0,
                    help="Phase B logical dataset; cut only where the "
                         "machine's free disk (3x this) forces it")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    from tpudfs.tpu import place_compile_cache

    cache_dir = place_compile_cache()
    cache = {"dir": cache_dir,
             "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
             "entries_at_start": _cache_entries(cache_dir)}
    report = Report(OUT_DIR / f"chip_smoke_{args.chips}chip.jsonl",
                    CompileClock())
    first = devices[0]
    report.emit("start", chips=args.chips, seed=args.seed,
                device={"platform": first.platform,
                        "kind": first.device_kind, "count": len(devices)},
                compile_cache=cache)
    if args.chips == 4:
        run_four_chips(devices[:4], report, seed=args.seed)
    else:
        files = max(1, round(args.gib * (1 << 30) / FILE_BYTES))
        report.emit("sizes", gib_requested=args.gib, files=files,
                    file_bytes=FILE_BYTES, block_bytes=BLOCK_BYTES)
        run_one_chip(first, report, seed=args.seed, files=files, cache=cache)
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
