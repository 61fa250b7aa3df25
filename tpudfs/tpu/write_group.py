"""The collective write group: live DFS writes riding ICI.

The reference's production write path is a sequential gRPC chain
client → CS1 → CS2 → CS3 (chunkserver.rs:777-825,1039-1087) — every block
crosses the NIC three times. When chunkservers colocate on the TPU hosts
of one pod (the BASELINE north star), a write whose replica chain matches
the group's ring successors is staged HERE instead: pending colocated
chunk writes batch into :class:`IciReplicator` ``ppermute`` rounds (the
"collective write group" SURVEY §7 names as a hard part), every received
replica CRC-verifies ON DEVICE, the ack count rides a ``psum``, and each
member persists the replica groups its device received. Any unhealthy
condition — dead member, device error, failed on-device verify, stale
fencing term at persist — degrades the submitting write transparently to
the TCP/gRPC chain, so durability semantics are never weaker than the
reference chain.

Single-process scope: one process hosts the whole mesh (the virtual-mesh
live cluster in tests, ``dryrun_multichip``, and the one-chip bench). On
a real multi-host pod each host runs this same scheduler in
multi-controller style (``jax.distributed``): it stages only its OWN ring
position's queue, executes the identical ``shard_map`` program at the
agreed round cadence, and drains only its addressable shard — the
in-process member registry here stands in for that per-host control
plane, and the persistence loop already walks ``addressable_shards``
(never the global array) so the code is shard-local by construction.

Round geometry: one round carries ``B`` blocks of a uniform chunk count
``cpb`` from every ring position (short positions pad with zero blocks,
whose expected CRCs are the constant zero-chunk CRC, so the on-device
verify stays uniform). ``B`` is bucketed to powers of two so the set of
compiled XLA programs stays bounded, mirroring the fused read path.
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import dataclass, field

import numpy as np

from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE, crc32c, crc32c_chunks
from tpudfs.tpu.crc32c_pallas import WORDS_PER_CHUNK
from tpudfs.tpu.ici_replication import IciReplicator

logger = logging.getLogger(__name__)

#: CRC32C of 512 zero bytes — the expected CRC of every padding slot.
_ZERO_CHUNK_CRC = crc32c(b"\x00" * CHECKSUM_CHUNK_SIZE)


class IciWriteError(Exception):
    """A collective round failed for this block; caller falls back to the
    TCP chain."""


@dataclass
class _Pending:
    block_id: str
    data: bytes
    cpb: int
    master_term: int
    master_shard: str
    fut: asyncio.Future
    seq: int = 0  # global submission order (round-geometry fairness)


@dataclass
class _RoundStats:
    rounds: int = 0
    blocks: int = 0
    bytes: int = 0
    round_failures: int = 0
    last_acks: int = 0
    persist_failures: int = 0

    def as_gauges(self) -> dict[str, float]:
        return {
            "ici_rounds_total": float(self.rounds),
            "ici_blocks_total": float(self.blocks),
            "ici_bytes_total": float(self.bytes),
            "ici_round_failures_total": float(self.round_failures),
            "ici_persist_failures_total": float(self.persist_failures),
            "ici_last_acks": float(self.last_acks),
        }


class IciWriteGroup:
    """Per-process scheduler batching colocated chunk writes into
    chain-replication ``ppermute`` rounds over the mesh.

    ``members`` lists the chunkserver addresses in DEVICE ORDER (the
    mesh's flattened device list): flat position ``p`` belongs to ring
    ``p // ring_size`` at ring position ``p % ring_size`` — the layout
    :class:`IciReplicator` replicates along. The successor chain of a
    member is the next ``R-1`` addresses around its own ring row, which
    is exactly the replica set a collective round physically produces.
    """

    #: Max blocks per position per round; with 1 MiB blocks a full 8-deep
    #: round moves 8 MiB per hop per host — comfortably above the
    #: latency-bound regime without blowing HBM staging.
    MAX_BLOCKS_PER_ROUND = 8
    #: How long the scheduler waits after a first submission for the
    #: round to fill before launching (seconds).
    ROUND_ACCUMULATE_S = 0.002

    def __init__(self, mesh, members: list[str], replication: int = 3,
                 axis: str | None = None):
        self.mesh = mesh
        self.replicator = IciReplicator(mesh, replication, axis=axis)
        self.replication = replication
        self.axis = self.replicator.axis
        self.ring_size = mesh.shape[self.axis]
        total = int(mesh.devices.size)
        if len(members) != total:
            raise ValueError(
                f"{len(members)} members for a {total}-device mesh "
                "(need one chunkserver per device, in device order)")
        self.members = list(members)
        self._cs: dict[int, object] = {}  # flat position -> ChunkServer
        self._queues: list[list[_Pending]] = [[] for _ in range(total)]
        self._kick = asyncio.Event()
        self._task: asyncio.Task | None = None
        self._closed = False
        self._seq = 0
        self.stats = _RoundStats()
        #: device (flat) position per mesh device, for shard routing.
        self._dev_pos = {
            d: i for i, d in enumerate(mesh.devices.reshape(-1))
        }

    # ----------------------------------------------------------- membership

    def attach(self, cs, position: int) -> None:
        """Register the ChunkServer living at flat mesh position
        ``position``. In-process stand-in for the per-host control plane:
        a position is 'alive' while its CS is attached."""
        if self.members[position] != cs.address:
            raise ValueError(
                f"position {position} belongs to {self.members[position]}, "
                f"not {cs.address}")
        self._cs[position] = cs
        cs._ici_group = self
        cs._ici_pos = position

    def detach(self, position: int) -> None:
        cs = self._cs.pop(position, None)
        if cs is not None:
            cs._ici_group = None

    def healthy(self) -> bool:
        """Every position attached and the scheduler not shut down. A dead
        member (its CS stopped and detached) flips the whole group to the
        TCP fallback until it re-attaches — replication must never
        silently drop below R."""
        return not self._closed and len(self._cs) == len(self.members)

    def successors(self, position: int) -> list[str]:
        """The R-1 ring successors of ``position`` — the replica set a
        collective round produces for its blocks, and therefore the ONLY
        chain this group may serve."""
        n = self.ring_size
        row = (position // n) * n
        return [self.members[row + ((position % n) + j) % n]
                for j in range(1, self.replication)]

    def ring_of(self, position: int) -> list[str]:
        """The ordered ring row containing ``position`` (advertised to
        the master via heartbeats for successor-chain placement)."""
        n = self.ring_size
        row = (position // n) * n
        return self.members[row : row + n]

    # ------------------------------------------------------------- staging

    async def submit(self, position: int, block_id: str, data: bytes,
                     master_term: int, master_shard: str) -> int:
        """Stage one block write from ring position ``position``; resolves
        with replicas_written once a collective round carried, verified,
        and persisted it. Raises :class:`IciWriteError` when the round
        failed — the caller falls back to the TCP chain."""
        if self._closed:
            raise IciWriteError("write group stopped")
        if not data:
            raise IciWriteError("empty block")
        cpb = -(-len(data) // CHECKSUM_CHUNK_SIZE)
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        fut.add_done_callback(
            lambda f: None if f.cancelled() else f.exception()
        )
        self._seq += 1
        self._queues[position].append(_Pending(
            block_id=block_id, data=data, cpb=cpb,
            master_term=master_term, master_shard=master_shard, fut=fut,
            seq=self._seq,
        ))
        if self._task is None or self._task.done():
            self._task = asyncio.create_task(self._scheduler())
        self._kick.set()
        return await asyncio.shield(fut)

    async def stop(self) -> None:
        self._closed = True
        task = self._task
        if task is not None and not task.done():
            self._kick.set()
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            except Exception:
                logger.exception("write-group scheduler failed during stop")
        for q in self._queues:
            for p in q:
                if not p.fut.done():
                    p.fut.set_exception(IciWriteError("write group stopped"))
            q.clear()

    # ------------------------------------------------------------ scheduler

    async def _scheduler(self) -> None:
        while not self._closed:
            if not any(self._queues):
                self._kick.clear()
                await self._kick.wait()
                continue
            # Let a burst of submissions from concurrent writers land so
            # the round is dense (same reasoning as the fused read path).
            await asyncio.sleep(self.ROUND_ACCUMULATE_S)
            try:
                await self._run_round()
            except asyncio.CancelledError:
                raise
            except Exception as e:  # pragma: no cover - defensive
                logger.exception("collective write round crashed: %s", e)

    def _take_round(self) -> tuple[int, int, list[list[_Pending]]]:
        """Pick geometry and drain this round's blocks: uniform ``cpb``
        taken from the GLOBALLY oldest pending block (by submission seq —
        head-of-first-queue would starve a minority-geometry block on a
        later ring position behind a busy earlier one), up to a
        power-of-two ``B`` blocks per position."""
        oldest = min((q[0] for q in self._queues if q),
                     key=lambda p: p.seq)
        cpb = oldest.cpb
        per_pos: list[list[_Pending]] = []
        most = 1
        for q in self._queues:
            take = [p for p in q if p.cpb == cpb][: self.MAX_BLOCKS_PER_ROUND]
            per_pos.append(take)
            most = max(most, len(take))
        B = 1 << (most - 1).bit_length()  # pow2 bucket: bounded XLA shapes
        for q, take in zip(self._queues, per_pos):
            taken = set(map(id, take))
            q[:] = [p for p in q if id(p) not in taken]
        return cpb, B, per_pos

    async def _run_round(self) -> None:
        """One collective round. EVERY pending drained by _take_round is
        resolved before this returns or re-raises: once a block leaves
        its queue, neither stop()'s sweep nor the scheduler's crash guard
        can see it, so an unresolved future here would strand its
        rpc_write_block handler forever (and with it the TCP fallback)."""
        cpb, B, per_pos = self._take_round()
        try:
            await self._round_body(cpb, B, per_pos)
        except asyncio.CancelledError:
            self._fail_round(per_pos, "write group stopped")
            raise
        except Exception as e:
            self.stats.round_failures += 1
            self._fail_round(per_pos, f"collective round failed: {e}")
        finally:
            # Belt-and-braces: _round_body resolves futures on every
            # path it knows about; anything it missed fails out here.
            self._fail_round(per_pos, "round ended without a verdict")

    async def _round_body(self, cpb: int, B: int,
                          per_pos: list[list[_Pending]]) -> None:
        total = len(self.members)
        C = B * cpb
        stride = cpb * CHECKSUM_CHUNK_SIZE

        def stage() -> tuple[np.ndarray, np.ndarray]:
            # Multi-MiB memcpy + CRC staging: worker thread, not the event
            # loop — a stalled loop stalls every RPC handler and heartbeat
            # in the process on the one-core host.
            words = np.zeros((total * C, WORDS_PER_CHUNK), dtype="<u4")
            crcs = np.full(total * C, _ZERO_CHUNK_CRC, dtype="<u4")
            flat = words.reshape(-1).view(np.uint8)
            for pos, take in enumerate(per_pos):
                for j, p in enumerate(take):
                    off = (pos * C + j * cpb) * CHECKSUM_CHUNK_SIZE
                    flat[off : off + len(p.data)] = np.frombuffer(
                        p.data, dtype=np.uint8)
                    padded = flat[off : off + stride].tobytes()
                    crcs[pos * C + j * cpb : pos * C + (j + 1) * cpb] = \
                        crc32c_chunks(padded, CHECKSUM_CHUNK_SIZE)
            return words, crcs

        try:
            import jax

            words, crcs = await asyncio.to_thread(stage)
            sharding = self.replicator.sharding()
            dwords, dcrcs = await asyncio.to_thread(
                lambda: (jax.device_put(words, sharding),
                         jax.device_put(crcs, sharding)))
            replicas, _ok, acks = await asyncio.to_thread(
                self.replicator.replicate, dwords, dcrcs)
            # int(np.asarray(...)) is a D2H sync (one per round) — worker
            # thread too.
            acks = await asyncio.to_thread(lambda: int(np.asarray(acks)))
        except Exception as e:
            self.stats.round_failures += 1
            self._fail_round(per_pos, f"collective round failed: {e}")
            return
        self.stats.last_acks = acks
        if acks != total:
            # Some host's on-device verify failed — a corrupt transfer or
            # garbage member. The whole round falls back: partial persists
            # would hand the master replica sets the ring never produced.
            self.stats.round_failures += 1
            self._fail_round(per_pos,
                             f"round verified on {acks}/{total} hosts")
            return
        written, local_ok = await self._persist_round(
            replicas, per_pos, cpb, C)
        self.stats.rounds += 1
        for pos, take in enumerate(per_pos):
            for p in take:
                n = written.get((pos, p.block_id), 0)
                if n > 0 and (pos, p.block_id) in local_ok:
                    self.stats.blocks += 1
                    self.stats.bytes += len(p.data)
                    if not p.fut.done():
                        p.fut.set_result(n)
                elif not p.fut.done():
                    p.fut.set_exception(IciWriteError(
                        f"persist failed for {p.block_id} "
                        f"({n}/{self.replication} copies)"))

    async def _persist_round(self, replicas, per_pos, cpb: int, C: int):
        """Each member drains ITS addressable shard — replica group r on
        device p holds the blocks of ring position (p - r) — and persists
        them through its fenced group-commit path. Returns
        ({(source_pos, block_id): copies_persisted}, local_ok) where
        local_ok holds the (source_pos, block_id) pairs whose SOURCE
        member persisted its own copy — the analogue of the TCP chain's
        local write; without it the write fails over to the TCP path."""
        n = self.ring_size
        R = self.replication
        written: dict = {}
        local_ok: set = set()
        jobs = []
        for shard in replicas.addressable_shards:
            p = self._dev_pos[shard.device]
            member = self._cs.get(p)
            if member is None:
                self.stats.persist_failures += 1
                continue
            # Several-MiB D2H drain per device shard: off the event loop.
            local = await asyncio.to_thread(
                lambda s=shard: np.asarray(s.data))  # (R, C, 128) u32
            row = (p // n) * n
            for r in range(R):
                src = row + ((p % n) - r) % n
                take = per_pos[src]
                for j, pend in enumerate(take):
                    raw = local[r, j * cpb : (j + 1) * cpb].tobytes()
                    jobs.append((src, pend, r, member,
                                 raw[: len(pend.data)]))

        async def persist(job):
            src, pend, r, member, data = job
            ok = await member.persist_ici_replica(
                pend.block_id, data, pend.master_term, pend.master_shard)
            return (src, pend.block_id, r, ok)

        for src, bid, r, ok in await asyncio.gather(
                *(persist(j) for j in jobs)):
            if ok:
                written[(src, bid)] = written.get((src, bid), 0) + 1
                if r == 0:
                    local_ok.add((src, bid))
            else:
                self.stats.persist_failures += 1
        return written, local_ok

    def _fail_round(self, per_pos, msg: str) -> None:
        for take in per_pos:
            for p in take:
                if not p.fut.done():
                    p.fut.set_exception(IciWriteError(msg))

    # --------------------------------------------------------------- warmup

    def warm(self, cpb: int, max_blocks: int | None = None) -> None:
        """Pre-compile the replicate program for every pow2 bucket up to
        ``max_blocks`` so no XLA compile lands inside a live write."""
        import jax

        total = len(self.members)
        sharding = self.replicator.sharding()
        b = 1
        cap = max_blocks or self.MAX_BLOCKS_PER_ROUND
        while b <= cap:
            C = b * cpb
            w = jax.device_put(
                np.zeros((total * C, WORDS_PER_CHUNK), dtype="<u4"), sharding)
            c = jax.device_put(
                np.full(total * C, _ZERO_CHUNK_CRC, dtype="<u4"), sharding)
            out = self.replicator.replicate(w, c)
            jax.block_until_ready(out)
            b <<= 1
