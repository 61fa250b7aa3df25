"""DFS → TPU HBM reader: chunk fetches land as device arrays, verified on-device.

The reference's read path concatenates fetched blocks into one host Vec
(mod.rs:898-917) that a consumer then copies again. Here each block's bytes go
straight from the fetch buffer into its target device's memory (one
``jax.device_put`` per block, round-robin across devices), the per-512B-chunk
CRC32C runs ON the device (Pallas kernel), and the chunk CRCs are folded with
the GF(2)-matrix combine into the whole-block checksum recorded at
CompleteFile — end-to-end verification without a host checksum pass. Uniform
blocks then assemble into a single sharded ``jax.Array`` via
``jax.make_array_from_single_device_arrays`` (no host concat at any point) —
the "chunk read into TPU HBM" path of BASELINE.json.
"""

from __future__ import annotations

import asyncio
import contextvars
import ctypes
import logging
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudfs.client.client import (
    PROBE_DUE,
    ChecksumMismatchError,
    Client,
    DfsError,
)
from tpudfs.common import native, telemetry
from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE, crc32c_combine
from tpudfs.tpu import host_buffers
from tpudfs.tpu.crc32c_pallas import (
    WORDS_PER_CHUNK,
    block_crc_device,
    bytes_to_words,
    crc32c_chunks_device,
)
from tpudfs.tpu.device_block import (
    DeviceBatch,
    DeviceBlock,
    device_array_to_bytes,
)
from tpudfs.tpu.read_combiner import (
    ReadCombiner,
    block_size,
    chunk_aligned,
    decode_matrix_on,
    may_fuse,
)

__all__ = ["DeviceBlock", "HbmReader", "device_array_to_bytes"]

logger = logging.getLogger(__name__)

#: erasure-coded blocks one reader holds between shard fetch and decode
#: dispatch on the PER-BLOCK path (k+m connections and k+m shards on the
#: host each), which serves what no fused round takes: a block whose data
#: shards are all reachable, an eager or unaligned read, a round's
#: fall-backs and ``confirm``'s re-reads. No more than the blockport pool
#: keeps idle connections a peer (``BlockConnPool.MAX_IDLE_PER_PEER``):
#: past that every block reopens connections (on the v5e's host, when this
#: path carried every degraded block, 4 read 0.263 GB/s, 8 0.284, 16
#: 0.275, 32 0.19-0.21, 64 0.153: PERF.md, PR 27).
EC_BLOCKS_IN_FLIGHT = 8


class HbmReader:
    def __init__(self, client: Client, devices: list | None = None, *,
                 batch_reads: int = 0):
        self.client = client
        self.devices = list(devices) if devices is not None else jax.devices()
        #: >0 enables the fused read path (read_combiner.ReadCombiner, one
        #: per device, max_batch=batch_reads) for lazily-verified local
        #: reads; 0 keeps every block on the per-block path.
        self.batch_reads = batch_reads
        self._combiners: dict = {}
        #: blocks served by the native sweep pump (observability/bench);
        #: its rounds, and those already produced when the sweep asked.
        self.sweep_blocks = 0
        self.sweep_rounds = 0
        self.sweep_rounds_ready = 0
        #: erasure-coded blocks the per-block path read, those of them that
        #: lost a data shard and were reconstructed on the device, the data
        #: shards they lacked, and the bytes of shards fetched for all of
        #: them. The public counts of the same names add what the
        #: combiners' rounds reconstructed.
        self._ec_blocks = 0
        self._ec_degraded_blocks = 0
        self._ec_missing_data_shards = 0
        self._ec_shard_bytes = 0
        self._decode_matrices: dict = {}
        self._ec_gate: asyncio.Semaphore | None = None
        self._ec_pool: ThreadPoolExecutor | None = None

    def _combiner(self, device):
        c = self._combiners.get(device)
        if c is None:
            c = ReadCombiner(self.client, device, max_batch=self.batch_reads)
            self._combiners[device] = c
        return c

    def _of_rounds(self, counter: str) -> int:
        return sum(getattr(c, counter) for c in self._combiners.values())

    @property
    def ec_rounds(self) -> int:
        """Fused (sub-)rounds of degraded erasure-coded blocks."""
        return self._of_rounds("ec_rounds")

    @property
    def ec_round_blocks(self) -> int:
        """Erasure-coded blocks that came through a fused round: each
        lost a data shard and was reconstructed on the device."""
        return self._of_rounds("ec_round_blocks")

    @property
    def ec_blocks(self) -> int:
        """Erasure-coded blocks read, in rounds or per block."""
        return self._ec_blocks + self.ec_round_blocks

    @property
    def ec_degraded_blocks(self) -> int:
        """Those of ``ec_blocks`` reconstructed on the device."""
        return self._ec_degraded_blocks + self.ec_round_blocks

    @property
    def ec_missing_data_shards(self) -> int:
        """Data shards the ``ec_degraded_blocks`` lacked."""
        return self._ec_missing_data_shards \
            + self._of_rounds("ec_missing_data_shards")

    @property
    def ec_shard_bytes(self) -> int:
        """Bytes of shards fetched for the ``ec_blocks``."""
        return self._ec_shard_bytes + self._of_rounds("ec_shard_bytes")

    async def _try_batched(self, block: dict, device,
                           verify: bool | str) -> DeviceBlock | None:
        """Fused-round read when enabled and the block qualifies (lazy
        verify, chunk-aligned; colocated replica OR a remote peer's
        batched ReadBlocks frame OR, degraded and erasure-coded, its
        surviving holders' frames). None -> per-block path."""
        if not self.batch_reads or verify != "lazy":
            return None
        return await self._combiner(device).read(block)

    def warm_batches(self, cpb: int) -> None:
        """Pre-compile every fused-round CRC bucket on every device (H2D
        only) so no XLA compile lands in a timed window."""
        if self.batch_reads:
            for device in self.devices:
                self._combiner(device).warm(cpb)

    # ------------------------------------------------------------ per block

    async def read_block_to_device(self, block: dict, device,
                                   verify: bool | str = True, *,
                                   safe_local: bool = False) -> DeviceBlock:
        """``verify``: False = no check; True = eager (syncs this block's
        device CRC now); ``"lazy"`` = dispatch the on-device check but defer
        the host sync to a later batched ``confirm`` call (one host sync
        per batch).

        ``safe_local``: force the host-verified short-circuit path (used by
        the corruption-retry; normally the on-device check subsumes it)."""
        if not safe_local:
            db = await self._try_batched(block, device, verify)
            if db is not None:
                return db
        try:
            db = await self._read_block_inner(block, device, verify,
                                              safe_local)
        except ChecksumMismatchError as e:
            # The fast path trusts the device CRC end-to-end; a mismatch —
            # checksum OR shard-length (a truncated local shard file that
            # an unverified pread returns as-is) — may be a corrupt LOCAL
            # replica that the host-verified path would have excluded
            # (falling through to healthy replicas / parity reconstruction,
            # and triggering chunkserver self-repair). Retry once through
            # that path before declaring the block lost.
            if safe_local:
                raise
            try:
                db = await self._read_block_inner(block, device, verify,
                                                  True)
            except DfsError as e2:
                raise DfsError(
                    f"on-device checksum mismatch for block "
                    f"{block['block_id']} (verified-path retry failed: {e2})"
                ) from None
        db.source = block
        db.device = device
        return db

    async def _read_block_inner(self, block: dict, device,
                                verify: bool | str,
                                safe_local: bool) -> DeviceBlock:
        if block.get("ec_data_shards"):
            words, size = await self._ec_block_to_device(
                block, device, verify, safe_local
            )
            return await self._finish_block(block, words, size, verify)
        # When the on-device CRC fold will verify this block end-to-end, a
        # short-circuit local read skips the redundant host sidecar pass
        # (the device check subsumes it; bit-rot surfaces at confirm()).
        device_verify = bool(verify) and bool(block.get("checksum_crc32c"))

        def _grid(nbytes: int) -> np.ndarray:
            # Chunk-padded word grid the blockport payload scatters
            # straight into: the returned view's .base is the padded
            # array, so no bytes_to_words pad-copy is needed after.
            pad = -nbytes % CHECKSUM_CHUNK_SIZE
            arr = np.zeros(max(nbytes + pad, CHECKSUM_CHUNK_SIZE),
                           dtype=np.uint8)
            return arr[:nbytes]

        data = await self.client._read_block_range(
            block, 0, 0, local_verify=safe_local or not device_verify,
            into=_grid,
        )
        size = len(data)
        if isinstance(data, np.ndarray):
            grid = data.base if data.base is not None else data
            words_np = grid.view("<u4").reshape(-1, WORDS_PER_CHUNK)
        else:
            # Local short-circuit / gRPC fallback delivered bytes.
            words_np = bytes_to_words(data)
        # Off the event loop: device_put blocks for the whole host->HBM
        # transfer and would stall the gRPC fetches of every other
        # in-flight block.
        words = await asyncio.to_thread(
            lambda: jax.device_put(words_np, device)
        )
        return await self._finish_block(block, words, size, verify)

    async def _ec_block_to_device(self, block: dict, device,
                                  verify: bool | str = True,
                                  safe_local: bool = False):
        """EC block → device words, the per-block way: what a degraded
        block that no fused round took falls back to (the combiner's
        rounds carry the rest), and the only way of a block whose data
        shards are all reachable. All data shards present: host concat +
        one upload. Degraded: upload the k surviving shards
        as the uint32 words they already are and reconstruct ON DEVICE
        with ``rs_pallas.rs_decode_block``: one compiled program whatever
        the failure pattern (the inverse matrix is an operand), straight to
        the chunk grid the CRC fold takes. One worker-thread hop does the
        stacking, the upload and the dispatch.

        Every shard of a block is a call of its own on a connection of its
        own here (server-verified ``ReadBlock``: this is the path that
        finds a rotten shard), so a file's blocks are not all fetched at
        once: one 64 MiB RS(6,3) file would hold 576 sockets (16 readers:
        9 216) and every shard of every block on the host.
        ``EC_BLOCKS_IN_FLIGHT`` blocks are between their fetch and their
        dispatch at a time."""
        k = int(block["ec_data_shards"])
        m = int(block["ec_parity_shards"])
        size = block_size(block)
        device_verify = bool(verify) and bool(block.get("checksum_crc32c"))
        if self._ec_gate is None:
            self._ec_gate = asyncio.Semaphore(EC_BLOCKS_IN_FLIGHT)
        queued = telemetry.span("ec.queued")
        async with self._ec_gate:
            queued.end()
            return await self._ec_block_gated(block, device, k, m, size,
                                              safe_local or not device_verify)

    async def _ec_block_gated(self, block: dict, device, k: int, m: int,
                              size: int, local_verify: bool):
        from tpudfs.tpu import rs_pallas

        with telemetry.span("ec.fetch_shards",
                            block=block["block_id"]) as fetching:
            shards = await self.client._read_ec_shards(
                block, local_verify=local_verify)
            present = tuple(i for i, s in enumerate(shards) if s is not None)
            missing_data = k - sum(i < k for i in present)
            fetching.set(present=len(present), missing_data=missing_data)
        self._ec_blocks += 1
        self._ec_shard_bytes += sum(len(shards[i]) for i in present)
        if not missing_data:
            def assemble():
                # Scatter the shards straight into the padded chunk grid
                # in ONE copy: `b"".join(shards)[:size]` copies the block
                # once to concatenate and bytes_to_words copies it AGAIN
                # to pad non-chunk-aligned sizes; the grid is where the
                # bytes end up either way.
                with telemetry.span("ec.assemble", degraded=False):
                    need = -(-max(size, 1) // CHECKSUM_CHUNK_SIZE) \
                        * CHECKSUM_CHUNK_SIZE
                    buf = np.zeros(need, dtype=np.uint8)
                    off = 0
                    for s in shards[:k]:
                        take = min(len(s), size - off)
                        if take <= 0:
                            break
                        buf[off : off + take] = \
                            np.frombuffer(s, dtype=np.uint8, count=take)
                        off += take
                    words = buf.view("<u4").reshape(-1, WORDS_PER_CHUNK)
                with telemetry.span("ec.device_put", degraded=False):
                    return jax.device_put(words, device)

            return await self._in_ec_thread(assemble), size
        if len(present) < k:
            raise DfsError(
                f"EC block {block['block_id']}: only {len(present)} of "
                f"{k}+{m} shards available"
            )
        use = present[:k]
        slen = len(shards[use[0]])  # type: ignore[arg-type]
        if any(len(shards[i]) != slen for i in use):  # type: ignore[arg-type]
            raise ChecksumMismatchError(
                f"EC block {block['block_id']}: shard length mismatch"
            )
        self._ec_degraded_blocks += 1
        self._ec_missing_data_shards += missing_data

        def reconstruct():
            with telemetry.span("ec.assemble", degraded=True):
                stack = rs_pallas.survivors_to_words(
                    [shards[i] for i in use], slen)
            with telemetry.span("ec.device_put", degraded=True):
                survivors = jax.device_put(stack, device)
                mat = self._decode_matrix_on(device, k, m, use)
            with telemetry.span("ec.decode_dispatch"):
                return rs_pallas.rs_decode_block(
                    survivors, mat, slen=slen, size=size)

        return await self._in_ec_thread(reconstruct), size

    async def _in_ec_thread(self, fn):
        """``fn`` on the reader's ONE upload thread, in the caller's context
        (its spans stay children of the read). One thread, not
        ``to_thread``'s pool: a dozen threads taking the GIL from the event
        loop in turn cost a tenth of the rate (0.257 against 0.284 GB/s)."""
        if self._ec_pool is None:
            self._ec_pool = ThreadPoolExecutor(
                1, thread_name_prefix="tpudfs-ec")
        return await asyncio.get_running_loop().run_in_executor(
            self._ec_pool, contextvars.copy_context().run, fn)

    def _decode_matrix_on(self, device, k: int, m: int, use: tuple):
        """The (k, k) inverse for survivor set ``use`` as a device value,
        uploaded once per set (the rounds keep theirs the same way)."""
        return decode_matrix_on(self._decode_matrices, device, k, m, use)

    def warm_ec(self, k: int, m: int, block_bytes: int) -> None:
        """Pre-compile the degraded read of RS(k, m) blocks of
        ``block_bytes`` on every device: the decode program (ONE per shard
        length; which servers are down does not matter) and the CRC fold
        of its output for the per-block path, and what a fused round of
        such blocks dispatches at every bucket up to ``batch_reads``, so
        no XLA compile lands in a timed window."""
        from tpudfs.common.erasure import shard_len
        from tpudfs.tpu import rs_pallas

        slen = shard_len(block_bytes, k)
        zeros = np.zeros((k, rs_pallas.decode_rows(slen), WORDS_PER_CHUNK),
                         dtype=np.uint32)
        use = tuple(range(1, k + 1))  # any set: the matrix is an operand
        for device in self.devices:
            words = rs_pallas.rs_decode_block(
                jax.device_put(zeros, device),
                self._decode_matrix_on(device, k, m, use),
                slen=slen, size=block_bytes)
            jax.block_until_ready(block_crc_device(words))
            if self.batch_reads and chunk_aligned(block_bytes):
                self._combiner(device).warm_ec(k, m, block_bytes)

    async def _finish_block(self, block: dict, words: jax.Array, size: int,
                            verify: bool | str) -> DeviceBlock:
        # verified means "an on-device CRC check ran and passed" — a block
        # with no recorded checksum was NOT verified.
        verified = False
        pending: jax.Array | None = None
        expected: int | None = None
        if verify and block.get("checksum_crc32c"):
            expected = int(block["checksum_crc32c"])
            if chunk_aligned(size):
                # Device fold: whole-block CRC without any chunk readback
                # (and no host->device scalar upload — compare on host).
                crc = block_crc_device(words)
                if verify == "lazy":
                    pending = crc
                else:
                    got = int(await asyncio.to_thread(np.asarray, crc))
                    verified = got == expected
            else:
                # Tail chunk was zero-padded on device, so the device fold
                # diverges from the stored CRC — rebuild the tail on host.
                # This path is eager even under verify="lazy" (there is no
                # device result to defer), so it must raise here: confirm()
                # only inspects pending_crc and would silently pass it.
                verified = await asyncio.to_thread(
                    self._verify_host_tail_block, words, size, expected
                )
            if pending is None and not verified:
                raise ChecksumMismatchError(
                    f"on-device checksum mismatch for block {block['block_id']}"
                )
        return DeviceBlock(block["block_id"], words, size, verified,
                           pending_crc=pending, expected_crc=expected)

    async def confirm(self, blocks: list[DeviceBlock], *,
                      retry: bool = True) -> None:
        """Resolve every lazy verification with ONE device→host sync —
        per-block 0-d CRCs (stacked) and fused-round CRC vectors
        (read_combiner.DeviceBatch) ride the same transfer.

        A failed block is retried once through the host-verified fetch path
        (``retry=False`` disables) — a corrupt local replica gets excluded
        there in favor of healthy replicas / parity reconstruction. Raises
        DfsError naming each unrecoverable block; marks the rest verified.
        """
        singles = [b for b in blocks if b.pending_crc is not None]
        batched = [b for b in blocks if b.batch_pending and b.batch is not None]
        if not singles and not batched:
            return
        # Unresolved batches, deduped by identity, in first-seen order.
        groups: list = []
        for b in batched:
            if b.batch.resolved is None and \
                    not any(g is b.batch for g in groups):
                groups.append(b.batch)
        # CRCs may live on different devices; gather them onto one device
        # (free when everything is already there) so ONE transfer resolves
        # the whole confirm call, then compare host-side. The singles stack
        # is padded to a power-of-two length: jnp.stack compiles per input
        # count, and an unbounded family of batch sizes would put a fresh
        # XLA compile on the hot path of every differently-sized confirm.
        home = self.devices[0]
        parts = []
        nsingles = len(singles)
        if singles:
            crcs = [jax.device_put(b.pending_crc, home) for b in singles]
            crcs += [crcs[0]] * (self._confirm_bucket(nsingles) - nsingles)
            parts.append(jnp.stack(crcs))
        for g in groups:
            parts.append(jax.device_put(g.crcs, home))
        if parts:
            # One D2H wave: start every part's async host copy, then
            # collect and concatenate on the HOST. No jnp.concatenate —
            # that would compile a fresh XLA program for each distinct
            # (singles, groups...) shape combination, and this runs right
            # inside the caller's verdict-fetch window.
            def fetch() -> np.ndarray:
                for p in parts:
                    p.copy_to_host_async()
                return np.concatenate([np.asarray(p) for p in parts]) \
                    if len(parts) > 1 else np.asarray(parts[0])

            with telemetry.span("hbm.confirm", blocks=len(blocks)):
                got = await asyncio.to_thread(fetch)
        else:
            # Every batch here was resolved by an earlier confirm call
            # (blocks of one fused round confirmed file-by-file) — nothing
            # to transfer, verdicts come from the cached resolutions.
            got = np.empty(0, dtype=np.uint32)
        bad = []
        for i, b in enumerate(singles):
            b.pending_crc = None
            b.verified = int(got[i]) == b.expected_crc
            if not b.verified:
                bad.append(b)
        off = self._confirm_bucket(nsingles) if singles else 0
        for g in groups:
            g.resolved = got[off : off + g.nblocks]
            g.crcs = None
            off += g.nblocks
        for b in batched:
            b.batch_pending = False
            b.verified = (
                int(b.batch.resolved[b.batch_index]) == b.expected_crc
            )
            if not b.verified:
                bad.append(b)
        # Mismatch re-reads run CONCURRENTLY: each one is a full network
        # fetch + upload, and a corrupted fused round can flag many
        # blocks at once — serial retries would stack those round-trips.
        async def _reread(b):
            try:
                return await self.read_block_to_device(
                    b.source, b.device, verify=True, safe_local=True
                )
            except DfsError:
                return None

        retryable = [
            b for b in bad
            if retry and b.source is not None and b.device is not None
        ]
        rereads = []
        if retryable:
            with telemetry.span("hbm.confirm_reread", blocks=len(retryable)):
                rereads = await asyncio.gather(
                    *(_reread(b) for b in retryable))
        fixed = {id(b): nb for b, nb in zip(retryable, rereads)}
        unrecovered = []
        for b in bad:
            nb = fixed.get(id(b))
            if nb is not None:
                b.array, b.size, b.verified = nb.array, nb.size, nb.verified
            else:
                unrecovered.append(b.block_id)
        if unrecovered:
            raise DfsError(
                "on-device checksum mismatch for blocks: "
                + ", ".join(unrecovered)
            )

    @staticmethod
    def _confirm_bucket(n: int) -> int:
        return 1 << (n - 1).bit_length()

    def warm_confirm(self, sample: DeviceBlock, n: int) -> None:
        """Pre-compile confirm's stacked fetch for an ``n``-block batch
        WITHOUT fetching (no device→host transfer): benchmarks keep the
        one-time XLA compile — and, on pathological transports, the first
        D2H — out of their timed windows."""
        if sample.pending_crc is None:
            return
        crc = jax.device_put(sample.pending_crc, self.devices[0])
        jax.block_until_ready(
            jnp.stack([crc] * self._confirm_bucket(n))
        )

    def _verify_host_tail_block(self, words: jax.Array, size: int,
                                expected_crc: int) -> bool:
        chunk_crcs = np.asarray(crc32c_chunks_device(words))
        return self._verify_with_host_tail(words, size, expected_crc, chunk_crcs)

    def _verify_with_host_tail(self, words, size, expected_crc, chunk_crcs):
        from tpudfs.common.checksum import crc32c_combine_chunks

        full_chunks = size // CHECKSUM_CHUNK_SIZE
        crc = crc32c_combine_chunks(
            chunk_crcs[:full_chunks], CHECKSUM_CHUNK_SIZE
        )
        tail_len = size - full_chunks * CHECKSUM_CHUNK_SIZE
        if tail_len:
            from tpudfs.common.checksum import crc32c

            tail_words = np.asarray(words[full_chunks:])
            # uint8 view instead of tobytes()[:tail_len]: tobytes copies
            # the whole padded tail chunk and the slice copies it again,
            # per confirmed block; the view costs nothing and crc32c
            # takes any buffer.
            tail = tail_words.astype("<u4").reshape(-1) \
                .view(np.uint8)[:tail_len]
            crc = crc32c_combine(crc, crc32c(tail), tail_len)
        return crc == expected_crc

    # ---------------------------------------------------- native sweep pump

    async def sweep_metas_to_device(self, metas: list[dict], device=None, *,
                                    round_blocks: int = 16,
                                    ring: int = 3) -> list[DeviceBlock]:
        """Steady-state SWEEP infeed, native end-to-end (the round-4
        verdict's 'push the round loop out of Python'): every eligible
        block of every file is handed to the native sweep pump
        (native/blockio.cc tpudfs_sweep_*) ONCE — a small team of
        producer threads (as many as the machine and a round allow, at
        most eight) drives fused pread+3-lane-CRC, a block each at a time,
        into a ring of round buffers, and this coroutine's only per-round
        work is one wait, one vectorized verify, one device_put, one
        release. No per-block futures, no executor hops, no staging. The
        ``hbm.sweep`` span says how many ``producers`` ran and how many
        rounds were ready when asked for (``rounds_ready`` of ``rounds``).

        Blocks that don't qualify (``may_fuse``; remote-only replica, CRC
        mismatch, short read), and every block where the library has no
        pump or the backend aliases host buffers, fall back to the general
        per-block path — identical recovery semantics. Returns DeviceBlocks
        flattened in (file, block) order, HOST-verified (the pump checks
        the recorded whole-block CRC; nothing pending for confirm).

        The ring's buffers are recycled under host_buffers' reuse rule:
        a round's device_put completes before its buffer is released to
        the producers. Round r - ring is released just before the wait
        for round r, whose buffer it is: the team fills a round while the
        transfers of the ``ring - 1`` rounds before it are in flight."""
        with telemetry.span("hbm.sweep") as whole:
            return await self._sweep_metas(whole, metas, device,
                                           round_blocks, ring)

    async def _sweep_metas(self, whole, metas: list[dict], device,
                           round_blocks: int, ring: int) -> list[DeviceBlock]:
        """The sweep itself, inside its caller's ``hbm.sweep`` span
        ``whole`` (one per public call, the metadata fan-out inside it when
        the caller came by paths), which it gives its attributes."""
        device = device or self.devices[0]
        lib = native.get_lib()
        # Without a pump in the library, or on a backend whose device
        # arrays alias the ring (no completion wait makes refilling it
        # safe), every block is a fallback entry.
        pump = (lib is not None and hasattr(lib, "tpudfs_sweep_info")
                and host_buffers.may_recycle(device))

        # ---- eligibility + local path resolution (meta order preserved)
        entries: list = []   # (slot_index | None, block) per (file, block)
        paths: list[bytes] = []
        expected_sizes: list[int] = []
        expected_crcs: list[int] = []
        resolving = telemetry.span("sweep.resolve")
        for meta in metas:
            for block in meta["blocks"]:
                store = None
                if pump and may_fuse(block):
                    store = self.client.local_replica_nowait(block)
                    if store is PROBE_DUE:
                        store = await self.client.local_replica(block)
                if store is None:
                    entries.append((None, block))
                    continue
                try:
                    # No-probe hot-tier path: a cold-tier/missing block
                    # fails its pread and takes the per-block fallback.
                    bpath = store.hot_path_str(block["block_id"])
                except ValueError:
                    entries.append((None, block))
                    continue
                entries.append((len(paths), block))
                paths.append(bpath.encode())
                expected_sizes.append(int(block["size"]))
                expected_crcs.append(int(block["checksum_crc32c"]))
        resolving.end(blocks=len(entries))

        fallback_idx = [i for i, (slot, _b) in enumerate(entries)
                        if slot is None]
        results: list = [None] * len(entries)
        n = len(paths)
        pump_info = np.zeros(2, dtype=np.int64)  # producers, rounds ready
        nrounds = -(-n // round_blocks)
        if n:
            stride = max(expected_sizes)
            stride = -(-stride // CHECKSUM_CHUNK_SIZE) * CHECKSUM_CHUNK_SIZE
            spb = stride // CHECKSUM_CHUNK_SIZE  # slot rows
            bufs = [host_buffers.alloc(device, round_blocks * stride)
                    for _ in range(ring)]
            buf_words = [b.view("<u4").reshape(-1, WORDS_PER_CHUNK)
                         for b in bufs]
            sizes = np.zeros(n, dtype=np.int64)
            crcs = np.zeros(n, dtype=np.uint32)
            cpaths = (ctypes.c_char_p * n)(*paths)
            cbufs = (ctypes.c_void_p * ring)(
                *(b.ctypes.data for b in bufs))
            exp_sizes = np.asarray(expected_sizes, dtype=np.int64)
            exp_crcs = np.asarray(expected_crcs, dtype=np.uint32)
            slot_entry = [i for i, (slot, _b) in enumerate(entries)
                          if slot is not None]
            handle = lib.tpudfs_sweep_start(
                cpaths, n, stride, round_blocks, cbufs, ring,
                sizes.ctypes.data, crcs.ctypes.data)
            outstanding: list = [None] * nrounds  # round words awaiting H2D
            try:
                for r in range(nrounds):
                    if r >= ring:
                        # Recycled buffer: its device copy must COMPLETE
                        # before the producer may refill it (host_buffers,
                        # rule 2).
                        with telemetry.span("sweep.gate", round=r):
                            prev = outstanding[r - ring]
                            if prev is not None:
                                await asyncio.to_thread(
                                    jax.block_until_ready, prev)
                            lib.tpudfs_sweep_release(handle, r - ring)
                    with telemetry.span("sweep.pump_wait",
                                        round=r) as pumped:
                        nblk = await asyncio.to_thread(
                            lib.tpudfs_sweep_wait, handle, r)
                        pumped.set(blocks=nblk)
                    if nblk < 0:
                        break
                    lo = r * round_blocks
                    hi = lo + nblk
                    ok = (sizes[lo:hi] == exp_sizes[lo:hi]) \
                        & (crcs[lo:hi] == exp_crcs[lo:hi])
                    with telemetry.span("sweep.device_put", round=r,
                                        blocks=nblk, bytes=nblk * stride):
                        words = jax.device_put(
                            buf_words[r % ring][: nblk * spb], device)
                    outstanding[r] = words
                    batch = DeviceBatch(words=words, crcs=None,
                                        cpb=spb, nblocks=nblk)
                    for j in range(nblk):
                        slot = lo + j
                        eidx = slot_entry[slot]
                        _s, block = entries[eidx]
                        if not ok[j]:
                            fallback_idx.append(eidx)
                            continue
                        results[eidx] = DeviceBlock(
                            block["block_id"], None,
                            int(exp_sizes[slot]), True,
                            expected_crc=int(exp_crcs[slot]),
                            source=block, device=device,
                            batch=batch, batch_index=j,
                            batch_pending=False)
                        self.sweep_blocks += 1
            finally:
                # Completion before stop: a dispatched transfer may still
                # be reading a ring buffer (any backend).
                with telemetry.span("sweep.drain", round=nrounds):
                    pend = [w for w in outstanding if w is not None]
                    if pend:
                        await asyncio.to_thread(jax.block_until_ready, pend)
                    lib.tpudfs_sweep_info(handle, pump_info.ctypes.data)
                    lib.tpudfs_sweep_stop(handle)
            self.sweep_rounds += nrounds
            self.sweep_rounds_ready += int(pump_info[1])

        if fallback_idx:
            async def fb(eidx: int):
                _slot, block = entries[eidx]
                results[eidx] = await self.read_block_to_device(
                    block, device, verify=True)

            with telemetry.span("sweep.fallback", blocks=len(fallback_idx)):
                await asyncio.gather(*(fb(i) for i in fallback_idx))
        whole.set(blocks=len(results), producers=int(pump_info[0]),
                  rounds=nrounds, rounds_ready=int(pump_info[1]))
        return results

    async def sweep_paths_to_device(self, paths: list[str], device=None, *,
                                    round_blocks: int = 16,
                                    ring: int = 3) -> list[DeviceBlock]:
        """sweep_metas_to_device with the metadata fan-out in front (the
        'cold' flagship pattern: nothing cached, metadata fetched
        in-sweep, then the native pump drives the data plane)."""
        with telemetry.span("hbm.sweep") as whole:
            with telemetry.span("sweep.metadata", files=len(paths)):
                metas = await asyncio.gather(
                    *(self.client.get_file_info(p) for p in paths))
            missing = [p for p, m in zip(paths, metas) if m is None]
            if missing:
                raise DfsError(f"file not found: {missing[0]}")
            return await self._sweep_metas(whole, metas, device,
                                           round_blocks, ring)

    # ------------------------------------------------------------- per file

    async def read_file_to_device_blocks(
        self, path: str, verify: bool | str = True,
        placement="round_robin",
    ) -> list[DeviceBlock | None]:
        """Fetch every block concurrently with per-block device placement
        (the fan-out of mod.rs:880-916 with DMA placement instead of host
        concat). ``round_robin``: block i → device i % n (spreads a stream of
        blocks). ``contiguous``: block i → device i // ceil(blocks/n) (keeps
        file order within each device — required for read_file_sharded).
        A callable: block i → ``placement(i)``, any device (its own
        combiner), or None: the block is not read and its entry is None."""
        with telemetry.span("hbm.read_file") as whole:
            meta = await self.client.get_file_info(path)
            if meta is None:
                raise DfsError(f"file not found: {path}")
            blocks = meta["blocks"]
            whole.set(blocks=len(blocks))
            n = len(self.devices)
            if callable(placement):
                device_of = placement
            elif placement == "contiguous":
                per = -(-len(blocks) // n) if blocks else 1
                device_of = lambda i: self.devices[i // per]  # noqa: E731
            else:
                device_of = lambda i: self.devices[i % n]  # noqa: E731
            where = [device_of(i) for i in range(len(blocks))]
            got = iter(await asyncio.gather(*(
                self.read_block_to_device(block, device, verify=verify)
                for block, device in zip(blocks, where)
                if device is not None)))
            return [None if device is None else next(got)
                    for device in where]

    async def read_file_sharded(self, path: str, mesh: Mesh | None = None,
                                verify: bool | str = True) -> jax.Array:
        """Whole file as ONE sharded jax.Array ((total_chunks, 128) uint32
        words, sharded over the device axis IN FILE ORDER). Blocks are
        assigned contiguously (block i → device i // per_group) and
        concatenated ON their device (never on the host); the tail pads with
        zero chunks so every shard has equal shape."""
        dblocks = await self.read_file_to_device_blocks(
            path, verify=verify, placement="contiguous"
        )
        await self.confirm(dblocks)  # one sync even in lazy mode
        if not dblocks:
            raise DfsError(f"file has no blocks: {path}")
        ndev = len(self.devices)
        max_chunks = max(b.array.shape[0] for b in dblocks)
        per = -(-len(dblocks) // ndev)
        groups: list[list[jax.Array]] = [[] for _ in range(ndev)]
        for i, b in enumerate(dblocks):
            short = max_chunks - b.array.shape[0]
            arr = b.array if short == 0 else jnp.pad(b.array, ((0, short), (0, 0)))
            groups[i // per].append(arr)
        per_group = max(len(g) for g in groups)
        shards = []
        for d, group in enumerate(groups):
            device = self.devices[d]
            while len(group) < per_group:
                group.append(
                    jax.device_put(
                        jnp.zeros((max_chunks, WORDS_PER_CHUNK), jnp.uint32),
                        device,
                    )
                )
            shard = group[0] if len(group) == 1 else jnp.concatenate(group)
            shards.append(jax.device_put(shard, device))
        if mesh is None:
            mesh = Mesh(np.array(self.devices), ("blocks",))
        sharding = NamedSharding(mesh, P("blocks"))
        return jax.make_array_from_single_device_arrays(
            (ndev * per_group * max_chunks, WORDS_PER_CHUNK), sharding, shards
        )
