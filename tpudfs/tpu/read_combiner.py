"""Batched DFS→HBM reads: a read-side group commit for the infeed hot path.

On the per-block path every 1 MiB block pays its own ``asyncio.to_thread``
hops, its own ``jax.device_put`` dispatch and its own CRC-kernel launch.
This module amortizes all three the same way ``GroupCommitter`` amortizes
fsyncs on the write side: concurrent per-file readers STAGE block requests,
and a two-stage drain pipeline fuses each round into

1. ONE native multi-block pread into one contiguous host buffer
   (``tpudfs_blocks_read``, native/blockio.cc — GIL released for the whole
   batch),
2. ONE ``jax.device_put`` of that buffer, and
3. ONE batched CRC dispatch (``batch_block_crc_device``) whose (n,) result
   is compared host-side in the caller's existing one-sync ``confirm``.

The two stages are separate tasks connected by a small queue, so one
round's reads overlap another's host→HBM transfer (both release the GIL).
The read stage keeps one round in flight per SOURCE (an origin chunkserver,
or the local disk), ``MAX_ROUNDS_IN_FLIGHT`` over all, each a task that
hands its round to the upload stage when its fetch ends: one origin's pread
overlaps another origin's payload, and traffic with one source goes a round
at a time. Rounds form naturally: whatever accumulated while a source's
round was in flight ships next — no artificial batching delay.

Round sizes are bucketed to powers of two (≤ ``max_batch``) so the batched
CRC program compiles a handful of times, not once per arrival pattern —
an unbounded shape family would put a fresh XLA compile (0.5-1 s each on
the v5e) on the hot path. ``warm()`` pre-compiles every bucket with H2D-only traffic.

A DEGRADED erasure-coded block (a data shard's holder is known dead) rides
a round too. Its fetch is one ``ReadBlocks`` frame a HOLDER for the k
surviving shards of every block of the round, each shard received in place
at its row of the round's (n, k, shard words) stack; its upload is the one
``device_put`` of that stack, then the decode program of every block and
the same batched CRC, over the RECONSTRUCTED bytes. A round asks every live
holder at once, so ``EC_ROUNDS_IN_FLIGHT`` of them run at a time rather
than one a source.

Blocks that don't fit the fused path (``may_fuse``: unchecksummed,
non-chunk-aligned, erasure-coded with every data shard reachable), or whose
round failed (no native library, a short or failed pread after a tiering
move or truncation, a failed frame, a shard its holder cannot serve), fall
back to the caller's general per-block path, which handles RPC fan-out,
the full shard fan-in of an EC block, and corruption retry.

Reference parity note: this accelerates the concurrent block fan-out of
dfs/client/src/mod.rs:880-916 (P5 in SURVEY.md §2.6); verification semantics
are unchanged — the on-device fold is still checked against the CompleteFile
whole-block CRC (chunkserver.rs:182-190 at-rest chunk CRCs feed the same
recorded value).
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from tpudfs.common import native, telemetry
from tpudfs.common.checksum import CHECKSUM_CHUNK_SIZE
from tpudfs.common.erasure import shard_len
from tpudfs.tpu import host_buffers, rs_pallas
from tpudfs.tpu.crc32c_pallas import WORDS_PER_CHUNK, batch_block_crc_device
from tpudfs.tpu.device_block import DeviceBatch, DeviceBlock

logger = logging.getLogger(__name__)

#: Largest fused round, in blocks. 32 x 1 MiB = 32 MiB per device_put.
DEFAULT_MAX_BATCH = 32
#: Rounds the read stage keeps in flight over all sources, one per source
#: (an origin chunkserver, or the local disk): one origin's pread hides
#: behind another's payload. Bounds host memory to this + 3 round buffers
#: (two queued for the upload stage, one in it).
MAX_ROUNDS_IN_FLIGHT = 3
#: Of those, rounds of erasure-coded blocks: each has a frame open to
#: every live holder, where a replicated round has one origin. All of
#: them: on the v5e's host 1 / 2 / 3 read 0.564-0.565 / 0.700-0.773 /
#: 0.857-0.884 GB/s (PERF.md, PR 32), a round's fetch taking two rounds'
#: uploads.
EC_ROUNDS_IN_FLIGHT = 3
#: Byte budget for one REMOTE round — comfortably under both transports'
#: 100 MiB frame/message caps (blocknet._MAX_PAYLOAD, rpc MAX_MESSAGE_BYTES)
#: including framing; oversized blocks simply round down to 1 per frame.
REMOTE_ROUND_BYTES = 48 << 20


def chunk_aligned(size: int) -> bool:
    """Whole 512-byte checksum chunks: only then does the device fold of
    the (zero-padded) chunk grid equal the block's recorded CRC."""
    return size % CHECKSUM_CHUNK_SIZE == 0


def block_size(block: dict) -> int:
    """Bytes of the block a reader hands back (an erasure-coded block
    records them as ``original_size``)."""
    if block.get("ec_data_shards"):
        return int(block.get("original_size") or block.get("size") or 0)
    return int(block.get("size") or 0)


def ec_survivors(block: dict, breakers) -> tuple | None:
    """The shards a round fetches of a DEGRADED erasure-coded block: its k
    lowest slots whose holder is not known dead (a slot with no location,
    or a holder whose blockport breaker is open, is), the set the per-block
    fan-in ends up decoding from. None when no data slot is known lost
    (nothing to decode) or fewer than k slots are left."""
    k = int(block["ec_data_shards"])
    slots = k + int(block.get("ec_parity_shards") or 0)
    use = tuple(i for i, addr in enumerate(
        (block.get("locations") or [])[:slots])
        if addr and not breakers.is_open(addr))[:k]
    if len(use) < k or use[-1] < k:
        return None
    return use


def may_fuse(block: dict, breakers=None) -> bool:
    """The one rule for what may ride a fused round (the combiner's, the
    sweep's): a block with a recorded whole-block CRC and a non-empty,
    chunk-aligned size that is replicated, or erasure-coded and DEGRADED
    as far as ``breakers`` (the client's blockport breakers; the sweep,
    which reads local disks, has none to offer) know: at least one data
    slot's holder known dead, at least k holders left (``ec_survivors``).
    Everything else reads per block: an erasure-coded block whose data
    shards are all reachable needs no decode, only their concatenation."""
    size = block_size(block)
    if not (block.get("checksum_crc32c") and size > 0
            and chunk_aligned(size)):
        return False
    if not block.get("ec_data_shards"):
        return True
    return breakers is not None and ec_survivors(block, breakers) is not None


@dataclass
class _Req:
    block: dict
    path: str  # local store path ("" for remote rounds)
    cpb: int
    size: int
    addr: str | None = None  # remote origin chunkserver (None = local)
    #: a degraded erasure-coded block: (k, m, shard bytes) and the k slots
    #: its round fetches (``ec_survivors``); ``addr`` stays None
    ec: tuple | None = None
    use: tuple = ()
    fut: asyncio.Future = field(default=None)  # created on the running loop
    #: ``combiner.queued``: opened by the reader that staged the block (its
    #: request), ended by the read stage when a round takes it.
    queued: object = None


_FALLBACK = object()  # resolve-to-slow-path sentinel
_BUSY = object()  # no place in flight for a request's round right now


def _bucket(n: int, cap: int) -> int:
    """Largest power of two ≤ min(n, cap) — the round size actually taken."""
    n = min(n, cap)
    return 1 << (n.bit_length() - 1)


def decode_matrix_on(cache: dict, device, k: int, m: int,
                     use: tuple) -> jax.Array:
    """The (k, k) inverse for survivor set ``use`` as a value on
    ``device``, uploaded once per set into the caller's ``cache`` (84 sets
    at most for RS(6,3))."""
    mat = cache.get((device, k, m, use))
    if mat is None:
        mat = cache[device, k, m, use] = jax.device_put(
            rs_pallas.decode_matrix(k, m, use), device)
    return mat


@jax.jit
def _unstack(stack: jax.Array) -> tuple:
    """An erasure-coded round's (n, k, rows, 128) survivors as the n
    operands of the decode program, in one dispatch."""
    return tuple(stack[i] for i in range(stack.shape[0]))


@jax.jit
def _restack(*blocks: jax.Array) -> jax.Array:
    """n decoded (cpb, 128) chunk grids as the one (n * cpb, 128) array a
    :class:`DeviceBatch` and the batched CRC take."""
    return jnp.concatenate(blocks)


class ReadCombiner:
    def __init__(self, client, device, *, max_batch: int = DEFAULT_MAX_BATCH,
                 host_verify: bool | None = None):
        self.client = client
        self.device = device
        self.max_batch = max_batch
        #: Where the whole-block CRC runs: on the chip the device folds it
        #: (one batched sync at confirm); on any other backend the fused
        #: native read computes it (tpudfs_blocks_read_crc, hardware
        #: CRC32C) and blocks arrive already verified.
        if host_verify is None:
            host_verify = getattr(device, "platform", "cpu") != "tpu"
        self.host_verify = host_verify
        self._pending: list[_Req] = []
        self._read_task: asyncio.Task | None = None
        self._upload_task: asyncio.Task | None = None
        self._queue: asyncio.Queue | None = None
        #: Reusable round buffers, keyed by shape (a replicated round's
        #: pread or receive target is (n*cpb, 128) u32, an erasure-coded
        #: round's (n, k, shard row bytes) u8), under host_buffers' reuse
        #: rule.
        self._buf_pool: dict[tuple, list[np.ndarray]] = {}
        #: rounds fused / blocks served whose bytes arrived as they are
        #: verified (observability + tests); ``combiner_blocks_per_round``
        #: and ``crc_verify_roofline_pct`` read these.
        self.rounds = 0
        self.blocks = 0
        #: rounds of degraded erasure-coded blocks (sub-rounds, as
        #: ``rounds``), the blocks they reconstructed, the data shards
        #: those lacked and the bytes of shards fetched for them:
        #: ``HbmReader`` adds them to its own.
        self.ec_rounds = 0
        self.ec_round_blocks = 0
        self.ec_missing_data_shards = 0
        self.ec_shard_bytes = 0
        self._decode_matrices: dict = {}
        #: rounds the read stage took; the ``round`` of every stage span.
        self._round_seq = 0

    #: Every buffer that can be out at once: no round allocates afresh in
    #: steady state.
    _POOL_PER_SHAPE = MAX_ROUNDS_IN_FLIGHT + 3

    def _get_buf(self, reqs: list[_Req]) -> np.ndarray:
        """The round's host buffer. Its rows are multiples of 512 bytes:
        every sub-round slice keeps the alignment host_buffers gave it."""
        n, ec = len(reqs), reqs[0].ec
        if ec is None:
            shape, dtype = (n * reqs[0].cpb, WORDS_PER_CHUNK), np.dtype("<u4")
        else:
            k, _m, slen = ec
            shape, dtype = (n, k, rs_pallas.decode_rows(slen)
                            * CHECKSUM_CHUNK_SIZE), np.dtype(np.uint8)
        free = self._buf_pool.get((shape, dtype.str))
        if free:
            return free.pop()
        return host_buffers.alloc(
            self.device, int(np.prod(shape)) * dtype.itemsize
        ).view(dtype).reshape(shape)

    def _put_buf(self, buf: np.ndarray | None) -> None:
        if buf is None or not host_buffers.may_recycle(self.device):
            return
        free = self._buf_pool.setdefault((buf.shape, buf.dtype.str), [])
        if len(free) < self._POOL_PER_SHAPE:
            free.append(buf)

    # ------------------------------------------------------------- staging

    # Verification is LAZY by design: the DeviceBlock carries a pending
    # on-device CRC32C fold that HbmReader.confirm resolves against
    # expected_crc before any bytes are handed to the consumer.
    # tpulint: disable=TPL005
    async def read(self, block: dict):
        """Stage one block; returns a lazily-verified DeviceBlock riding a
        DeviceBatch, or None when the block must take the general path."""
        breakers = self.client.block_pool.breakers
        if not may_fuse(block, breakers):
            return None
        size = block_size(block)
        path, remote, ec, use = "", None, None, ()
        if block.get("ec_data_shards"):
            # Once more, for the slots: the breaker's window may just have
            # run out.
            use = ec_survivors(block, breakers)
            if use is None:
                return None
            k = int(block["ec_data_shards"])
            ec = (k, int(block["ec_parity_shards"]), shard_len(size, k))
        else:
            store = await self.client.local_replica(block)
            if store is not None:
                try:
                    path = str(store.block_path(block["block_id"]))
                except ValueError:
                    return None
            else:
                # No colocated replica: fuse over the wire instead — rounds
                # group per origin chunkserver and ship as ONE ReadBlocks
                # frame (_data_call keeps aliased routes on gRPC, so fault
                # interposers still see the traffic).
                remote = next(
                    (a for a in block.get("locations") or [] if a), None)
                if remote is None:
                    return None
        req = _Req(block=block, path=path,
                   cpb=size // CHECKSUM_CHUNK_SIZE, size=size, addr=remote,
                   ec=ec, use=use,
                   fut=asyncio.get_running_loop().create_future())
        # Mark retrieved even when the awaiting reader is cancelled away.
        req.fut.add_done_callback(
            lambda f: None if f.cancelled() else f.exception()
        )
        req.queued = telemetry.span("combiner.queued")
        self._pending.append(req)
        self._ensure_running()
        result = await asyncio.shield(req.fut)
        if result is _FALLBACK:
            return None
        return result

    def _ensure_running(self) -> None:
        if self._read_task is None or self._read_task.done():
            self._queue = asyncio.Queue(maxsize=2)
            self._read_task = asyncio.create_task(self._read_stage())
            self._upload_task = asyncio.create_task(
                self._upload_stage(self._queue)
            )

    # ------------------------------------------------------ stage 1: fetch

    async def _read_stage(self) -> None:
        queue = self._queue
        #: place -> the task of its round, MAX_ROUNDS_IN_FLIGHT in all. A
        #: replicated round's place is its source (origin chunkserver;
        #: None = the local disk): one round per source. An erasure-coded
        #: round, whose frames go to every live holder, takes one of
        #: EC_ROUNDS_IN_FLIGHT places of its own.
        in_flight: dict = {}
        aborted = True
        try:
            while self._pending or in_flight:
                lead, place = None, _BUSY
                if len(in_flight) < MAX_ROUNDS_IN_FLIGHT:
                    ec_place = next(
                        (p for p in (("ec", i)
                                     for i in range(EC_ROUNDS_IN_FLIGHT))
                         if p not in in_flight), _BUSY)
                    for r in self._pending:
                        if r.ec is not None:
                            place = ec_place
                        else:
                            place = _BUSY if r.addr in in_flight else r.addr
                        if place is not _BUSY:
                            lead = r
                            break
                if lead is None:
                    # Every source with pending requests is busy, or the
                    # cap is reached: whatever accumulates meanwhile ships
                    # when a round ends.
                    done, _ = await asyncio.wait(
                        in_flight.values(),
                        return_when=asyncio.FIRST_COMPLETED)
                    in_flight = {s: t for s, t in in_flight.items()
                                 if t not in done}
                    continue
                # One round: the first request with a free place picks the
                # group by its (chunk count, origin, code) — uniform
                # geometry, one source (local disk, or one remote peer's
                # ReadBlocks frame) or one code's shard geometry. Mixed
                # requests only split rounds, they are never dropped.
                cpb, origin, ec = lead.cpb, lead.addr, lead.ec
                uniform = [r for r in self._pending if r.cpb == cpb
                           and r.addr == origin and r.ec == ec]
                cap = self.max_batch
                if origin is not None or ec is not None:
                    # One frame must fit the transports' 100 MiB caps (a
                    # holder's frame of an erasure-coded round is a k-th
                    # of it: the budget then bounds the round's buffer).
                    stride = cpb * CHECKSUM_CHUNK_SIZE
                    cap = min(cap, max(1, REMOTE_ROUND_BYTES // stride))
                reqs = uniform[:_bucket(len(uniform), cap)]
                taken = set(map(id, reqs))
                self._pending = [
                    r for r in self._pending if id(r) not in taken
                ]
                self._round_seq += 1
                for r in reqs:
                    r.queued.end(round=self._round_seq)
                in_flight[place] = asyncio.create_task(self._round(
                    queue, reqs, self._round_seq, len(in_flight) + 1))
            aborted = False
        finally:
            # Synchronously (no await since the nothing-pending, nothing-in-
            # flight check) clear the task slot BEFORE the first suspension
            # below: a request staged while we drain out must see
            # done-and-restartable state from _ensure_running, not a live
            # task that will never serve it. On abnormal exit (cancellation)
            # the still-pending requests are ours (no new generation can
            # have started while the task slot was occupied) and would
            # otherwise await forever; each in-flight round fails out its
            # own. The sentinel goes last: every round has handed off.
            self._read_task = None
            if aborted:
                if self._pending:
                    self._fail_out(self._pending)
                    self._pending = []
                for task in in_flight.values():
                    task.cancel()
                await asyncio.gather(*in_flight.values(),
                                     return_exceptions=True)
            await queue.put(None)

    async def _round(self, queue: asyncio.Queue, reqs: list[_Req], rnd: int,
                     in_flight: int) -> None:
        """One round, fetch to hand-off, as a task of its own: it owns its
        requests and its buffer until the upload stage has them, and its
        source stays busy until it ends. Only cancellation leaves it as an
        exception."""
        cpb, origin, ec = reqs[0].cpb, reqs[0].addr, reqs[0].ec
        #: leading entries of the buffer one block takes
        per = cpb if ec is None else 1
        buf = self._get_buf(reqs)
        try:
            # The stage tasks serve every reader (request=None): their
            # context is that of whichever reader started them and says
            # nothing about this round.
            stage = {"request": None, "round": rnd, "blocks": len(reqs),
                     "in_flight": in_flight}
            if ec is None:
                stage.update(bytes=len(reqs) * cpb * CHECKSUM_CHUNK_SIZE,
                             origin=origin or "local")
            with telemetry.span(
                    "combiner.fetch" if ec is None else "ec.fetch_shards",
                    **stage) as fetched:
                if ec is not None:
                    ok, crcs = await self._fetch_ec(reqs, buf, fetched), None
                elif origin is not None:
                    ok, crcs = await self._fetch_remote(reqs, buf)
                else:
                    ok, crcs = await asyncio.to_thread(
                        self._fill_buffer, reqs, buf
                    )
                fetched.set(fell_back=len(ok) - sum(ok))
            if crcs is not None:
                # Host-verified round: a CRC mismatch here is a corrupt
                # LOCAL replica — route it to the general path, whose
                # verified retry excludes this replica, reads a healthy
                # one, and triggers chunkserver self-repair.
                for i, r in enumerate(reqs):
                    if ok[i] and int(crcs[i]) != int(
                            r.block["checksum_crc32c"]):
                        logger.warning(
                            "fused read: CRC mismatch on local replica "
                            "of %s; falling back", r.block["block_id"])
                        ok[i] = False
            good = [r for r, o in zip(reqs, ok) if o]
            self._fall_back([r for r, o in zip(reqs, ok) if not o])
            if not good:
                return
            # Compact rows when some slots fell back, preserving request
            # order (row i belongs to good[i]). The compacted copy is NOT
            # pooled: its shape is a non-bucket size _get_buf would never
            # hand out.
            rows = buf
            if len(good) < len(reqs):
                rows = np.concatenate([
                    buf[i * per : (i + 1) * per]
                    for i, o in enumerate(ok) if o
                ])
            # One item a round, so the sub-rounds of one buffer reach the
            # upload stage together whatever order rounds end in.
            with telemetry.span("combiner.handoff", request=None,
                                round=rnd, blocks=len(good)):
                await queue.put((good, rows, per, crcs is not None,
                                 rows is buf, rnd))
            if rows is buf:
                buf = None  # the upload stage's to release
        except asyncio.CancelledError:
            self._fail_out(reqs)
            raise
        except Exception as e:
            # One bad round (allocation failure, I/O blowup) must not
            # stall its source or the stage: route its blocks to the
            # general per-block path.
            logger.warning("fused read round failed (%s); "
                           "falling back %d blocks", e, len(reqs))
            self._fall_back(reqs)
        finally:
            self._put_buf(buf)

    def _fall_back(self, reqs: list[_Req]) -> None:
        """Route ``reqs`` to the caller's general per-block path."""
        for r in reqs:
            if not r.fut.done():
                r.fut.set_result(_FALLBACK)

    def _fail_out(self, reqs: list[_Req]) -> None:
        for r in reqs:
            if not r.fut.done():
                r.fut.set_exception(
                    RuntimeError("read combiner shut down mid-request")
                )

    async def _fetch_frame(self, addr: str, block_ids: list[str],
                           flat: np.ndarray, offsets: list[int],
                           sizes: list[int]) -> list[bool]:
        """One ReadBlocks frame to ``addr`` (served by the native engine or
        the asyncio/gRPC handlers — the pool picks the transport): slot i's
        bytes land at ``flat[offsets[i]:offsets[i] + sizes[i]]`` (``flat``:
        the round buffer as bytes). False for a slot the peer couldn't
        serve or answered with another size, and for every slot of a frame
        that failed: those fall back to the general per-block path."""
        from tpudfs.common.rpc import RpcError

        scatter_ok: list[bool] | None = None

        def scatter(header: dict, plen: int):
            """Blockport scatter: route each slot's payload span DIRECTLY
            into its round-buffer position (the VERDICT r4 'zero-copy
            handoff from blockport socket into combiner buffers') —
            instead of one multi-MiB bytes materialization plus per-slot
            slice copies. Mismatched/short slots drain into scratch so
            the stream stays framed. None (-> bytes fallback) when the
            header doesn't look like a success with sizes."""
            nonlocal scatter_ok
            if not header.get("ok") or "sizes" not in header:
                return None
            got = list(header.get("sizes") or [])
            if len(got) != len(block_ids):
                return None
            segs = []
            oks = []
            covered = 0
            run_start, run_end = 0, -1  # the last segment's span of flat
            for start, want, sz in zip(offsets, sizes, got):
                if sz is None or sz < 0:
                    oks.append(False)
                    continue
                covered += sz
                if covered > plen:
                    # Untrusted header sizes: never allocate past the
                    # framed payload (a desynced peer could claim TiB).
                    return None
                if sz != want:
                    segs.append(np.empty(sz, dtype=np.uint8))  # drain
                    oks.append(False)
                    run_end = -1
                    continue
                if start == run_end:
                    # Payload-adjacent AND buffer-adjacent (the slot
                    # before was full): one segment, so the transport
                    # receives the whole run with no cut at the seam.
                    segs[-1] = flat[run_start : start + sz]
                else:
                    run_start = start
                    segs.append(flat[start : start + sz])
                run_end = start + sz
                oks.append(True)
            if covered != plen:
                return None  # inconsistent frame: let readexactly handle
            scatter_ok = oks
            return segs

        try:
            # _data_call centralizes transport choice AND the
            # aliased-routes-stay-on-gRPC rule (fault interposers see the
            # traffic either way).
            resp = await self.client._data_call(
                addr, "ReadBlocks", {"block_ids": block_ids},
                timeout=60.0, payload_into=scatter,
            )
        except RpcError as e:
            logger.debug("remote fused round to %s failed: %s", addr, e)
            return [False] * len(block_ids)
        if scatter_ok is not None:
            return scatter_ok
        # gRPC path (or fallback): payload arrives as one bytes.
        got = list(resp.get("sizes") or [])
        data = resp.get("data") or b""
        ok = []
        pos = 0
        for i, (start, want) in enumerate(zip(offsets, sizes)):
            sz = got[i] if i < len(got) else -1
            if sz is None or sz < 0:
                ok.append(False)
                continue
            end = pos + sz
            span = np.frombuffer(data, dtype=np.uint8,
                                 count=sz, offset=pos) \
                if end <= len(data) else None
            pos = end
            if sz != want or span is None:
                ok.append(False)
                continue
            flat[start : start + sz] = span
            ok.append(True)
        return ok

    async def _fetch_remote(
        self, reqs: list[_Req], buf: np.ndarray,
    ) -> tuple[list[bool], np.ndarray | None]:
        """A replicated round's fetch: one frame to its origin chunkserver
        for every block; in host-verify mode the received bytes are
        re-checked end-to-end against the recorded whole-block CRCs.
        ``buf`` is the caller's pooled (n*cpb, 128) round buffer."""
        stride = reqs[0].cpb * CHECKSUM_CHUNK_SIZE
        flat = buf.reshape(-1).view(np.uint8)
        ok = await self._fetch_frame(
            reqs[0].addr, [r.block["block_id"] for r in reqs], flat,
            [i * stride for i in range(len(reqs))], [r.size for r in reqs])
        if not self.host_verify:
            return ok, None
        crcs = await asyncio.to_thread(self._host_crcs, reqs, flat, ok)
        return ok, crcs

    async def _fetch_ec(self, reqs: list[_Req], buf: np.ndarray,
                        fetched) -> list[bool]:
        """An erasure-coded round's fetch: one frame a HOLDER for its
        shards of the round's blocks (a holder keeps its shard under the
        block's own id), every frame in flight at once, each shard
        received at ``buf[block, row, :shard bytes]`` of the caller's
        pooled (n, k, row bytes) stack, row j being the j-th of the
        block's ``use`` slots. A block with a shard that did not arrive
        falls back alone. ``fetched``: the round's ``ec.fetch_shards``."""
        k, _m, slen = reqs[0].ec
        row = buf.shape[2]
        flat = buf.reshape(-1)
        #: holder -> (block of the round, offset of its shard in flat)
        frames: dict[str, list[tuple[int, int]]] = {}
        for i, r in enumerate(reqs):
            for j, slot in enumerate(r.use):
                frames.setdefault(r.block["locations"][slot], []).append(
                    (i, (i * k + j) * row))
        fetched.set(holders=len(frames), bytes=len(reqs) * k * slen)
        calls = [asyncio.ensure_future(self._fetch_frame(
            addr, [reqs[i].block["block_id"] for i, _off in shards], flat,
            [off for _i, off in shards], [slen] * len(shards)))
            for addr, shards in frames.items()]
        try:
            answers = await asyncio.gather(*calls, return_exceptions=True)
        except asyncio.CancelledError:
            # The frames receive into ``buf``: they end before the caller
            # gives it back to the pool.
            for call in calls:
                call.cancel()
            await asyncio.gather(*calls, return_exceptions=True)
            raise
        ok = [True] * len(reqs)
        for (addr, shards), got in zip(frames.items(), answers):
            if isinstance(got, BaseException):
                logger.warning("erasure-coded round: frame to %s failed "
                               "(%r); falling back %d blocks", addr, got,
                               len(shards))
                got = [False] * len(shards)
            for (i, _off), arrived in zip(shards, got):
                ok[i] = ok[i] and arrived
        return ok

    def _host_crcs(self, reqs: list[_Req], flat: np.ndarray,
                   ok: list[bool]) -> np.ndarray:
        from tpudfs.common.checksum import crc32c

        stride = reqs[0].cpb * CHECKSUM_CHUNK_SIZE
        out = np.zeros(len(reqs), dtype=np.uint32)
        for i, r in enumerate(reqs):
            if ok[i]:
                # Contiguous uint8 view: crc32c takes it by pointer.
                out[i] = crc32c(flat[i * stride : i * stride + r.size])
        return out

    def _fill_buffer(
        self, reqs: list[_Req], buf: np.ndarray,
    ) -> tuple[list[bool], np.ndarray | None]:
        """Worker thread: pread every request's file into the caller's
        pooled contiguous (n*cpb, 128) uint32 buffer, one GIL-free native
        call for the whole round. In ``host_verify`` mode also returns each
        slot's whole-block CRC (fused into the same call). Without the
        native library the whole round falls back, as a failed remote
        frame does."""
        import ctypes

        lib = native.get_lib()
        if lib is None or not hasattr(lib, "tpudfs_blocks_read"):
            return [False] * len(reqs), None
        stride = reqs[0].cpb * CHECKSUM_CHUNK_SIZE
        paths = (ctypes.c_char_p * len(reqs))(
            *(r.path.encode() for r in reqs)
        )
        sizes = np.empty(len(reqs), dtype=np.int64)
        crcs = None
        if self.host_verify and hasattr(lib, "tpudfs_blocks_read_crc"):
            crcs = np.empty(len(reqs), dtype=np.uint32)
            lib.tpudfs_blocks_read_crc(
                paths, len(reqs), stride,
                buf.ctypes.data, sizes.ctypes.data, crcs.ctypes.data,
            )
        else:
            lib.tpudfs_blocks_read(
                paths, len(reqs), stride,
                buf.ctypes.data, sizes.ctypes.data,
            )
        return [int(s) == r.size for s, r in zip(sizes, reqs)], crcs

    # ----------------------------------------------------- stage 2: device

    async def _upload_stage(self, queue: asyncio.Queue) -> None:
        while True:
            with telemetry.span("combiner.upload_wait", request=None):
                item = await queue.get()
            if item is None:
                return
            await self._upload_round(*item)

    async def _upload_round(self, reqs: list[_Req], rows: np.ndarray,
                            per: int, host_verified: bool, pooled: bool,
                            rnd: int) -> None:
        """Ship one round in power-of-two sub-rounds: a compacted count (15
        after one dropped slot) would otherwise dispatch a CRC shape warm()
        never compiled — a fresh XLA compile mid-infeed on TPU. A full
        bucket is one sub-round. ``per``: leading entries of ``rows`` a
        block takes.

        A pooled ``rows`` returns to the pool only once every transfer out
        of it COMPLETED (host_buffers, rule 2)."""
        cpb, ec = reqs[0].cpb, reqs[0].ec
        #: words of the sub-rounds already shipped out of ``rows`` (an
        #: erasure-coded round's are computed from its transfer)
        shipped: list = []
        off = 0
        while off < len(reqs):
            take = 1 << ((len(reqs) - off).bit_length() - 1)
            sub = reqs[off : off + take]
            sub_rows = rows[off * per : (off + take) * per]
            off += take
            stage = {"request": None, "round": rnd, "blocks": take,
                     "bytes": sub_rows.nbytes}
            try:
                if ec is not None:
                    # Off the event loop, in one hop: take + 4 dispatches.
                    words, crcs = await asyncio.to_thread(
                        self._reconstruct, sub, sub_rows, stage)
                else:
                    with telemetry.span("combiner.device_put", **stage):
                        words = await asyncio.to_thread(
                            jax.device_put, sub_rows, self.device
                        )
                    crcs = None
                    if not host_verified:
                        with telemetry.span("combiner.crc_dispatch",
                                            **stage):
                            crcs = batch_block_crc_device(words, take)
                if pooled and off == len(reqs):
                    # Completion wait only — no readback. Inside the try:
                    # a device error here must take the same fall-back
                    # path as a failed device_put, not kill the consumer
                    # task.
                    with telemetry.span("combiner.release_wait", **stage):
                        await asyncio.to_thread(
                            jax.block_until_ready, shipped + [words]
                        )
            except asyncio.CancelledError:
                self._fail_out(reqs)
                raise
            except Exception as e:
                # A failed upload must not kill the consumer — with it gone
                # the producers would block forever on the full queue and
                # every later read would hang. Fall this sub-round back to
                # the per-block path (where a genuinely broken device
                # surfaces its own error) and keep consuming. The buffer's
                # state is unknown now: dropped, not pooled.
                logger.warning("fused upload failed (%s); falling back "
                               "%d blocks", e, take)
                pooled = False
                self._fall_back(sub)
                continue
            shipped.append(words)
            batch = DeviceBatch(words=words, crcs=crcs, cpb=cpb,
                                nblocks=take)
            if ec is not None:
                k, _m, slen = ec
                self.ec_rounds += 1
                self.ec_round_blocks += take
                self.ec_missing_data_shards += sum(
                    k - sum(slot < k for slot in r.use) for r in sub)
                self.ec_shard_bytes += take * k * slen
            else:
                self.rounds += 1
                self.blocks += take
            for i, r in enumerate(sub):
                db = DeviceBlock(
                    r.block["block_id"], None, r.size, host_verified,
                    expected_crc=int(r.block["checksum_crc32c"]),
                    source=r.block, device=self.device,
                    batch=batch, batch_index=i,
                    batch_pending=not host_verified,
                )
                if not r.fut.done():
                    r.fut.set_result(db)
        if pooled:
            self._put_buf(rows)

    def _reconstruct(self, reqs: list[_Req], stack: np.ndarray,
                     stage: dict) -> tuple[jax.Array, jax.Array]:
        """Worker thread: a (sub-)round of degraded erasure-coded blocks
        from its (n, k, row bytes) stack of surviving shards to what a
        replicated round hands on, the blocks' chunk grids as one
        (n * cpb, 128) array and its batched CRC, here over the
        RECONSTRUCTED bytes. One ``device_put`` of the stack, the decode
        program once a block (its inverse an operand: one program whatever
        the survivor sets of the round), one CRC program."""
        k, m, slen = reqs[0].ec
        with telemetry.span("ec.assemble", degraded=True, **stage):
            # A pooled stack keeps the last round's bytes; the decode
            # program wants zeros past a shard's end.
            stack[:, :, slen:] = 0
            survivors = stack.view("<u4").reshape(
                len(reqs), k, -1, WORDS_PER_CHUNK)
            mats = [decode_matrix_on(self._decode_matrices, self.device,
                                     k, m, r.use) for r in reqs]
        with telemetry.span("ec.device_put", degraded=True, **stage):
            shards = _unstack(jax.device_put(survivors, self.device))
        decoded = []
        for r, words, mat in zip(reqs, shards, mats):
            with telemetry.span("ec.decode_dispatch", request=None,
                                round=stage["round"],
                                block=r.block["block_id"]):
                decoded.append(rs_pallas.rs_decode_block(
                    words, mat, slen=slen, size=r.size))
        with telemetry.span("combiner.crc_dispatch", **stage):
            grid = _restack(*decoded)
            return grid, batch_block_crc_device(grid, len(reqs))

    # -------------------------------------------------------------- warmup

    def warm(self, cpb: int) -> None:
        """Pre-compile every bucket's batched-CRC program with H2D-only
        traffic (device_put of zeros + dispatch + completion wait, no
        readback) so no XLA compile lands inside a timed window.
        Host-verified rounds dispatch no device CRC — nothing to warm."""
        if self.host_verify:
            return
        b = 1
        while b <= self.max_batch:
            z = jax.device_put(
                np.zeros((b * cpb, WORDS_PER_CHUNK), dtype="<u4"), self.device
            )
            jax.block_until_ready(batch_block_crc_device(z, b))
            b <<= 1

    def warm_ec(self, k: int, m: int, block_bytes: int) -> None:
        """Pre-compile every program a round of degraded RS(k, m) blocks
        of ``block_bytes`` dispatches (``_reconstruct``), at every bucket:
        which servers are down does not matter, the inverse is an operand.
        The reconstructed bytes are verified on the device wherever the
        combiner runs, so this warms on every backend."""
        slen = shard_len(block_bytes, k)
        req = _Req(block={"block_id": "warm"}, path="",
                   cpb=block_bytes // CHECKSUM_CHUNK_SIZE, size=block_bytes,
                   ec=(k, m, slen), use=tuple(range(1, k + 1)))
        b = 1
        while b <= self.max_batch:
            stack = np.zeros((b, k, rs_pallas.decode_rows(slen)
                              * CHECKSUM_CHUNK_SIZE), dtype=np.uint8)
            jax.block_until_ready(self._reconstruct(
                [req] * b, stack,
                {"request": None, "round": 0, "blocks": b}))
            b <<= 1
