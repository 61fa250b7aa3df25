"""JAX/Grain infeed: DFS files streamed as training batches (the BASELINE
north star's "JAX/Grain infeed that streams training batches directly from
DFS chunks").

The reference's analogue is the s3a/Spark read path (test_scripts/
spark-s3-test/spark_s3_test.py) — a JVM copying bytes through CPU staging
buffers. Here the DFS is a first-class `grain` random-access data source:

- ``DfsRecordSource`` — fixed-size records carved out of DFS files, fetched
  by byte range through the DFS client (concurrent block fan-out, hedged
  reads, EC degraded reads all apply). Grain calls ``__getitem__`` from its
  prefetch workers/threads; the asyncio client runs on a dedicated event-loop
  thread and calls bridge via ``run_coroutine_threadsafe``.
- ``make_dataset`` — the one supported way to build the pipeline, for
  either source (``DfsRecordSource``, or ``wds.DfsWdsSource`` + a decode
  map): source -> (shard by JAX process) -> shuffle -> repeat -> decode ->
  ``to_iter_dataset()`` -> batch. Grain's prefetch threads (its default
  ``ReadOptions``: 16 threads, 500 records) fetch RECORDS
  concurrently under the overload governor's gate; batches are stacked
  after the prefetch, in the thread that iterates the dataset.
- ``device_iterator`` — lands every batch on the device (optionally a
  sharded jax.Array over a mesh axis) from a thread of its own, so the
  ``device_put`` of batch n overlaps the fetch and stacking of batch n+1;
  the training step takes HBM-resident arrays from a bounded hand-off.

The fetches run on threads of the caller's process, and a full pass of
Python's collector holds them all with the GIL: a trainer should
``gc.collect(); gc.freeze()`` once its set-up is over (docs/operations.md
"A trainer's heap").

Spans (``infeed.index``, ``infeed.fetch`` with its child
``infeed.gate_wait``, ``infeed.collate``, ``infeed.device_put``,
``infeed.next_wait``) and the sources' ``stats()`` are listed in
docs/operations.md "Tracing".

``tpudfs.tpu.infeed.DfsInfeed`` remains as the grain-free fallback prefetcher.
"""

from __future__ import annotations

import asyncio
import logging
import queue
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np

logger = logging.getLogger(__name__)

try:
    import grain

    if not hasattr(grain, "MapDataset"):
        # Some grain distributions install only the namespace package at the
        # top level, with the real API one level down.
        import grain.python as grain  # type: ignore[no-redef]

    _HAVE_GRAIN = True
except Exception as e:  # pragma: no cover - grain is installed in this image
    logger.debug("grain unavailable, DfsGrainSource disabled: %s", e)
    grain = None
    _HAVE_GRAIN = False

from tpudfs.client.client import Client, OverloadedError
from tpudfs.common import telemetry

#: Batches ``device_iterator`` holds on the device, put and not yet taken by
#: the consumer: the transfer of one overlaps the stacking of the next.
HANDOFF_DEPTH = 2


class _AdaptiveGate:
    """A semaphore whose limit can shrink/grow at runtime (threading
    semaphores can't resize). Grain prefetch workers block here, so lowering
    the limit IS lowering the effective prefetch depth."""

    def __init__(self, limit: int):
        self._cond = threading.Condition()
        self._limit = limit
        self._active = 0
        #: most fetches ever inside the gate at once
        self.max_active = 0

    def set_limit(self, n: int) -> None:
        with self._cond:
            self._limit = max(1, n)
            self._cond.notify_all()

    def acquire(self) -> None:
        with self._cond:
            while self._active >= self._limit:
                self._cond.wait()
            self._active += 1
            self.max_active = max(self.max_active, self._active)

    def release(self) -> None:
        with self._cond:
            self._active -= 1
            self._cond.notify_all()


class _OverloadGovernor:
    """Degradation ladder for shed fetches (cluster said RESOURCE_EXHAUSTED
    and the client's in-call retries ran out).

    Training infeed is throughput-, not latency-critical, so when the
    cluster sheds we cut *our own* pressure rather than hammering it:
    level 1 drops read hedges (each hedge is a whole duplicate replica
    read — the cheapest load to shed); each further level halves fetch
    concurrency down to 1. ``RECOVERY_SUCCESSES`` consecutive clean
    fetches climb one level back up, restoring hedges last-removed-first.
    """

    RECOVERY_SUCCESSES = 32
    MAX_LEVEL = 5  # 1 hedge drop + concurrency 16 -> 8 -> 4 -> 2 -> 1

    def __init__(self, max_concurrency: int = 16):
        self._lock = threading.Lock()
        self.max_concurrency = max_concurrency
        self.gate = _AdaptiveGate(max_concurrency)
        self.level = 0
        #: fetches the cluster shed (each stepped the ladder or found it at
        #: its last rung)
        self.sheds = 0
        self._streak = 0
        self._saved_hedge: float | None = None

    def _apply(self, client: Client) -> None:
        # Called under _lock. hedge_delay is a plain attribute read once per
        # client read call; cross-thread assignment is safe.
        if self.level >= 1:
            if client.hedge_delay is not None:
                self._saved_hedge = client.hedge_delay
                client.hedge_delay = None
        elif self._saved_hedge is not None:
            client.hedge_delay = self._saved_hedge
            self._saved_hedge = None
        self.gate.set_limit(self.max_concurrency >> max(0, self.level - 1))

    def on_overload(self, client: Client) -> float:
        """Step down one level; returns the backoff to sleep before retry."""
        with self._lock:
            self._streak = 0
            self.sheds += 1
            if self.level < self.MAX_LEVEL:
                self.level += 1
                self._apply(client)
                logger.warning(
                    "DFS overloaded: infeed degraded to level %d "
                    "(hedges %s, concurrency %d)", self.level,
                    "off" if self.level >= 1 else "on",
                    self.max_concurrency >> max(0, self.level - 1))
            return min(2.0, 0.1 * (2 ** self.level))

    def on_success(self, client: Client) -> None:
        with self._lock:
            if self.level == 0:
                return
            self._streak += 1
            if self._streak >= self.RECOVERY_SUCCESSES:
                self._streak = 0
                self.level -= 1
                self._apply(client)
                logger.info("DFS recovered: infeed back to level %d",
                            self.level)


class _ClientLoop:
    """A dedicated event-loop thread owning a DFS Client.

    grpc-aio channels bind to the loop that created them, so the Client is
    constructed inside this loop; sync callers (grain workers) submit
    coroutines with run_coroutine_threadsafe.
    """

    def __init__(self, master_addrs: Sequence[str], client_kwargs: dict):
        self._loop = asyncio.new_event_loop()
        # Calls in flight, so that ``close`` can end them: a prefetch
        # thread must not wait out its timeout on a loop that has stopped.
        self._pending: set = set()
        self._pending_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._loop.run_forever, daemon=True,
            name="tpudfs-grain-client",
        )
        self._thread.start()
        try:
            self.client: Client = self.run(
                self._make_client(list(master_addrs), client_kwargs)
            )
        except BaseException:
            self._shutdown_loop()
            raise

    @staticmethod
    async def _make_client(addrs: list[str], kwargs: dict) -> Client:
        return Client(addrs, **kwargs)

    def run(self, coro, timeout: float = 120.0) -> Any:
        try:
            fut = asyncio.run_coroutine_threadsafe(coro, self._loop)
        except RuntimeError:  # the loop is closed: nobody will await it
            coro.close()
            raise
        with self._pending_lock:
            self._pending.add(fut)
        try:
            return fut.result(timeout)
        except TimeoutError:
            # Don't let the orphaned coroutine keep running (and holding
            # RPCs in flight) after the caller has given up on it.
            fut.cancel()
            raise
        finally:
            with self._pending_lock:
                self._pending.discard(fut)

    def close(self) -> None:
        with self._pending_lock:
            in_flight = list(self._pending)
        for fut in in_flight:
            fut.cancel()
        try:
            self.run(self.client.close(), timeout=10.0)
        except Exception:
            logger.warning("DFS client close failed during infeed shutdown",
                           exc_info=True)
        self._shutdown_loop()

    def _shutdown_loop(self) -> None:
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5.0)
        if not self._thread.is_alive():
            self._loop.close()


class DfsSourceBase:
    """Shared plumbing for DFS-backed grain sources: a lazily-built
    per-process client/event-loop (pickle-safe for grain workers) and the
    file-metadata prefetch. Subclasses implement ``_build_index`` and the
    grain protocol.

    Concurrency model (audited against tpulint TPL011): ``_lock`` is a
    ``threading.Lock`` and must stay one. Every acquisition is on a
    synchronous grain-worker thread (``_client_loop`` via
    ``__getitem__``/``_fetch_metas``, and ``close``) — never on an event
    loop. The async side of this class lives entirely inside
    ``_ClientLoop``'s dedicated loop thread, which this lock guards the
    creation and teardown of but is never itself entered while holding
    it: ``_ClientLoop.__init__`` blocks the *worker* thread on
    ``run_coroutine_threadsafe`` while the loop thread does the async
    work. Converting to ``asyncio.Lock`` would be wrong (no loop exists
    on the acquiring threads); adding an ``await`` under this lock is
    impossible (no async defs in this module) and must stay that way.
    """

    def __init__(self, master_addrs: Sequence[str],
                 client_kwargs: dict | None = None,
                 tenant: str | None = None):
        self.master_addrs = list(master_addrs)
        self.client_kwargs = dict(client_kwargs or {})
        if tenant is not None:
            # Training reads are attributable: the per-process Client stamps
            # this identity on every RPC (x-tenant/_tn) so server-side QoS
            # charges the infeed its own fair share. The contextvar itself
            # can't cross into _ClientLoop's thread — the Client's per-op
            # scope is what carries it.
            self.client_kwargs.setdefault("tenant", tenant)
        # Held only on sync grain-worker threads; see class docstring.
        self._lock = threading.Lock()
        self._cl: _ClientLoop | None = None
        self._closed = False
        self._governor = _OverloadGovernor()
        # Immutable block layout per path, cached so record fetches skip the
        # per-read master GetFileInfo round-trip (read_meta_range fast path).
        self._metas: dict[str, dict] = {}
        self._counts = dict.fromkeys(("records", "bytes", "hop_ns",
                                      "loop_ns"), 0)
        # Leaf lock of ``stats()``'s sums: held for the adds alone, on sync
        # threads only, like ``_lock``.
        self._counts_lock = threading.Lock()
        # Block reads and ``ReadBlocks`` frames the client had issued when
        # the index was built: the tar-header walk's.
        self._index_block_reads = 0
        self._index_frames = 0

    def _client_loop(self) -> _ClientLoop:
        with self._lock:
            if self._closed:
                # A prefetch thread that outlived its pipeline: no new
                # client for it.
                raise RuntimeError(f"{self!r} is closed")
            if self._cl is None:
                self._cl = _ClientLoop(self.master_addrs, self.client_kwargs)
            return self._cl

    _OVERLOAD_RETRIES = 8

    def _governed_run(self, cl: _ClientLoop,
                      coro_factory: Callable[[], Any]) -> Any:
        """Run a fetch under the overload governor: gate concurrency, and on
        a shed fetch degrade (hedges off, then narrower gate), back off and
        retry — a training job should ride out overload, not crash on it."""
        gate = self._governor.gate
        with telemetry.span("infeed.gate_wait"):
            gate.acquire()
        try:
            for _ in range(self._OVERLOAD_RETRIES):
                try:
                    result = cl.run(coro_factory())
                except OverloadedError as e:
                    backoff = self._governor.on_overload(cl.client)
                    last = e
                    time.sleep(backoff)
                else:
                    self._governor.on_success(cl.client)
                    return result
            raise last
        finally:
            gate.release()

    def _fetch_range(self, path: str, offset: int, length: int) -> bytes:
        """One record: ``length`` bytes at ``offset`` of ``path``, through
        the governed fetch (gate wait, the hop onto the client's loop, one
        ranged ``ReadBlock`` a block touched) against the block layout
        cached at index time (``read_meta_range``: no master round-trip).
        Both sources' ``__getitem__`` come through here, on Grain's
        prefetch threads."""
        cl = self._client_loop()
        meta = self._metas[path]
        # perf_counter_ns at the hand-off to the loop, at the coroutine's
        # first step there and at its return (the try that succeeded).
        clocks = [0, 0, 0]

        async def on_loop() -> bytes:
            clocks[1] = time.perf_counter_ns()
            data = await cl.client.read_meta_range(meta, offset, length)
            clocks[2] = time.perf_counter_ns()
            return data

        def hand_off():
            clocks[0] = time.perf_counter_ns()
            return on_loop()

        with telemetry.span("infeed.fetch", bytes=length):
            data = self._governed_run(cl, hand_off)
        with self._counts_lock:
            self._counts["records"] += 1
            self._counts["bytes"] += len(data)
            self._counts["hop_ns"] += clocks[1] - clocks[0]
            self._counts["loop_ns"] += clocks[2] - clocks[1]
        return data

    def _issued(self) -> int:
        """Block reads the source's client has issued, counted where each
        is issued: every ``ReadBlock`` it sent (a hedge, and every replica
        tried after a failure, beside the primary) and every block it read
        off a colocated replica's disk."""
        cl = self._cl  # kept by ``close``: a closed source still says
        if cl is None:
            return 0
        return cl.client.read_block_calls + cl.client.local_read_blocks

    def _frames_sent(self) -> int:
        """``ReadBlocks`` frames the source's client has sent."""
        cl = self._cl
        return 0 if cl is None else cl.client.read_blocks_frames

    def stats(self) -> dict:
        """What the source did so far, in this process: ``records`` and
        ``bytes`` fetched; ``hop_ns``, their fetches' time from the hand-off
        on a Grain thread to the first step on the client's loop, and
        ``loop_ns``, from there to the read's return; ``range_reads`` (the
        block reads its client issued for them, ``_issued`` less the index
        walk's) and ``range_frames`` (the ``ReadBlocks`` frames it sent,
        less the walk's); the most fetches ever in flight at once (Grain's
        prefetch threads under the governor's gate), fetches the cluster
        shed and the governor's level now (0: hedges on, the whole
        gate)."""
        with self._counts_lock:
            out = dict(self._counts)
        out["range_reads"] = self._issued() - self._index_block_reads
        out["range_frames"] = self._frames_sent() - self._index_frames
        out["max_in_flight"] = self._governor.gate.max_active
        out["sheds"] = self._governor.sheds
        out["governor_level"] = self._governor.level
        return out

    def _fetch_metas(self, paths: Sequence[str]) -> list[dict]:
        """File metadata for every path, failing on missing files; kept
        for ``_fetch_range``."""
        cl = self._client_loop()

        async def metas(client: Client) -> list[dict]:
            out = await asyncio.gather(
                *(client.get_file_info(p) for p in paths)
            )
            for p, m in zip(paths, out):
                if m is None:
                    raise FileNotFoundError(f"DFS file not found: {p}")
            return out

        found = self._governed_run(cl, lambda: metas(cl.client))
        self._metas.update(zip(paths, found))
        return found

    def close(self) -> None:
        """Final: fetches in flight are cancelled, later ones raise."""
        with self._lock:
            if self._cl is not None and not self._closed:
                self._cl.close()
            self._closed = True

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_cl"] = None
        state["_lock"] = None
        state["_counts_lock"] = None
        state["_governor"] = None  # holds a Condition; rebuilt per process
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        # Fresh lock per unpickled worker process — same sync-only
        # discipline as the one dropped in __getstate__.
        self._lock = threading.Lock()
        self._counts_lock = threading.Lock()
        self._governor = _OverloadGovernor()
        # This process's client starts at 0: the walk was the parent's.
        self._index_block_reads = 0
        self._index_frames = 0


class DfsRecordSource(DfsSourceBase):
    """Grain ``RandomAccessDataSource`` over fixed-size records in DFS files.

    Each record is ``record_bytes`` consecutive bytes; file tails shorter
    than a record are dropped (standard fixed-length record semantics).
    Supports pickling for grain multiprocessing workers: the client/loop is
    re-created lazily per process.
    """

    def __init__(
        self,
        master_addrs: Sequence[str],
        paths: Sequence[str],
        record_bytes: int,
        dtype: str = "uint8",
        client_kwargs: dict | None = None,
        tenant: str | None = None,
    ):
        if record_bytes <= 0:
            raise ValueError("record_bytes must be positive")
        itemsize = np.dtype(dtype).itemsize
        if record_bytes % itemsize:
            raise ValueError(
                f"record_bytes={record_bytes} is not a multiple of "
                f"dtype {dtype} itemsize {itemsize}"
            )
        super().__init__(master_addrs, client_kwargs, tenant=tenant)
        self.paths = list(paths)
        self.record_bytes = int(record_bytes)
        self.dtype = dtype
        # (path, base_offset) per record, built once from file metadata.
        self._index: list[tuple[str, int]] = []
        try:
            self._build_index()
        except BaseException:
            # __init__ failed — the caller never gets an object to close(),
            # so tear down the client loop thread here.
            self.close()
            raise

    def _build_index(self) -> None:
        with telemetry.span("infeed.index", shards=len(self.paths)) as sp:
            for path, meta in zip(self.paths,
                                  self._fetch_metas(self.paths)):
                for off in range(0, int(meta["size"]) - self.record_bytes
                                 + 1, self.record_bytes):
                    self._index.append((path, off))
            sp.set(samples=len(self._index), range_reads=0)

    # ------------------------------------------------------- grain protocol

    def __len__(self) -> int:
        return len(self._index)

    def __getitem__(self, record_key: int) -> np.ndarray:
        path, off = self._index[record_key]
        data = self._fetch_range(path, off, self.record_bytes)
        return np.frombuffer(data, dtype=self.dtype)

    def __repr__(self) -> str:
        return (
            f"DfsRecordSource(files={len(self.paths)}, "
            f"records={len(self._index)}, record_bytes={self.record_bytes})"
        )


def _collate(samples: Sequence[Any]) -> Any:
    """``batch``'s stacking, as Grain's default does it (every leaf of the
    samples stacked along a new first dimension), under a span: the host
    staging copy of a batch."""
    import jax

    with telemetry.span("infeed.collate", request=None,
                        records=len(samples)) as sp:
        batch = jax.tree.map(lambda *leaves: np.stack(leaves), *samples)
        sp.set(bytes=sum(leaf.nbytes for leaf in jax.tree.leaves(batch)))
    return batch


def make_dataset(
    source: DfsSourceBase,
    *,
    batch_size: int,
    shuffle_seed: int | None = None,
    shard_by_process: bool = True,
    num_epochs: int | None = 1,
    decode: Callable[[Any], Any] | None = None,
):
    """Build the grain pipeline for either source: source -> shard ->
    shuffle -> repeat -> decode -> prefetch -> batch.

    ``source`` is a ``DfsRecordSource`` (elements are arrays already) or a
    ``wds.DfsWdsSource`` with ``decode`` the per-sample map from its member
    dicts to arrays (e.g. ``wds.decode_sample``). The prefetch is Grain's
    own (its default ``ReadOptions``: 16 threads, the governor's gate, and
    a buffer of 500 records) and fetches RECORDS concurrently under the
    gate; batches are stacked after it, so 16 records, not 16 batches, are
    in flight. ``repeat`` comes before ``batch``: batches cross epoch ends,
    every epoch is a fresh permutation (``reseed_each_epoch``) and the
    order is a function of the seed alone.

    Returns a ``grain.IterDataset`` yielding numpy batches of
    ``batch_size`` elements (a partial last batch is dropped)."""
    if not _HAVE_GRAIN:
        raise RuntimeError("grain is not installed; use tpudfs.tpu.infeed")
    ds = grain.MapDataset.source(source)
    if shard_by_process:
        import jax

        ds = ds[jax.process_index():: jax.process_count()]
    if shuffle_seed is not None:
        ds = ds.shuffle(seed=shuffle_seed)
    if num_epochs is None:
        ds = ds.repeat()
    elif num_epochs > 1:
        ds = ds.repeat(num_epochs)
    if decode is not None:
        ds = ds.map(decode)
    return ds.to_iter_dataset().batch(
        batch_size, drop_remainder=True, batch_fn=_collate)


def device_iterator(dataset, devices=None, mesh=None, axis: str | None = None):
    """Iterate a grain dataset, landing each batch in HBM.

    - default: ``jax.device_put`` to the first device;
    - with ``mesh``+``axis``: batches become jax.Arrays sharded over that
      mesh axis (batch dim split across devices) — the data-parallel infeed
      layout for a pjit training step.

    A thread of its own takes batches from ``dataset``, puts each on the
    device and waits for the transfer, and hands it over through a queue of
    ``HANDOFF_DEPTH``: the transfer of batch n overlaps the fetch and
    stacking of batch n+1, and what the caller takes is on the device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is not None:
        where = NamedSharding(mesh, P(axis or mesh.axis_names[0]))
    else:
        where = (devices or jax.devices())[0]
    handoff: queue.Queue = queue.Queue(maxsize=HANDOFF_DEPTH)
    stop = threading.Event()
    end = object()

    def offer(item) -> bool:
        while not stop.is_set():
            try:
                handoff.put(item, timeout=0.1)
                return True
            except queue.Full:
                pass
        return False

    def produce() -> None:
        batches = iter(dataset)
        try:
            for batch in batches:
                with telemetry.span("infeed.device_put", request=None):
                    landed = jax.block_until_ready(
                        jax.device_put(batch, where))
                if not offer(landed):
                    return
            offer(end)
        except BaseException as e:  # handed to the consumer, which raises
            offer(e)
        finally:
            # Grain's prefetch threads stop with their iterator.
            close = getattr(batches, "close", None)
            if close is not None:
                close()

    producer = threading.Thread(target=produce, daemon=True,
                                name="tpudfs-infeed-put")
    producer.start()
    try:
        while True:
            with telemetry.span("infeed.next_wait", request=None):
                item = handoff.get()
            if item is end:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        producer.join(timeout=30.0)
