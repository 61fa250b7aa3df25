"""Master service: the namespace gRPC front + background maintenance loops.

Model: reference dfs/metaserver/src/master.rs MyMaster (RPC handlers
master.rs:2179-3660) and its background tasks (master.rs:712-1427 +
bin/master.rs:230-238):

- namespace RPCs gated by safe mode (master.rs:2163-2173) and, once sharding
  lands, shard ownership (REDIRECT, master.rs:2141-2159);
- linearizable reads via the Raft ReadIndex barrier (ensure_linearizable_read,
  master.rs:1911);
- AllocateBlock picks replicas rack-aware from live chunkservers and returns
  the allocating master's Raft term for epoch fencing (master.rs:2351);
- Heartbeat updates soft state, reports bad blocks, drains the per-CS command
  queue stamped with the current term (master.rs:2596-2723);
- liveness checker drops silent CSes after 15 s and heals (master.rs:729-760);
  periodic healer (master.rs:762-775); block balancer (master.rs:777-845);
- tiering scanner marks cold files and schedules EC policy conversion
  (scan_tiering / scan_ec_conversion, master.rs:1933-2138).
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
import uuid

from tpudfs.common import ckptpaths
from tpudfs.common.resilience import (
    admission_controlled,
    shedder_from_env,
    shielded_from_deadline,
)
from tpudfs.common.rpc import RpcClient, RpcError, RpcServer
from tpudfs.common.sharding import ShardMap
from tpudfs.master import autoshard, placement
from tpudfs.master.state import (
    MasterState,
    REPLICATION_FACTOR,
    now_ms,
)
from tpudfs.master.transactions import TransactionManager
from tpudfs.raft.core import NotLeaderError, Timings
from tpudfs.raft.node import RaftNode

logger = logging.getLogger(__name__)

SERVICE = "MasterService"
CONFIG_SERVICE = "ConfigService"

LIVENESS_CUTOFF_MS = 15_000  # reference master.rs:740-757
LIVENESS_INTERVAL = 5.0
HEALER_INTERVAL = 300.0
BALANCER_INTERVAL = 30.0
TIERING_INTERVAL = 60.0
EC_MIGRATION_RETRY_SECS = 60.0  # re-issue CONVERT_TO_EC after this silence
SHARD_REFRESH_INTERVAL = 5.0  # reference master.rs:1429
TX_CLEANUP_INTERVAL = 5.0  # reference master.rs:968
TX_RECOVERY_INTERVAL = 30.0  # reference master.rs:1171
METRICS_DECAY_INTERVAL = 5.0  # reference master.rs:1421-1427
SPLIT_DETECTOR_INTERVAL = 5.0  # reference master.rs:1495
DATA_SHUFFLER_INTERVAL = 10.0  # reference master.rs:1325
STAGED_INGEST_TTL_MS = 600_000  # abandoned-stage GC horizon
CKPT_GC_INTERVAL = 60.0  # incomplete-checkpoint staging GC cadence
#: Unpublished staging files older than this are collectable even when no
#: newer checkpoint superseded them (env-overridable for chaos/tests).
CKPT_GC_AGE_SECS = 3600.0
#: Per-cycle delete cap: GC is a janitor, not a bulk deleter — it must not
#: monopolize the Raft pipeline right after a big checkpoint is abandoned.
CKPT_GC_MAX_DELETES = 64
DEFAULT_COLD_THRESHOLD_SECS = 7 * 24 * 3600  # reference: COLD_THRESHOLD_SECS
DEFAULT_EC_THRESHOLD_SECS = 30 * 24 * 3600  # reference: EC_THRESHOLD_SECS
EC_CONVERSION_SHAPE = (6, 3)  # reference RS(6,3), master.rs:2016-2138


def _parse_ec_shape(value: str) -> tuple[int, int]:
    """Validate an EC_SHAPE env value ("k,m") at startup — a malformed or
    degenerate shape must fail fast, not persist an unusable policy into
    the replicated metadata."""
    parts = [p.strip() for p in value.split(",")]
    if len(parts) != 2 or not all(parts):
        raise ValueError(f'EC_SHAPE must be "k,m", got {value!r}')
    try:
        k, m = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(f'EC_SHAPE must be "k,m" integers, got {value!r}')
    return k, m


class Master:
    def __init__(
        self,
        address: str,
        peers: list[str],
        data_dir: str,
        *,
        shard_id: str = "shard-0",
        config_servers: list[str] | None = None,
        raft_timings: Timings | None = None,
        rpc_client: RpcClient | None = None,
        cold_threshold_secs: int | None = None,
        ec_threshold_secs: int | None = None,
        ec_shape: tuple[int, int] | None = None,
        liveness_cutoff_ms: int = LIVENESS_CUTOFF_MS,
        intervals: dict | None = None,
        split_threshold_rps: float = 100.0,
        merge_threshold_rps: float = -1.0,
        split_cooldown_secs: float = 30.0,
        snapshot_backup=None,
    ):
        self.address = address
        self.config_servers = list(config_servers or [])
        self.shard_map: ShardMap | None = None
        self.state = MasterState(shard_id)
        self.state.enter_safe_mode()
        self._owns_client = rpc_client is None
        self.client = rpc_client or RpcClient()
        self.raft = RaftNode(
            address, peers, data_dir,
            apply=self.state.apply,
            snapshot=self.state.snapshot,
            restore=self.state.restore,
            timings=raft_timings,
            rpc_client=self.client,
            snapshot_backup=snapshot_backup,
        )
        self.cold_threshold_ms = 1000 * (
            cold_threshold_secs
            if cold_threshold_secs is not None
            else int(os.environ.get("COLD_THRESHOLD_SECS", DEFAULT_COLD_THRESHOLD_SECS))
        )
        self.ec_threshold_ms = 1000 * (
            ec_threshold_secs
            if ec_threshold_secs is not None
            else int(os.environ.get("EC_THRESHOLD_SECS", DEFAULT_EC_THRESHOLD_SECS))
        )
        if ec_shape:
            self.ec_shape = tuple(ec_shape)
        elif os.environ.get("EC_SHAPE"):  # "k,m" — env-driven like the
            self.ec_shape = _parse_ec_shape(os.environ["EC_SHAPE"])
        else:
            self.ec_shape = EC_CONVERSION_SHAPE
        k_, m_ = self.ec_shape
        if k_ < 1 or m_ < 1 or k_ + m_ > 64:
            raise ValueError(f"invalid EC shape RS({k_},{m_})")
        #: block_id -> in-flight CONVERT_TO_EC attempt (leader soft state):
        #: {"ts", "new_id", "targets", "stale": [(new_id, targets), ...]}.
        #: Re-issued after EC_MIGRATION_RETRY_SECS; each attempt gets a
        #: UNIQUE new block id so a slow earlier attempt can never mix its
        #: shard writes into a later attempt's positional layout.
        self._ec_migrations: dict[str, dict] = {}
        self.liveness_cutoff_ms = liveness_cutoff_ms
        iv = intervals or {}
        self._intervals = {
            "liveness": iv.get("liveness", LIVENESS_INTERVAL),
            "healer": iv.get("healer", HEALER_INTERVAL),
            "balancer": iv.get("balancer", BALANCER_INTERVAL),
            "tiering": iv.get("tiering", TIERING_INTERVAL),
            "shard_refresh": iv.get("shard_refresh", SHARD_REFRESH_INTERVAL),
            "tx_cleanup": iv.get("tx_cleanup", TX_CLEANUP_INTERVAL),
            "tx_recovery": iv.get("tx_recovery", TX_RECOVERY_INTERVAL),
            "metrics_decay": iv.get("metrics_decay", METRICS_DECAY_INTERVAL),
            "split_detector": iv.get("split_detector", SPLIT_DETECTOR_INTERVAL),
            "data_shuffler": iv.get("data_shuffler", DATA_SHUFFLER_INTERVAL),
            "ckpt_gc": iv.get("ckpt_gc", CKPT_GC_INTERVAL),
        }
        #: Staging files removed by the incomplete-checkpoint GC
        #: (observability/tests).
        self.ckpt_gc_deleted = 0
        self.monitor = autoshard.ThroughputMonitor(
            split_threshold_rps=split_threshold_rps,
            merge_threshold_rps=merge_threshold_rps,
            split_cooldown_secs=split_cooldown_secs,
            interval_secs=self._intervals["metrics_decay"],
        )
        self.tx = TransactionManager(self)
        # Namespace-RPC admission control. Control-plane traffic (heartbeats,
        # registration, Raft membership, safe mode, 2PC coordination) is
        # exempt: shedding it under load would turn congestion into false
        # liveness failures and stuck transactions.
        # TPUDFS_QOS=1 upgrades this to the tenant-aware QosShedder
        # (weighted-fair queue + per-tenant rate limits); default stays the
        # flat LoadShedder.
        self.shedder = shedder_from_env("TPUDFS_MASTER_MAX_INFLIGHT", 256)
        self._tasks: set[asyncio.Task] = set()
        #: Coalesced access-stats (see _note_access): path -> (at_ms, count)
        #: pending since the last batched proposal.
        self._access_pending: dict[str, tuple[int, int]] = {}
        self._access_flusher: asyncio.Task | None = None
        self.access_stats_flush_s = 0.5

    # --------------------------------------------------------------- wiring

    def handlers(self) -> dict:
        return {
            "GetFileInfo": self.rpc_get_file_info,
            "BatchGetFileInfo": self.rpc_batch_get_file_info,
            "CreateFile": self.rpc_create_file,
            "DeleteFile": self.rpc_delete_file,
            "AllocateBlock": self.rpc_allocate_block,
            "CompleteFile": self.rpc_complete_file,
            "ListFiles": self.rpc_list_files,
            "GetBlockLocations": self.rpc_get_block_locations,
            "Heartbeat": self.rpc_heartbeat,
            "RegisterChunkServer": self.rpc_register_chunk_server,
            "Rename": self.rpc_rename,
            "PublishCheckpoint": self.rpc_publish_checkpoint,
            "SafeModeStatus": self.rpc_safe_mode_status,
            "EnterSafeMode": self.rpc_enter_safe_mode,
            "ExitSafeMode": self.rpc_exit_safe_mode,
            "AddRaftNode": self.rpc_add_raft_node,
            "RemoveRaftNode": self.rpc_remove_raft_node,
            "TransferLeadership": self.rpc_transfer_leadership,
            "RaftState": self.rpc_raft_state,
            "PrepareTransaction": self.tx.rpc_prepare,
            "CommitTransaction": self.tx.rpc_commit,
            "AbortTransaction": self.tx.rpc_abort,
            "InquireTransaction": self.tx.rpc_inquire,
            "CompleteEcConversion": self.rpc_complete_ec_conversion,
            "IngestMetadata": self.rpc_ingest_metadata,
            "InitiateShuffle": self.rpc_initiate_shuffle,
            "StageIngest": self.rpc_stage_ingest,
            "CommitStagedIngest": self.rpc_commit_staged_ingest,
            "DropStagedIngest": self.rpc_drop_staged_ingest,
        }

    def attach(self, server: RpcServer) -> None:
        server.add_service(SERVICE, self.handlers())
        self.raft.attach(server)

    async def start(self, background_tasks: bool = True) -> None:
        await self.raft.start()
        if background_tasks:
            self._spawn(self._loop(self._intervals["liveness"], self.run_liveness_check))
            self._spawn(self._loop(self._intervals["healer"], self.run_healer))
            self._spawn(self._loop(self._intervals["balancer"], self.run_balancer))
            self._spawn(self._loop(self._intervals["tiering"], self.run_tiering_scan))
            self._spawn(self._loop(self._intervals["tx_cleanup"], self.tx.run_cleanup))
            self._spawn(self._loop(self._intervals["tx_recovery"], self.tx.run_recovery))
            self._spawn(self._loop(self._intervals["metrics_decay"],
                                   self.run_metrics_decay))
            self._spawn(self._loop(self._intervals["data_shuffler"],
                                   self.run_data_shuffler))
            self._spawn(self._loop(self._intervals["ckpt_gc"],
                                   self.run_ckpt_gc))
            if self.config_servers:
                # Prime the map BEFORE serving: without it a sharded master
                # can't tell its keys from a peer's and could e.g. apply a
                # cross-shard rename as a local one. Retries cover config
                # Raft still electing at boot, bounded by wall-clock (each
                # attempt can itself burn several RPC timeouts against
                # blackholed config servers); _check_shard_ownership fails
                # closed if this deadline passes without a map.
                deadline = asyncio.get_running_loop().time() + 30.0
                while asyncio.get_running_loop().time() < deadline:
                    await self.run_shard_refresh()
                    if self.shard_map is not None:
                        break
                    await asyncio.sleep(0.5)
                self._spawn(self._loop(self._intervals["shard_refresh"],
                                       self.run_shard_refresh))
                self._spawn(self._loop(self._intervals["split_detector"],
                                       self.run_split_detector))

    def _spawn(self, coro) -> asyncio.Task:
        task = asyncio.create_task(coro)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
        return task

    async def _loop(self, interval: float, fn) -> None:
        while True:
            await asyncio.sleep(interval)
            try:
                await fn()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("background task %s failed", fn.__name__)

    async def stop(self) -> None:
        for t in list(self._tasks):
            t.cancel()
        self._tasks.clear()
        await self.raft.stop()
        if self._owns_client:
            await self.client.close()

    # -------------------------------------------------------------- helpers

    async def _propose(self, cmd: dict):
        try:
            return await self.raft.propose(cmd)
        except NotLeaderError as e:
            raise RpcError.not_leader(e.leader_hint) from None
        except ValueError as e:
            msg = str(e)
            if "not found" in msg:
                raise RpcError.not_found(msg) from None
            if "exists" in msg:
                raise RpcError.already_exists(msg) from None
            raise RpcError.invalid(msg) from None

    async def _linearizable_read(self) -> None:
        """ReadIndex barrier before serving metadata reads
        (reference master.rs:1911)."""
        try:
            await self.raft.read_index()
        except NotLeaderError as e:
            raise RpcError.not_leader(e.leader_hint) from None

    def _check_safe_mode(self) -> None:
        if self.state.safe_mode and self.state.should_exit_safe_mode():
            self.state.exit_safe_mode()
        if self.state.safe_mode:
            raise RpcError.unavailable(
                "Master is in safe mode; writes are temporarily disabled"
            )

    def _check_tx_lock(self, *paths: str) -> None:
        """Reject namespace ops on paths reserved by an in-flight 2PC tx
        (prepared-window isolation — without it a concurrent CreateFile on a
        rename destination is clobbered at commit, and a DeleteFile of the
        source frees blocks the committed destination still references)."""
        locked = self.state.tx_locked_paths()
        for p in paths:
            if p in locked:
                raise RpcError.failed_precondition(
                    f"path {p!r} is locked by an in-flight transaction"
                )

    def _check_migration_freeze(self, *paths: str) -> None:
        """Writes in a range with an open outgoing migration are frozen
        until the handoff completes (or aborts): an acknowledged write after
        the metadata snapshot was staged would be silently lost when the
        target publishes the stage. Reads keep being served from our copy
        until the map flips."""
        for p in paths:
            if self.state.migrating_out(p):
                raise RpcError.unavailable(
                    f"range containing {p!r} is migrating to another shard; "
                    "retry shortly"
                )

    def _owner_shard(self, path: str) -> str | None:
        if self.shard_map is None:
            return None
        return self.shard_map.get_shard(path)

    def _check_shard_ownership(self, path: str) -> None:
        """REDIRECT:<owning-shard> for keys outside our range (reference
        check_shard_ownership master.rs:2141-2159). A sharded master whose
        map hasn't loaded yet fails CLOSED (it can't tell its keys from a
        peer's); an unsharded one (no config servers) skips the check, as
        does one whose shard isn't in the map yet (bootstrap)."""
        if not self.state.shard_id:
            # Spare (unassigned) master: it owns no range at all, so every
            # namespace op fails closed until a split allocates it a shard.
            raise RpcError.unavailable(
                "master not yet assigned to a shard; retry shortly"
            )
        if self.shard_map is None:
            if self.config_servers:
                raise RpcError.unavailable(
                    "shard map not yet loaded; retry shortly"
                )
            return
        if self.state.staged_in(path):
            # We own this range per the map (or soon will), but its metadata
            # is still staged, not published: unavailable — NOT found=False,
            # which would 404 existing files and let new writes be clobbered
            # by the staged publish.
            raise RpcError.unavailable(
                f"range containing {path!r} is migrating in; retry shortly"
            )
        if not self.shard_map.has_shard(self.state.shard_id):
            return
        owner = self.shard_map.get_shard(path)
        if owner is not None and owner != self.state.shard_id:
            raise RpcError.redirect(owner)

    async def call_shard(self, shard_id: str, method: str, req: dict,
                         attempts: int = 4) -> dict:
        """RPC to another shard's master group, following Not-Leader hints
        (the master-to-master path of the 2PC/sharding flows)."""
        peers = (self.shard_map.get_peers(shard_id) or []) \
            if self.shard_map else []
        if not peers:
            raise RpcError.unavailable(f"no peers known for shard {shard_id}")
        last: RpcError | None = None
        idx = 0
        for _ in range(attempts):
            target = peers[idx % len(peers)]
            try:
                return await self.client.call(target, SERVICE, method, req,
                                              timeout=10.0)
            except RpcError as e:
                last = e
                if e.is_not_leader:
                    hint = e.not_leader_hint
                    if hint:
                        if hint in peers:
                            idx = peers.index(hint)
                        else:
                            peers.insert(0, hint)
                            idx = 0
                    else:
                        # Mid-election, no hint yet: try the next peer
                        # rather than failing the whole cross-shard op.
                        idx += 1
                        await asyncio.sleep(0.2)
                    continue
                if e.code.name in ("INVALID_ARGUMENT", "NOT_FOUND",
                                   "ALREADY_EXISTS", "FAILED_PRECONDITION"):
                    raise
                idx += 1
                await asyncio.sleep(0.2)
        raise last if last is not None else RpcError.unavailable(
            f"shard {shard_id} unreachable"
        )

    async def call_config(self, method: str, req: dict) -> dict:
        """RPC to the Config Server group, following Not-Leader hints."""
        targets = list(self.config_servers)
        if not targets:
            raise RpcError.unavailable("no config servers configured")
        last: RpcError | None = None
        for _ in range(len(targets) + 2):
            target = targets[0]
            try:
                return await self.client.call(target, CONFIG_SERVICE, method,
                                              req, timeout=10.0)
            except RpcError as e:
                last = e
                hint = e.not_leader_hint
                if hint and hint != target:
                    targets = [hint] + [t for t in targets if t != hint]
                    continue
                targets = targets[1:] + targets[:1]
        raise last if last is not None else RpcError.unavailable(
            "config servers unreachable"
        )

    @staticmethod
    def _new_block_id() -> str:
        return f"blk-{uuid.uuid4().hex}"

    # ------------------------------------------------------- namespace RPCs

    @admission_controlled
    async def rpc_create_file(self, req: dict) -> dict:
        self._check_safe_mode()
        self._check_shard_ownership(req["path"])
        self._check_migration_freeze(req["path"])
        self._check_tx_lock(req["path"])
        self.monitor.record(req["path"])
        # Write-session token: minted here, replicated in the command (so
        # apply is deterministic), enforced by the state machine on every
        # AllocateBlock/CompleteFile of this file — two interleaved create
        # sessions can never graft blocks onto each other's file.
        token = uuid.uuid4().hex
        await self._propose({
            "op": "create_file",
            "path": req["path"],
            "ec_data_shards": int(req.get("ec_data_shards") or 0),
            "ec_parity_shards": int(req.get("ec_parity_shards") or 0),
            "created_at_ms": now_ms(),
            "overwrite": bool(req.get("overwrite")),
            "token": token,
        })
        if not req.get("first_block"):
            return {"success": True, "write_token": token}
        # Fused create+allocate: the common single-client write path pays
        # one master round-trip (and envelope) instead of two — the
        # reference issues CreateFile then AllocateBlock separately
        # (mod.rs:225-266). Allocation failures (no chunkservers yet)
        # surface as alloc_error rather than failing the create, so the
        # client can fall back to its per-block AllocateBlock retry loop.
        try:
            alloc = await self.rpc_allocate_block(
                {"path": req["path"], "token": token}
            )
        except RpcError as e:
            return {"success": True, "write_token": token,
                    "alloc_error": e.message}
        return {"success": True, "write_token": token, **alloc}

    @admission_controlled
    async def rpc_allocate_block(self, req: dict) -> dict:
        self._check_safe_mode()
        self._check_shard_ownership(req["path"])
        self._check_migration_freeze(req["path"])
        # Leadership first: a follower's local state may lag, and the client
        # must get a redirect rather than a spurious not_found.
        if not self.raft.is_leader:
            raise RpcError.not_leader(self.raft.leader_hint)
        path = req["path"]
        f = self.state.files.get(path)
        if f is None:
            raise RpcError.not_found(f"file not found: {path}")
        k, m = f.ec_data_shards, f.ec_parity_shards
        count = (k + m) if k > 0 else REPLICATION_FACTOR
        servers = placement.select_servers_rack_aware(
            list(self.state.chunk_servers.items()), count
        )
        if k == 0:
            # Prefer a collective-write-group successor chain when one is
            # advertised: that replica set lets the primary replicate the
            # block as ICI ppermute rounds (tpudfs.tpu.write_group).
            chain = placement.select_ici_chain(
                self.state.chunk_servers, servers, count)
            if chain:
                servers = chain
        if k > 0 and len(servers) < count:
            raise RpcError.unavailable(
                f"EC({k},{m}) needs {count} chunkservers, have {len(servers)}"
            )
        if not servers:
            raise RpcError.unavailable("no chunkservers available")
        block_id = self._new_block_id()
        result = await self._propose({
            "op": "allocate_block",
            "path": path,
            "block_id": block_id,
            "locations": servers,
            "ec_data_shards": k,
            "ec_parity_shards": m,
            "token": str(req.get("token") or ""),
        })
        return {
            "block": result["block"],
            "chunk_server_addresses": servers,
            "ec_data_shards": k,
            "ec_parity_shards": m,
            "master_term": self.raft.core.term,
            # Fencing epoch is (shard, term): chunkservers scope stale-term
            # checks to the issuing Raft group.
            "shard_id": self.state.shard_id,
        }

    @admission_controlled
    async def rpc_complete_file(self, req: dict) -> dict:
        self._check_safe_mode()
        self._check_shard_ownership(req["path"])
        self._check_migration_freeze(req["path"])
        self._check_tx_lock(req["path"])
        self.monitor.record(req["path"], int(req["size"]))
        await self._propose({
            "op": "complete_file",
            "path": req["path"],
            "size": int(req["size"]),
            "etag_md5": req.get("etag_md5", ""),
            "attrs": req.get("attrs") or {},
            "created_at_ms": int(req.get("created_at_ms") or now_ms()),
            "block_checksums": req.get("block_checksums") or [],
            "token": str(req.get("token") or ""),
        })
        return {"success": True}

    @admission_controlled
    async def rpc_get_file_info(self, req: dict) -> dict:
        self._check_shard_ownership(req["path"])
        await self._linearizable_read()
        f = self.state.get_file(req["path"])
        self.monitor.record(req["path"], f.size if f else 0)
        if f is None:
            return {"found": False, "metadata": None}
        # Fire-and-forget access-stats update for tiering
        # (reference master.rs:2190-2209) — coalesced: under a read-heavy
        # infeed, a Raft proposal per GetFileInfo makes the metadata plane
        # pay one log append per read; pending updates flush as ONE
        # replicated command per window instead.
        self._note_access(req["path"])
        return {"found": True, "metadata": self._public_meta(f)}

    @staticmethod
    def _public_meta(f) -> dict:
        """Client-visible metadata: the live write-session token must not
        leave the master (a reader who copied it could forge the fence)."""
        d = f.to_dict()
        d.pop("create_token", None)
        return d

    @admission_controlled
    async def rpc_batch_get_file_info(self, req: dict) -> dict:
        """Coalesced GetFileInfo: ONE ReadIndex/lease barrier covers the
        whole batch. Linearizability per caller is preserved — every
        coalesced invocation happens-before the barrier and returns after
        it, so the barrier is a valid linearization point for each. Paths
        this shard can't serve (REDIRECT/unavailable) get a per-path
        ``retry`` marker — the client re-issues those individually through
        its full retry/redirect machinery — so one misrouted path can't
        fail a whole batch."""
        await self._linearizable_read()
        results = []
        for path in req.get("paths") or []:
            try:
                self._check_shard_ownership(path)
            except RpcError as e:
                results.append({"retry": True, "why": e.message})
                continue
            f = self.state.get_file(path)
            self.monitor.record(path, f.size if f else 0)
            if f is None:
                results.append({"found": False, "metadata": None})
            else:
                self._note_access(path)
                results.append({"found": True,
                                "metadata": self._public_meta(f)})
        return {"results": results}

    def _note_access(self, path: str) -> None:
        at, count = self._access_pending.get(path, (0, 0))
        self._access_pending[path] = (now_ms(), count + 1)
        if self._access_flusher is None or self._access_flusher.done():
            self._access_flusher = self._spawn(self._flush_access_stats())

    async def _flush_access_stats(self) -> None:
        # Loop until a window stays empty: accesses noted while a propose
        # was in flight land in the fresh dict, and _note_access won't
        # spawn a second flusher while this one is alive — exiting after
        # one window would strand them until the next read.
        while True:
            await asyncio.sleep(self.access_stats_flush_s)
            pending, self._access_pending = self._access_pending, {}
            if not pending:
                return
            try:
                await self.raft.propose({
                    "op": "update_access_stats_batch",
                    "updates": [
                        [path, at, count]
                        for path, (at, count) in pending.items()
                    ],
                })
            except (NotLeaderError, ValueError):
                return

    @admission_controlled
    async def rpc_delete_file(self, req: dict) -> dict:
        self._check_safe_mode()
        self._check_shard_ownership(req["path"])
        self._check_migration_freeze(req["path"])
        self._check_tx_lock(req["path"])
        await self._propose({"op": "delete_file", "path": req["path"]})
        return {"success": True}

    @admission_controlled
    async def rpc_rename(self, req: dict) -> dict:
        """Rename: same-shard fast path through one Raft command
        (master.rs:2777-2808), cross-shard via the 2PC coordinator
        (master.rs:2809-3021)."""
        self._check_safe_mode()
        src, dst = req["src"], req["dst"]
        # Leadership first: only the leader's map decides the rename, and
        # bouncing off followers must not each pay a linearizable
        # cross-group FetchShardMap round trip.
        if not self.raft.is_leader:
            raise RpcError.not_leader(self.raft.leader_hint)
        # Rename is the one op where a stale shard map corrupts the
        # namespace (a cross-shard rename mistaken for same-shard creates
        # the destination in a keyspace this shard doesn't own), so fetch a
        # fresh map before deciding; renames are rare enough to afford it.
        if self.config_servers:
            try:
                resp = await self.call_config("FetchShardMap", {})
                self.shard_map = ShardMap.from_dict(resp["shard_map"])
            except RpcError as e:
                logger.warning("rename: shard map refresh failed (%s); "
                               "using cached map", e.message)
        self._check_shard_ownership(src)
        self._check_migration_freeze(src, dst)
        self._check_tx_lock(src, dst)
        replace = bool(req.get("replace"))
        dest_shard = self._owner_shard(dst)
        if dest_shard is None or dest_shard == self.state.shard_id:
            await self._propose({"op": "rename_file", "src": src, "dst": dst,
                                 "replace": replace})
            return {"success": True}
        await self.tx.run_cross_shard_rename(src, dst, dest_shard,
                                             replace=replace)
        return {"success": True, "cross_shard": True}

    @admission_controlled
    async def rpc_publish_checkpoint(self, req: dict) -> dict:
        """Phase two of the two-phase checkpoint commit (see
        tpudfs/tpu/checkpoint.py + docs/checkpoint.md): atomically rename
        the staged manifest to its published ``MANIFEST-{step}`` name. The
        checkpoint-specific invariants — idempotent re-publish, monotonic
        steps per base, staged manifest must be complete — live in
        ``_apply_publish_checkpoint``, the authoritative ordering point."""
        self._check_safe_mode()
        src, dst = req["src"], req["dst"]
        self._check_shard_ownership(src)
        self._check_shard_ownership(dst)
        self._check_migration_freeze(src, dst)
        self._check_tx_lock(src, dst)
        result = await self._propose({
            "op": "publish_checkpoint", "src": src, "dst": dst,
            "base": req["base"], "step": int(req["step"]),
        })
        return {"success": True,
                "already_published": bool(result.get("already_published"))}

    async def run_ckpt_gc(self) -> None:
        """Collect unpublished checkpoint staging prefixes.

        A staging file (any path under ``{base}/.ckpt/{step}/``) is garbage
        once its step has no published manifest AND either a newer step was
        published for the same base (the save was superseded — a preempted
        writer's publish would be rejected as stale anyway) or the file is
        older than TPUDFS_CKPT_GC_AGE_SECS. Files of *published* steps are
        the checkpoint's data and are never touched here — only an explicit
        prune removes them, manifest first.

        Control-plane exemption (the PR-4 scrubber treatment): this loop
        proposes directly — NOT through the admission-controlled RPC
        surface — and runs shielded from any ambient deadline, because GC
        must make progress exactly when the cluster is overloaded or
        budget-starved; shedding or deadline-aborting it would turn
        congestion into a permanent storage leak."""
        if not self.raft.is_leader or self.state.safe_mode:
            return
        with shielded_from_deadline():
            ttl_ms = int(1000 * float(
                os.environ.get("TPUDFS_CKPT_GC_AGE_SECS", CKPT_GC_AGE_SECS)))
            at = now_ms()
            published: dict[str, set[int]] = {}
            latest: dict[str, int] = {}
            for p, f in self.state.files.items():
                parsed = ckptpaths.parse_manifest_path(p)
                if parsed is None or not f.complete:
                    continue
                base, step = parsed
                published.setdefault(base, set()).add(step)
                latest[base] = max(latest.get(base, -1), step)
            doomed: list[str] = []
            # Incomplete files (a writer SIGKILLed mid-put) are collectable
            # too — they hold chunkserver blocks but are invisible to
            # clients, so only this scan can ever free them.
            for p, f in self.state.files.items():
                parsed = ckptpaths.parse_step_path(p)
                if parsed is None:
                    continue
                base, step = parsed
                if step in published.get(base, ()):
                    continue
                superseded = latest.get(base, -1) > step
                expired = f.created_at_ms and at - f.created_at_ms >= ttl_ms
                if superseded or expired:
                    doomed.append(p)
            for p in sorted(doomed)[:CKPT_GC_MAX_DELETES]:
                try:
                    await self._propose({"op": "delete_file", "path": p})
                    self.ckpt_gc_deleted += 1
                except RpcError:
                    return

    @admission_controlled
    async def rpc_list_files(self, req: dict) -> dict:
        await self._linearizable_read()
        prefix = req.get("path", "")
        # basename narrows to paths whose final segment matches exactly —
        # lets the S3 gateway discover bucket markers without shipping the
        # whole namespace (ListAllMyBuckets would otherwise be O(all files)).
        basename = req.get("basename")
        entries = sorted(
            (p, f) for p, f in self.state.files.items()
            if f.complete and p.startswith(prefix)
            and (basename is None or p.rsplit("/", 1)[-1] == basename)
        )
        resp = {"files": [p for p, _ in entries]}
        if req.get("with_meta"):
            # S3 ListObjects needs Size/ETag/LastModified per key without a
            # GetFileInfo round trip each (reference ListObjects handlers.rs
            # walk per-shard metadata the same way).
            resp["metas"] = [
                {"size": f.size, "etag_md5": f.etag_md5,
                 "created_at_ms": f.created_at_ms}
                for _, f in entries
            ]
        return resp

    @admission_controlled
    async def rpc_get_block_locations(self, req: dict) -> dict:
        # Linearizable by default; chunkserver recovery passes allow_stale
        # because it sweeps all masters and any copy of the location set
        # helps (reference recover_block queries every master).
        if not req.get("allow_stale"):
            await self._linearizable_read()
        found = self.state.find_block(req["block_id"])
        if found is None:
            return {"found": False, "locations": []}
        f, block = found
        return {
            "found": True,
            "locations": block.locations,
            "ec_data_shards": block.ec_data_shards,
            "ec_parity_shards": block.ec_parity_shards,
        }

    # ----------------------------------------------------- chunkserver RPCs

    async def rpc_register_chunk_server(self, req: dict) -> dict:
        self.state.record_heartbeat(
            req["address"],
            used_space=0,
            available_space=int(req.get("capacity") or 0),
            chunk_count=0,
            rack_id=req.get("rack_id", ""),
        )
        return {"success": True}

    async def rpc_heartbeat(self, req: dict) -> dict:
        addr = req["chunk_server_address"]
        self.state.record_heartbeat(
            addr,
            used_space=int(req.get("used_space") or 0),
            available_space=int(req.get("available_space") or 0),
            chunk_count=int(req.get("chunk_count") or 0),
            rack_id=req.get("rack_id", ""),
            ici_ring=tuple(req.get("ici_ring") or ()),
        )
        bad = list(req.get("bad_blocks") or [])
        if bad:
            logger.warning("heartbeat: %d bad block(s) reported by %s", len(bad), addr)
        self.state.report_bad_blocks(addr, bad)
        if bad:
            self._spawn(self.run_healer())
        results_processed = await self._process_command_results(
            addr, req.get("command_results") or []
        )
        term = self.raft.core.term
        commands = self.state.drain_commands(addr)
        for c in commands:
            c["master_term"] = term
            c["master_shard"] = self.state.shard_id
        return {
            "success": True,
            "commands": commands,
            "master_term": term,
            # Epoch fencing is scoped to the issuing Raft group: a term
            # bump in one shard's failover must not fence writes allocated
            # by a different, healthy shard.
            "shard_id": self.state.shard_id,
            "results_processed": results_processed,
        }

    async def _process_command_results(self, reporter: str, results: list[dict]) -> bool:
        """Commit metadata changes only after the chunkserver ACKED the data
        movement (prevents phantom locations from failed commands). Returns
        False when this master can't process them (not leader) so the CS
        retains and re-reports them.

        A balancer move (a ``REPLICATE`` carrying ``balance_delete_source``,
        reported by its source) is ONE metadata step: the target goes in and
        the source comes out in the same ``mark_block_locations`` entry, and
        the source's ``DELETE`` is queued only after that entry committed.
        Invariant: the master orders a replica deleted only after a committed
        record that no longer names it. A move never shortens a block: where
        dropping the source would leave fewer than REPLICATION_FACTOR
        distinct locations, only the target goes in and no ``DELETE`` goes
        out. A re-reported move finds nothing to change and queues the
        ``DELETE`` again (deleting a missing block is harmless); a ``DELETE``
        result changes nothing. Accepted trade: command queues are leader
        soft state, so a leadership change between the commit and the
        source's next heartbeat loses the ``DELETE`` and the source keeps a
        copy no record names (wasted disk, no data lost)."""
        if not results:
            return True
        if not self.raft.is_leader:
            return False
        for res in results:
            if not res.get("success"):
                continue
            found = self.state.find_block(res.get("block_id", ""))
            if found is None:
                continue
            _, block = found
            rtype = res.get("type")
            new_locs = None
            delete_source = False
            if rtype == "REPLICATE":
                target = res.get("target_chunk_server_address")
                new_locs = list(block.locations)
                if target and target not in new_locs:
                    new_locs.append(target)
                if res.get("balance_delete_source"):
                    moved = [l for l in new_locs if l != reporter]
                    if len(set(moved)) >= REPLICATION_FACTOR:
                        new_locs, delete_source = moved, True
            elif rtype == "RECONSTRUCT_EC_SHARD":
                idx = int(res.get("shard_index", -1))
                if 0 <= idx < len(block.locations):
                    new_locs = list(block.locations)
                    new_locs[idx] = reporter
            if new_locs is not None and new_locs != block.locations:
                try:
                    await self.raft.propose({
                        "op": "mark_block_locations",
                        "block_id": res["block_id"],
                        "locations": new_locs,
                    })
                except (NotLeaderError, ValueError) as e:
                    logger.warning("location update failed: %s", e)
                    return False
            if delete_source:
                self.state.queue_command(reporter, {
                    "type": "DELETE", "block_id": res["block_id"],
                })
        return True

    # ------------------------------------------------------- sharding RPCs

    @admission_controlled
    async def rpc_ingest_metadata(self, req: dict) -> dict:
        """Bulk-import file metadata pushed by a peer shard during split
        migration (reference IngestMetadata master.rs:3558-3620). Gated like
        every other namespace write; a misdirected ingest (range has since
        moved on) is rejected wholesale rather than overwriting metadata for
        keys this shard doesn't own. Duplicate ingests of the same migration
        are idempotent overwrites."""
        self._check_safe_mode()
        if not self.raft.is_leader:
            raise RpcError.not_leader(self.raft.leader_hint)
        files = dict(req["files"])
        # Same freeze as every other namespace write: an ingest into a
        # migrating (or staged-in) range would be acked and then lost to
        # the sweep / clobbered by the staged publish. Apply re-checks too.
        self._check_migration_freeze(*files.keys())
        if self.shard_map is not None and \
                self.shard_map.has_shard(self.state.shard_id):
            foreign = [p for p in files
                       if (self.shard_map.get_shard(p) or self.state.shard_id)
                       != self.state.shard_id]
            if foreign:
                raise RpcError.failed_precondition(
                    f"ingest rejected: {len(foreign)} path(s) outside this "
                    f"shard's range (e.g. {foreign[0]!r})"
                )
        result = await self._propose({"op": "ingest_metadata", "files": files})
        return {"success": True, "count": result["count"]}

    @admission_controlled
    async def rpc_initiate_shuffle(self, req: dict) -> dict:
        """Operator-triggered background block re-spread for a prefix
        (reference InitiateShuffle master.rs:3620-3660)."""
        self._check_safe_mode()
        # Probe with a key strictly inside the prefix: the prefix string
        # itself can be a carve boundary, and a key equal to a boundary
        # belongs to the range below it (the flank, not the prefix's owner).
        self._check_shard_ownership(req["prefix"] + "\x00")
        await self._propose({"op": "trigger_shuffle", "prefix": req["prefix"]})
        return {"success": True}

    async def run_metrics_decay(self) -> None:
        """EMA-fold the per-prefix counters (reference master.rs:1421-1427)."""
        self.monitor.decay()

    async def run_split_detector(self) -> None:
        """Auto split/merge driver (reference run_split_detector
        master.rs:1483-1837). Leader-only. Resumes any in-flight migration
        before considering new ones — at most one reshard is in flight per
        shard, and a leader crash mid-handoff is picked up here by the next
        leader from the replicated migration record."""
        if not self.raft.is_leader or not self.config_servers:
            return
        await self._gc_staged_ingests()
        if self.state.migrations:
            for mid, mig in list(self.state.migrations.items()):
                await self._advance_migration(mid, dict(mig))
            return
        if not self.state.shard_id:
            return
        hot = self.monitor.hot_prefix()
        if hot is not None:
            await self._start_split(*hot)
            return
        if self.monitor.should_merge():
            await self._start_merge()

    async def _start_split(self, prefix: str, rps: float) -> None:
        """Kick off a hot-prefix split: record the migration intent in Raft
        FIRST (crash-resumable), then carve exactly the hot prefix's range
        out to a freshly allocated shard and hand its metadata over."""
        if self.shard_map is not None:
            owner = self.shard_map.get_shard(prefix)
            if owner is not None and owner != self.state.shard_id:
                return  # raced: another shard owns the hot range now
            interval = self.shard_map.shard_interval(self.state.shard_id)
            if interval is not None and interval[0] >= prefix \
                    and interval[1] <= autoshard.prefix_end(prefix):
                # Our whole range already IS (or sits inside) the hot
                # prefix: carving it off again cannot spread the load, it
                # would only hand the identical range to a fresh group and
                # leave this one range-less — forever, every cooldown.
                return
        new_shard_id = f"{self.state.shard_id}-split-{uuid.uuid4().hex[:8]}"
        mid = f"mig-{uuid.uuid4().hex[:12]}"
        logger.warning(
            "hot prefix %s (%.1f rps > %.1f): splitting into %s",
            prefix, rps, self.monitor.split_threshold_rps, new_shard_id,
        )
        await self._propose({
            "op": "begin_migration", "migration_id": mid, "kind": "split",
            "target_shard_id": new_shard_id, "start": prefix,
            "end": autoshard.prefix_end(prefix), "prefix": prefix,
        })
        self.monitor.mark_resharded()
        await self._advance_migration(mid, self.state.migrations.get(mid, {}))

    async def _start_merge(self) -> None:
        """Underutilized shard retires itself into the range-neighbor that
        inherits its keyspace when its boundaries fold away (victim = self;
        deviation from the reference documented in autoshard.py)."""
        if self.shard_map is None or len(self.shard_map.shards) < 2:
            return
        target = self.shard_map.merge_target(self.state.shard_id)
        interval = self.shard_map.shard_interval(self.state.shard_id)
        if target is None or interval is None:
            return
        mid = f"mig-{uuid.uuid4().hex[:12]}"
        logger.warning(
            "shard %s underutilized (%.2f rps < %.2f): merging into %s",
            self.state.shard_id, self.monitor.total_rps(),
            self.monitor.merge_threshold_rps, target,
        )
        await self._propose({
            "op": "begin_migration", "migration_id": mid, "kind": "merge",
            "target_shard_id": target,
            # Exactly our owned interval: the target's staged-range guard
            # makes these keys unavailable until the commit, so staging the
            # whole keyspace would blackout the target's own ranges too.
            "start": interval[0], "end": interval[1],
        })
        self.monitor.mark_resharded()
        await self._advance_migration(mid, self.state.migrations.get(mid, {}))

    async def _call_group(self, peers: list[str], method: str, req: dict,
                          attempts: int = 4) -> dict:
        """RPC to an explicit master group, following Not-Leader hints (like
        call_shard, but usable for targets not yet in the shard map)."""
        peers = list(peers)
        if not peers:
            raise RpcError.unavailable("no peers for group call")
        last: RpcError | None = None
        idx = 0
        for _ in range(attempts):
            target = peers[idx % len(peers)]
            try:
                return await self.client.call(target, SERVICE, method, req,
                                              timeout=10.0)
            except RpcError as e:
                last = e
                hint = e.not_leader_hint
                if e.is_not_leader:
                    if hint and hint not in peers:
                        peers.insert(0, hint)
                        idx = 0
                    elif hint:
                        idx = peers.index(hint)
                    else:
                        idx += 1
                        await asyncio.sleep(0.2)
                    continue
                if e.code.name in ("INVALID_ARGUMENT", "NOT_FOUND",
                                   "ALREADY_EXISTS", "FAILED_PRECONDITION"):
                    raise
                idx += 1
                await asyncio.sleep(0.2)
        raise last if last is not None else RpcError.unavailable(
            "group unreachable"
        )

    async def _stage_migration(self, mid: str, mig: dict,
                               peers: list[str]) -> bool:
        """Stage the migration's frozen file snapshot at the target group.
        Built here (not per tick) so the O(namespace) scan only runs when a
        stage is actually sent."""
        files = {
            p: f.to_dict() for p, f in self.state.files.items()
            if mig["start"] < p <= mig["end"]  # carve_shard's (start, end]
        }
        try:
            await self._call_group(peers, "StageIngest", {
                "migration_id": mid, "start": mig["start"],
                "end": mig["end"], "files": files,
                "staged_at_ms": now_ms(),
            })
            return True
        except RpcError as e:
            logger.info("migration %s: stage not accepted yet: %s",
                        mid, e.message)
            return False

    async def _advance_migration(self, mid: str, mig: dict) -> None:
        """Drive one migration forward as far as it will go this tick.

        Freeze -> allocate -> stage -> flip map -> commit -> complete:
        writes in the range are frozen from begin_migration (the freeze
        check), the metadata snapshot is STAGED at the target before the
        map flips (so the target never serves found=False for migrated
        keys — its staged-range guard answers unavailable until commit),
        and only then does the range route there. Every step is idempotent;
        a new leader resumes from the replicated migration record."""
        if not mig:
            return
        target = mig["target_shard_id"]
        kind = mig["kind"]
        try:
            resp = await self.call_config("FetchShardMap", {})
            fetched = ShardMap.from_dict(resp["shard_map"])
            if self.shard_map is None or fetched.version >= self.shard_map.version:
                self.shard_map = fetched
        except RpcError as e:
            logger.warning("migration %s: map fetch failed: %s", mid, e.message)
            return
        map_done = (
            self.shard_map.has_shard(target)
            if kind == "split"
            else not self.shard_map.has_shard(self.state.shard_id)
        )
        if not map_done:
            # 1. Target group's peers: reserved via the config server for a
            # split — re-requested EVERY tick (idempotent by shard id) so
            # the reservation's liveness refreshes while we retry staging,
            # and a GC'd/stolen reservation is transparently re-allocated.
            # For a merge, read from the map.
            if kind == "split":
                try:
                    resp = await self.call_config("AllocateShardGroup",
                                                  {"shard_id": target})
                    peers = list(resp["peers"])
                except RpcError as e:
                    # Abandoning is safe while the map is untouched (just
                    # verified with a linearizable fetch) and the refusal is
                    # deterministic — no spare capacity.
                    if "no healthy registered masters" in e.message and \
                            e.code.name in ("UNAVAILABLE",
                                            "INVALID_ARGUMENT"):
                        logger.warning("migration %s abandoned: %s",
                                       mid, e.message)
                        await self._propose({
                            "op": "complete_migration",
                            "migration_id": mid, "aborted": True,
                        })
                    else:
                        logger.warning("migration %s: allocation failed: %s",
                                       mid, e.message)
                    return
            else:
                peers = self.shard_map.get_peers(target) or []
                if not peers:
                    # Retained neighbor vanished and the map is untouched.
                    logger.warning("migration %s abandoned: merge target %s "
                                   "gone", mid, target)
                    await self._propose({"op": "complete_migration",
                                         "migration_id": mid,
                                         "aborted": True})
                    return
            if peers != list(mig.get("peers") or []):
                await self._propose({"op": "update_migration",
                                     "migration_id": mid, "peers": peers})
                mig["peers"] = peers
            # 2. Stage the frozen snapshot at the target (idempotent
            # overwrite; re-staged on every resume until the flip).
            if not await self._stage_migration(mid, mig, peers):
                return
            # 3. Flip the map. The carve names the reserved peers
            # explicitly — allocation already happened.
            try:
                if kind == "split":
                    await self.call_config("CarveShard", {
                        "start": mig["start"], "end": mig["end"],
                        "new_shard_id": target, "peers": peers,
                    })
                else:
                    await self.call_config("MergeShards", {
                        "victim_shard_id": self.state.shard_id,
                        "retained_shard_id": target,
                    })
            except RpcError as e:
                if e.code.name == "INVALID_ARGUMENT":
                    # Raced/malformed reshard, map untouched: drop the stage
                    # (best-effort; the target GCs abandoned stages anyway)
                    # and abandon.
                    logger.warning("migration %s abandoned: %s", mid,
                                   e.message)
                    try:
                        await self._call_group(peers, "DropStagedIngest",
                                               {"migration_id": mid})
                    except RpcError:
                        pass
                    await self._propose({"op": "complete_migration",
                                         "migration_id": mid,
                                         "aborted": True})
                else:
                    logger.warning("migration %s: reshard RPC failed: %s",
                                   mid, e.message)
                return
            return  # commit on the next tick, once the map propagates
        # 4. Map flipped: publish the stage on the target.
        peers = list(mig.get("peers") or [])
        if kind == "merge" and not self.shard_map.has_shard(target):
            # Retained shard itself vanished (merged/removed) before our
            # commit landed: redirect the handoff to whoever owns the range
            # now — we still hold every file (complete never ran).
            owner = self.shard_map.get_shard(mig["end"])
            owner_peers = (self.shard_map.get_peers(owner) or []) \
                if owner else []
            if not owner or owner == self.state.shard_id or not owner_peers:
                logger.warning("migration %s: no live owner for the merged "
                               "range yet; holding", mid)
                return
            logger.warning("migration %s: retained shard %s gone; "
                           "retargeting handoff to %s", mid, target, owner)
            await self._propose({"op": "update_migration",
                                 "migration_id": mid, "peers": owner_peers,
                                 "target_shard_id": owner})
            mig["peers"], mig["target_shard_id"] = owner_peers, owner
            peers, target = owner_peers, owner
        if not peers:
            peers = self.shard_map.get_peers(target) or []
            if not peers:
                logger.warning("migration %s: no peers known for target %s",
                               mid, target)
                return
        try:
            await self._call_group(peers, "CommitStagedIngest",
                                   {"migration_id": mid})
        except RpcError as e:
            if "no staged ingest" in e.message:
                # This group never got (or GC'd) the stage — e.g. a
                # retargeted merge, or a stage dropped as abandoned. We
                # still hold the files: re-stage, commit next tick.
                await self._stage_migration(mid, mig, peers)
            else:
                logger.info("migration %s: staged commit pending: %s",
                            mid, e.message)
            return
        if kind == "split" and mig.get("prefix"):
            # The hot prefix's files now live on the target shard — that's
            # where the block re-spread has to run. Best-effort: the target
            # can also be told later via the CLI's shuffle command.
            try:
                await self._call_group(peers, "InitiateShuffle",
                                       {"prefix": mig["prefix"]})
            except RpcError as e:
                logger.info("migration %s: shuffle handoff skipped: %s",
                            mid, e.message)
        # 5. Drop the moved range locally (and, for a merge, retire into
        # the spare pool — cleared atomically inside the same apply).
        await self._propose({"op": "complete_migration", "migration_id": mid})
        if kind == "merge":
            logger.info("shard merged away; master group back in spare pool")

    async def run_data_shuffler(self) -> None:
        """Re-spread blocks of shuffling prefixes across chunkservers, one
        copy per prefix per tick (reference run_data_shuffler
        master.rs:1324-1419). Deviations from the reference, on purpose:
        spreading is bounded by each block's replication target (RF or k+m)
        so a shuffle can never inflate a prefix to N-way replication —
        space equalization is the balancer's job, not the shuffler's — and
        the prefix only retires when nothing is left to spread AND nothing
        is still in flight (the reference stops as soon as one scan finds no
        candidate, dropping work queued but unacked). Replicate-then-ack:
        the location list only grows after the copy is confirmed (the
        REPLICATE result path), so a crashed copy never strands metadata."""
        if not self.raft.is_leader or not self.state.shuffling_prefixes:
            return
        by_fullness = [
            addr for addr, _ in sorted(
                ((addr, st.available_space)
                 for addr, st in self.state.chunk_servers.items()),
                key=lambda t: t[1],
            )
        ]
        if len(by_fullness) < 2:
            return
        live = set(by_fullness)
        pending = {
            (c.get("type"), c.get("block_id"))
            for cmds in self.state.pending_commands.values()
            for c in cmds
        }
        for prefix in list(self.state.shuffling_prefixes):
            blocks = [
                b for path, f in self.state.files.items()
                if path.startswith(prefix) for b in f.blocks
            ]
            moved = in_flight = False
            for b in blocks:
                if b.ec_data_shards:
                    # EC locations are positional (shard index -> holder);
                    # appending a REPLICATE target would corrupt the slot
                    # mapping. Missing EC shards are the healer's job
                    # (RECONSTRUCT_EC_SHARD rebuilds into the right slot).
                    continue
                want = REPLICATION_FACTOR
                if len([l for l in b.locations if l in live]) >= want:
                    continue
                if ("REPLICATE", b.block_id) in pending:
                    in_flight = True
                    continue
                donor = next(
                    (d for d in by_fullness if d in b.locations), None
                )
                target = next(
                    (t for t in reversed(by_fullness)
                     if t not in b.locations), None
                )
                if donor is None or target is None:
                    continue
                self.state.queue_command(donor, {
                    "type": "REPLICATE",
                    "block_id": b.block_id,
                    "target_chunk_server_address": target,
                })
                logger.info("shuffle %s: %s %s -> %s",
                            prefix, b.block_id, donor, target)
                moved = True
                break
            if not moved and not in_flight:
                # Nothing left to spread for this prefix — retire it
                # (reference StopShuffle, simple_raft.rs:3249-3250).
                try:
                    await self._propose({"op": "stop_shuffle",
                                         "prefix": prefix})
                except RpcError:
                    pass

    @admission_controlled
    async def rpc_stage_ingest(self, req: dict) -> dict:
        """Target side of a migration handoff: hold the moved range's
        metadata without serving it (the staged-range guard answers
        unavailable for these keys until the commit). Accepted even before
        this group adopts the new shard — the stage is inert until then."""
        if not self.raft.is_leader:
            raise RpcError.not_leader(self.raft.leader_hint)
        if req["start"] >= req["end"]:
            raise RpcError.invalid("empty staged range")
        await self._propose({
            "op": "stage_ingest",
            "migration_id": req["migration_id"],
            "start": req["start"], "end": req["end"],
            "files": dict(req.get("files") or {}),
            "staged_at_ms": int(req.get("staged_at_ms") or now_ms()),
        })
        return {"success": True}

    @admission_controlled
    async def rpc_commit_staged_ingest(self, req: dict) -> dict:
        """Publish a staged handoff once the map routes its range here.
        Idempotent: a commit for an unknown migration id is a duplicate
        (the stage was already published), not an error."""
        if not self.raft.is_leader:
            raise RpcError.not_leader(self.raft.leader_hint)
        result = await self._propose({
            "op": "commit_staged_ingest", "migration_id": req["migration_id"],
            "at_ms": now_ms(),
        })
        return {"success": True, "count": result.get("count", 0)}

    @admission_controlled
    async def rpc_drop_staged_ingest(self, req: dict) -> dict:
        """GC hook for a stage whose migration aborted before the map flip."""
        if not self.raft.is_leader:
            raise RpcError.not_leader(self.raft.leader_hint)
        await self._propose({
            "op": "drop_staged_ingest", "migration_id": req["migration_id"],
        })
        return {"success": True}

    async def _gc_staged_ingests(self) -> None:
        """Drop stale stages for ranges the map never routed to us (their
        migration aborted after staging); keeps an abandoned stage from
        permanently blocking a future carve of the same range."""
        if not self.state.staged_ingests or not self.raft.is_leader:
            return
        at = now_ms()
        for mid, s in list(self.state.staged_ingests.items()):
            if at - s.get("staged_at_ms", 0) < STAGED_INGEST_TTL_MS:
                continue
            owner = self.shard_map.get_shard(s["end"]) \
                if self.shard_map is not None else None
            if owner != self.state.shard_id:
                logger.warning("dropping abandoned staged ingest %s", mid)
                try:
                    await self._propose({"op": "drop_staged_ingest",
                                         "migration_id": mid})
                except RpcError:
                    pass

    async def run_shard_refresh(self) -> None:
        """Refresh the shard map from the Config Server, register this
        master, and (leader only) report shard liveness (reference
        master.rs:1429-1481 + RegisterMaster/ShardHeartbeat)."""
        try:
            resp = await self.call_config(
                "FetchShardMap", {"allow_stale": True}
            )
            fetched = ShardMap.from_dict(resp["shard_map"])
            # allow_stale may answer from a lagging config follower; a map
            # older than the one we hold would regress shard boundaries and
            # let two shards accept the same key. Install monotonically.
            if self.shard_map is None or fetched.version >= self.shard_map.version:
                self.shard_map = fetched
            reg = await self.call_config("RegisterMaster", {
                "address": self.address, "shard_id": self.state.shard_id,
                # This master's whole Raft group: new-shard allocation must
                # hand a range to ONE group (N addresses from different
                # groups would each adopt it — split brain).
                "group": sorted(self.raft.core.config.voters),
            })
            # Spare master allocated to a split-off shard: adopt it through
            # Raft so the whole group agrees on its new identity — but only
            # once the shard actually exists in the map (a reservation whose
            # carve later aborts must not be adopted; and a dead shard id
            # accidentally echoed back must never resurrect).
            assigned = reg.get("assigned_shard_id") or ""
            if assigned and not self.state.shard_id and self.raft.is_leader \
                    and self.shard_map is not None \
                    and self.shard_map.has_shard(assigned):
                logger.info("adopting shard %s from config server", assigned)
                await self._propose({"op": "adopt_shard", "shard_id": assigned})
            if self.raft.is_leader and self.state.shard_id:
                await self.call_config("ShardHeartbeat", {
                    "shard_id": self.state.shard_id, "address": self.address,
                    "rps_per_prefix": self.monitor.rps_per_prefix(),
                    # The leader's CURRENT voter set: the config server
                    # reconciles the shard map's peer routing with it, so
                    # clients discover members added/removed by dynamic
                    # membership changes (cluster add/remove-server). The
                    # term fences the reconciliation — a deposed leader's
                    # stale group report must not regress the map.
                    "group": sorted(self.raft.core.config.voters),
                    "term": self.raft.core.term,
                })
        except RpcError as e:
            logger.warning("shard refresh failed: %s", e.message)

    # ------------------------------------------------------- admin RPCs

    async def rpc_safe_mode_status(self, _req: dict) -> dict:
        return {
            "safe_mode": self.state.safe_mode,
            "reported_blocks": self.state.safe_mode_reported_blocks,
            "total_blocks": self.state.total_known_blocks(),
        }

    async def rpc_enter_safe_mode(self, _req: dict) -> dict:
        self.state.enter_safe_mode()
        return {"success": True}

    async def rpc_exit_safe_mode(self, _req: dict) -> dict:
        self.state.exit_safe_mode()
        return {"success": True}

    async def rpc_add_raft_node(self, req: dict) -> dict:
        try:
            await self.raft.add_server(req["address"])
        except NotLeaderError as e:
            raise RpcError.not_leader(e.leader_hint) from None
        except ValueError as e:
            raise RpcError.invalid(str(e)) from None
        return {"success": True}

    async def rpc_remove_raft_node(self, req: dict) -> dict:
        try:
            await self.raft.remove_server(req["address"])
        except NotLeaderError as e:
            raise RpcError.not_leader(e.leader_hint) from None
        except ValueError as e:
            raise RpcError.invalid(str(e)) from None
        return {"success": True}

    async def rpc_transfer_leadership(self, req: dict) -> dict:
        try:
            await self.raft.transfer_leadership(req["target"])
        except NotLeaderError as e:
            raise RpcError.not_leader(e.leader_hint) from None
        except ValueError as e:
            raise RpcError.invalid(str(e)) from None
        return {"success": True}

    async def rpc_raft_state(self, _req: dict) -> dict:
        return self.raft.status()

    def ops_gauges(self) -> dict[str, float]:
        """Gauges for /metrics (reference bin/master.rs:280-350 exports
        raft + safe-mode; raft gauges are appended by OpsServer)."""
        st = self.state
        return {
            **self.shedder.counters(),
            "safe_mode": 1 if st.safe_mode else 0,
            "files": len(st.files),
            "blocks": st.total_known_blocks(),
            "chunk_servers": len(st.chunk_servers),
            "transactions": len(st.transactions),
            "migrations": len(st.migrations),
            "staged_ingests": len(st.staged_ingests),
            "shuffling_prefixes": len(st.shuffling_prefixes),
            "bad_blocks": len(st.bad_block_locations),
        }

    # ------------------------------------------------------ background tasks

    async def run_liveness_check(self) -> None:
        """Drop CSes silent for >15 s, then heal (reference master.rs:729-760)."""
        cutoff = now_ms() - self.liveness_cutoff_ms
        dead = [
            addr for addr, st in self.state.chunk_servers.items()
            if st.last_heartbeat_ms < cutoff
        ]
        for addr in dead:
            logger.warning("chunkserver %s considered dead; removing", addr)
            self.state.remove_chunk_server(addr)
        if dead:
            await self.run_healer()

    async def run_healer(self) -> None:
        if not self.raft.is_leader:
            return
        plan = placement.heal_under_replicated(self.state)
        await self._execute_plan(plan)

    async def run_balancer(self) -> None:
        if not self.raft.is_leader:
            return
        plan = placement.plan_balancing(self.state)
        await self._execute_plan(plan)

    async def _execute_plan(self, plan: placement.HealPlan) -> None:
        for addr, cmd in plan.queues:
            self.state.queue_command(addr, cmd)

    def _schedule_ec_migrations(self, path: str, f) -> None:
        """Queue CONVERT_TO_EC commands for still-replicated blocks of an
        EC-policy file: one source chunkserver reads its replica, RS-encodes
        it, distributes one shard per target server under a new block id,
        then reports back (CompleteEcConversion) for the atomic metadata
        swap. Issue-tracking is leader soft state with a retry timeout —
        a lost command or crashed chunkserver just re-issues."""
        k, m = f.ec_data_shards, f.ec_parity_shards
        now = time.monotonic()
        live = set(self.state.live_servers())
        for b in f.blocks:
            if b.is_ec or not b.size:
                continue
            attempt = self._ec_migrations.get(b.block_id)
            if attempt is not None and (
                    attempt.get("committing")
                    or now - attempt["ts"] < EC_MIGRATION_RETRY_SECS):
                # committing: the swap propose is in flight — issuing a
                # duplicate conversion now would only produce shards for
                # the sweep to GC.
                continue
            sources = [loc for loc in b.locations if loc in live]
            if not sources:
                continue
            targets = placement.select_servers_rack_aware(
                [(a, s) for a, s in self.state.chunk_servers.items()
                 if a in live],
                k + m,
            )
            if len(set(targets)) < k + m:
                logger.warning(
                    "EC migration for %s needs %d live chunkservers, "
                    "have %d", b.block_id, k + m, len(set(targets)),
                )
                continue
            # Unique id per attempt: a slow superseded attempt writes its
            # shards under ITS id and can never corrupt the positional
            # shard layout the committed attempt's metadata points at.
            new_id = f"{b.block_id}.ec-{uuid.uuid4().hex[:8]}"
            stale = []
            if attempt is not None:
                stale = attempt["stale"] + [
                    (attempt["new_id"], attempt["targets"])
                ]
            self._ec_migrations[b.block_id] = {
                "ts": now, "new_id": new_id, "targets": targets,
                "stale": stale,
            }
            self.state.queue_command(sources[0], {
                "type": "CONVERT_TO_EC",
                "block_id": b.block_id,
                "new_block_id": new_id,
                "ec_data_shards": k,
                "ec_parity_shards": m,
                "targets": targets,
                "master_term": self.raft.core.term,
            })
            logger.info("tiering: EC data migration of %s scheduled on %s "
                        "(targets=%s)", b.block_id, sources[0], targets)

    def _gc_ec_attempt(self, block_id: str, new_id: str,
                       targets: list[str]) -> None:
        """Delete the shards a dead conversion attempt wrote (file deleted
        mid-migration / attempt superseded across a leader change) and drop
        its tracking entry.

        WINNER GUARD (round-5 roulette catch, seed 8100): never GC an id
        that RESOLVES in the metadata — it is live data. The poison
        interleaving: attempt C's swap propose APPLIES while its handler
        still awaits the propose (pop pending); a LATE completion for a
        dead-leader attempt A lands in the not-found branch, pops C from
        the soft state here, and without the guard would queue DELETE for
        C's freshly-committed shards on every target — all k+m copies of
        live data."""

        def gc(bid: str, addrs: list[str]) -> None:
            if self.state.find_block(bid) is not None:
                return  # committed winner: live data, never GC
            for addr in addrs:
                self.state.queue_command(
                    addr, {"type": "DELETE", "block_id": bid}
                )

        gc(new_id, targets)
        attempt = self._ec_migrations.pop(block_id, None)
        if attempt is not None:
            stale = attempt["stale"] + [
                (attempt["new_id"], attempt["targets"])
            ]
            for stale_id, stale_targets in stale:
                if stale_id == new_id:
                    continue
                gc(stale_id, stale_targets)

    def _sweep_dead_ec_migrations(self) -> None:
        """Drop tracking (and GC issued shards) for migrations whose source
        block vanished — e.g. the file was deleted before any completion
        report arrived, so no RPC path ever cleans the entry."""
        for block_id in list(self._ec_migrations):
            if self.state.find_block(block_id) is not None:
                continue
            attempt = self._ec_migrations[block_id]
            if self.state.find_block(attempt["new_id"]) is not None:
                # The swap COMMITTED and the completion handler's pop is
                # still in flight (its propose yielded) or was lost to a
                # restart. The new_id shards are live data — GC only the
                # superseded attempts, never the committed one.
                self._ec_migrations.pop(block_id, None)
                for stale_id, stale_targets in attempt["stale"]:
                    for addr in stale_targets:
                        self.state.queue_command(
                            addr, {"type": "DELETE", "block_id": stale_id}
                        )
                continue
            self._gc_ec_attempt(block_id, attempt["new_id"],
                                attempt["targets"])

    async def rpc_complete_ec_conversion(self, req: dict) -> dict:
        """Chunkserver reports a finished shard distribution; commit the
        metadata swap through Raft."""
        if not self.raft.is_leader:
            raise RpcError.not_leader(self.raft.leader_hint)
        # Shard scoping FIRST (round-5 roulette catch, seed 8100): the
        # reporting chunkserver retries across EVERY known master — both
        # shard groups — when the issuing leader died. A wrong-shard
        # master must bounce the report: "block not in MY namespace" is
        # NOT "file deleted", and the GC below would otherwise delete all
        # k+m freshly committed shards of live data.
        req_shard = str(req.get("shard_id") or "")
        if req_shard and req_shard != self.state.shard_id:
            raise RpcError.failed_precondition(
                f"conversion report for shard {req_shard}, "
                f"this is {self.state.shard_id}")
        found = self.state.find_block(req["block_id"])
        if found is None:
            # Already swapped (the new id resolves) — duplicate completion.
            if self.state.find_block(req["new_block_id"]) is not None:
                return {"success": True}
            # Otherwise the file was deleted mid-migration, or another
            # attempt won after a leader change: the shards THIS attempt
            # wrote are orphans — queue their deletion before failing, or
            # they live on the target stores forever. Only a report that
            # PROVES it belongs to this shard may trigger the GC — an
            # unscoped (legacy) report is refused without side effects.
            # NON-EMPTY match only: a spare/retired master's shard_id is
            # "" and an unscoped legacy report would "match" it, re-
            # opening the wrong-namespace GC this gate exists to close.
            if req_shard and req_shard == self.state.shard_id:
                self._gc_ec_attempt(req["block_id"], req["new_block_id"],
                                    req.get("targets") or [])
            raise RpcError.not_found(f"block not found: {req['block_id']}")
        attempt = self._ec_migrations.get(req["block_id"])
        if attempt is not None and attempt["new_id"] != req["new_block_id"]:
            # Fencing: a superseded attempt must not commit — its target
            # list no longer matches what the current attempt will report.
            # (After a leader change the soft state is empty and any attempt
            # is accepted; that is safe because attempt ids are unique.)
            raise RpcError.failed_precondition(
                f"conversion attempt {req['new_block_id']} superseded"
            )
        f, _block = found
        # Mark the entry COMMITTING before awaiting the propose: the
        # await yields, and concurrent handlers must keep full context —
        # the tiering scan must not re-schedule a duplicate conversion
        # (the entry stays, so the throttle holds), a late completion for
        # a superseded attempt must still be fenced locally (the entry's
        # new_id comparison above), and once the swap APPLIES, a late
        # dead-attempt completion's _gc_ec_attempt is stopped from
        # deleting the winner's shards by the resolve guard there
        # (seed-8100 catch — that interleaving deleted all k+m committed
        # shards). On propose failure the flag clears and the 60 s retry
        # owns recovery.
        committing = {
            "ts": time.monotonic(),
            "new_id": req["new_block_id"],
            "targets": list(req["targets"]),
            "stale": (attempt or {}).get("stale", []),
            "committing": True,
        }
        self._ec_migrations[req["block_id"]] = committing
        try:
            await self._propose({
                "op": "complete_ec_block_conversion",
                "path": f.path,
                "block_id": req["block_id"],
                "new_block_id": req["new_block_id"],
                "ec_data_shards": int(req["ec_data_shards"]),
                "ec_parity_shards": int(req["ec_parity_shards"]),
                "targets": list(req["targets"]),
            })
        except BaseException:
            # Restore the pre-commit view so the 60 s retry owns
            # recovery — but never reinstate ANOTHER handler's committing
            # entry (a client-retry duplicate racing this handler): a
            # restored committing=True dict with no handler behind it
            # would suppress re-scheduling forever. Dropping the entry is
            # always safe (re-issue after the retry window at worst).
            if self._ec_migrations.get(req["block_id"]) is committing:
                if attempt is not None and not attempt.get("committing"):
                    self._ec_migrations[req["block_id"]] = attempt
                else:
                    self._ec_migrations.pop(req["block_id"], None)
            raise
        self._ec_migrations.pop(req["block_id"], None)
        # GC shards any superseded attempt managed to write.
        if attempt is not None:
            for stale_id, stale_targets in attempt["stale"]:
                for addr in stale_targets:
                    self.state.queue_command(
                        addr, {"type": "DELETE", "block_id": stale_id}
                    )
        return {"success": True}

    async def run_tiering_scan(self) -> None:
        """Mark cold files + schedule EC policy conversion
        (reference scan_tiering master.rs:1933-2013, scan_ec_conversion
        master.rs:2016-2138)."""
        if not self.raft.is_leader:
            return
        self._sweep_dead_ec_migrations()
        at = now_ms()
        for path, f in list(self.state.files.items()):
            if not f.complete:
                continue
            reference_ms = f.last_access_ms or f.created_at_ms
            if not f.moved_to_cold_at_ms and reference_ms and \
                    at - reference_ms >= self.cold_threshold_ms:
                try:
                    await self.raft.propose(
                        {"op": "move_to_cold", "path": path, "at_ms": at}
                    )
                    logger.info("tiering: moved %s to cold", path)
                except (NotLeaderError, ValueError) as e:
                    logger.warning("tiering move failed for %s: %s", path, e)
            elif f.moved_to_cold_at_ms and not f.ec_data_shards and \
                    at - f.moved_to_cold_at_ms >= self.ec_threshold_ms:
                k, m = self.ec_shape
                try:
                    await self.raft.propose({
                        "op": "convert_to_ec", "path": path,
                        "ec_data_shards": k, "ec_parity_shards": m,
                    })
                    logger.info("tiering: EC policy conversion for %s", path)
                except (NotLeaderError, ValueError) as e:
                    logger.warning("EC conversion failed for %s: %s", path, e)
            elif f.ec_data_shards:
                # Policy already EC: migrate any block still replicated —
                # the DATA half of the conversion, which the reference
                # leaves TODO (master.rs:2108-2118).
                self._schedule_ec_migrations(path, f)
