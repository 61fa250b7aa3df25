"""Sans-io Raft core.

Feature parity with the reference's dfs/metaserver/src/simple_raft.rs:
- leader election with randomized 1.5-3 s timeouts (simple_raft.rs:758,1288),
- log replication with conflict back-off,
- snapshot compaction beyond a log-length threshold (simple_raft.rs:1210-1213)
  and InstallSnapshot catch-up for lagging followers (simple_raft.rs:1455-1533),
- ReadIndex linearizable reads confirmed by heartbeat quorum acks
  (simple_raft.rs:1863-1887,993-1011),
- joint-consensus membership change with a non-voting catch-up stage
  (10 rounds, simple_raft.rs:72-106,241-243,2458-2512) and joint-majority
  commit advancement (simple_raft.rs:2246-2277),
- leader transfer via TimeoutNow (simple_raft.rs:2740-2813).

Architecturally this is NOT a port: the reference interleaves consensus with
tokio channels, reqwest HTTP and RocksDB in one 3.8k-line loop. Here the core
is a pure deterministic state machine — time comes in via ``tick(now)``,
messages via ``handle_message``, randomness via an injected ``random.Random``
— and all I/O is returned as effect objects for a shell (tpudfs/raft/node.py)
to execute. That makes the whole consensus layer simulable in-process, which
is how the model-level test tiers (tests/test_raft_core.py,
test_raft_partitions.py, test_raft_jepsen.py) drive it.

On a TPU pod this control plane runs host-side over DCN (SURVEY.md §2.6 P4);
consensus never touches the accelerator.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field, replace
from enum import Enum
from typing import Any

# ---------------------------------------------------------------------------
# Data model
# ---------------------------------------------------------------------------


class Role(str, Enum):
    FOLLOWER = "follower"
    CANDIDATE = "candidate"
    LEADER = "leader"


@dataclass(frozen=True)
class LogEntry:
    index: int
    term: int
    command: Any  # opaque msgpack-able value; dicts with "_config" are internal

    def to_dict(self) -> dict:
        return {"index": self.index, "term": self.term, "command": self.command}

    @classmethod
    def from_dict(cls, d: dict) -> "LogEntry":
        return cls(int(d["index"]), int(d["term"]), d["command"])


@dataclass(frozen=True)
class Config:
    """Cluster membership. ``voters_old`` is set only during joint consensus:
    decisions then require a majority of BOTH voter sets."""

    voters: frozenset[str]
    voters_old: frozenset[str] | None = None
    learners: frozenset[str] = frozenset()

    @property
    def joint(self) -> bool:
        return self.voters_old is not None

    def all_nodes(self) -> frozenset[str]:
        nodes = self.voters | self.learners
        if self.voters_old:
            nodes = nodes | self.voters_old
        return nodes

    def has_quorum(self, acks: set[str]) -> bool:
        def maj(group: frozenset[str]) -> bool:
            return len(acks & group) * 2 > len(group)

        if not self.voters:
            return False
        ok = maj(self.voters)
        if self.voters_old is not None:
            ok = ok and maj(self.voters_old)
        return ok

    def to_dict(self) -> dict:
        return {
            "voters": sorted(self.voters),
            "voters_old": sorted(self.voters_old) if self.voters_old is not None else None,
            "learners": sorted(self.learners),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        old = d.get("voters_old")
        return cls(
            voters=frozenset(d.get("voters") or []),
            voters_old=frozenset(old) if old is not None else None,
            learners=frozenset(d.get("learners") or []),
        )


@dataclass(frozen=True)
class Snapshot:
    last_index: int
    last_term: int
    config: Config
    data: bytes

    def to_dict(self) -> dict:
        return {
            "last_index": self.last_index,
            "last_term": self.last_term,
            "config": self.config.to_dict(),
            "data": self.data,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Snapshot":
        return cls(
            int(d["last_index"]), int(d["last_term"]),
            Config.from_dict(d["config"]), d["data"],
        )


# ---------------------------------------------------------------------------
# Effects (what the shell must do after each core call)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Send:
    to: str
    msg: dict


@dataclass(frozen=True)
class PersistHardState:
    term: int
    voted_for: str | None


@dataclass(frozen=True)
class AppendLog:
    entries: tuple[LogEntry, ...]


@dataclass(frozen=True)
class TruncateLog:
    """Drop every entry with index >= from_index."""

    from_index: int


@dataclass(frozen=True)
class Apply:
    entries: tuple[LogEntry, ...]


@dataclass(frozen=True)
class SaveSnapshot:
    snapshot: Snapshot


@dataclass(frozen=True)
class RestoreFromSnapshot:
    """State machine must reset itself from snapshot.data."""

    snapshot: Snapshot


@dataclass(frozen=True)
class ReadReady:
    request_id: Any
    read_index: int


@dataclass(frozen=True)
class SteppedDown:
    """Leadership lost — shell fails pending proposals with Not Leader."""

    term: int


@dataclass(frozen=True)
class BecameLeader:
    term: int


@dataclass(frozen=True)
class SnapshotNeeded:
    """Log exceeded the compaction threshold; shell should serialize the state
    machine and call ``compact(snapshot_data)``."""


class NotLeaderError(Exception):
    def __init__(self, leader_hint: str | None):
        super().__init__(f"Not Leader|{leader_hint or ''}")
        self.leader_hint = leader_hint


# ---------------------------------------------------------------------------
# Timings
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Timings:
    """Reference values: 1.5-3 s election (simple_raft.rs:758), 100 ms tick
    loop (simple_raft.rs:1190), snapshot at >100 entries
    (simple_raft.rs:1211), 10 catch-up rounds (simple_raft.rs:241-243)."""

    election_min: float = 1.5
    election_max: float = 3.0
    heartbeat: float = 0.5
    snapshot_threshold: int = 100
    catchup_rounds: int = 10
    #: Pre-vote (Raft §9.6, the etcd extension; the reference lacks it): a
    #: timed-out follower first polls a non-binding quorum before
    #: incrementing its term, so a partitioned node cannot inflate terms
    #: and depose a healthy leader when its partition heals.
    prevote: bool = True
    #: Leader-lease reads (Raft §6.4.1 / etcd lease read; the reference has
    #: only quorum ReadIndex): a heartbeat-quorum ack for a round sent at
    #: time t proves no new leader can be elected before
    #: t + election_min (followers refuse votes within election_min of
    #: leader contact — see vote stickiness in _on_request_vote), so reads
    #: until t + election_min*(1 - clock_drift_bound) skip the quorum
    #: round-trip entirely. Only honored when ``prevote`` is also on.
    lease_reads: bool = True
    #: Upper bound assumed on relative clock RATE drift between nodes over
    #: one election timeout (monotonic clocks; absolute offsets cancel out).
    clock_drift_bound: float = 0.1


# ---------------------------------------------------------------------------
# Core
# ---------------------------------------------------------------------------


class RaftCore:
    def __init__(
        self,
        node_id: str,
        config: Config,
        *,
        term: int = 0,
        voted_for: str | None = None,
        log: list[LogEntry] | None = None,
        snapshot: Snapshot | None = None,
        timings: Timings | None = None,
        rng: random.Random | None = None,
        now: float = 0.0,
    ):
        self.node_id = node_id
        self.timings = timings or Timings()
        self.rng = rng or random.Random()

        # Persistent state (the shell re-creates the core from storage).
        self.term = term
        self.voted_for = voted_for
        self.snapshot = snapshot
        self.log: list[LogEntry] = list(log or [])

        # Config: latest config entry in the log wins; else snapshot's; else boot.
        self._boot_config = config
        self.config = config
        if snapshot is not None:
            self.config = snapshot.config
        for e in self.log:
            cfg = self._config_of(e)
            if cfg is not None:
                self.config = cfg

        # Volatile state.
        self.role = Role.FOLLOWER
        self.leader_id: str | None = None
        self.commit_index = snapshot.last_index if snapshot else 0
        self.last_applied = self.commit_index
        self.votes: set[str] = set()
        self.next_index: dict[str, int] = {}
        self.match_index: dict[str, int] = {}
        # ReadIndex machinery: monotonically increasing heartbeat probe seq,
        # per-peer highest acked seq, pending reads.
        self._probe_seq = 0
        self._peer_ack_seq: dict[str, int] = {}
        self._pending_reads: list[dict] = []  # {id, index, seq, lease?}
        # Leader-lease machinery: send time per probe round, the lease
        # expiry, the last instant a quorum was provably reachable (for
        # check-quorum step-down), and whether a TimeoutNow was fired this
        # leadership (transfer elections bypass vote stickiness, so the
        # lease argument is void once one is in flight).
        self._probe_sent_at: dict[int, float] = {}
        self._lease_until = float("-inf")
        self._quorum_contact = now
        self._transfer_fired = False
        # Membership-change machinery.
        self._catchup: dict | None = None  # {node, rounds_left, last_match}
        self._transfer_target: str | None = None
        self._transfer_deadline = 0.0
        # Pre-vote machinery: target term of the open round (None = no
        # round) and its grants. Nothing here persists — pre-votes are
        # non-binding and never touch term/voted_for.
        self._prevote_term: int | None = None
        self._prevotes: set[str] = set()
        # Initialized to NOW, not -inf: a restarted node must conservatively
        # assume it heard from a leader just before the crash, else its
        # reset stickiness window lets a new leader be elected inside an
        # old leader's still-valid lease (stale read). Costs at most one
        # election_min of vote refusal after boot — elections start no
        # earlier than that anyway (_election_deadline below).
        self._last_leader_contact = now

        self._election_deadline = now + self._election_timeout()
        self._heartbeat_due = now

    # ------------------------------------------------------------ log helpers

    @property
    def log_start(self) -> int:
        """Index of the first entry held in memory (1 if no snapshot)."""
        return (self.snapshot.last_index + 1) if self.snapshot else 1

    @property
    def last_index(self) -> int:
        if self.log:
            return self.log[-1].index
        return self.snapshot.last_index if self.snapshot else 0

    @property
    def last_term(self) -> int:
        if self.log:
            return self.log[-1].term
        return self.snapshot.last_term if self.snapshot else 0

    def entry(self, index: int) -> LogEntry | None:
        pos = index - self.log_start
        if 0 <= pos < len(self.log):
            return self.log[pos]
        return None

    def term_at(self, index: int) -> int | None:
        if index == 0:
            return 0
        if self.snapshot and index == self.snapshot.last_index:
            return self.snapshot.last_term
        e = self.entry(index)
        return e.term if e else None

    def entries_from(self, index: int, limit: int = 512) -> list[LogEntry]:
        pos = max(index - self.log_start, 0)
        return self.log[pos : pos + limit]

    @staticmethod
    def _config_of(entry: LogEntry) -> Config | None:
        cmd = entry.command
        if isinstance(cmd, dict) and "_config" in cmd:
            return Config.from_dict(cmd["_config"])
        return None

    def _recompute_config(self) -> None:
        """Re-derive membership from snapshot + surviving log entries (needed
        after truncation drops an uncommitted config entry)."""
        cfg = self.snapshot.config if self.snapshot else self._boot_config
        for e in self.log:
            c = self._config_of(e)
            if c is not None:
                cfg = c
        self.config = cfg

    def _election_timeout(self) -> float:
        return self.rng.uniform(self.timings.election_min, self.timings.election_max)

    @property
    def is_voter(self) -> bool:
        cfg = self.config
        return self.node_id in cfg.voters or (
            cfg.voters_old is not None and self.node_id in cfg.voters_old
        )

    # ------------------------------------------------------------------- tick

    def tick(self, now: float) -> list:
        effects: list = []
        if self.role == Role.LEADER:
            if self._transfer_target and now >= self._transfer_deadline:
                self._transfer_target = None  # transfer timed out; resume
            if self.config.has_quorum({self.node_id}):
                # Single-voter config: the leader alone is the quorum.
                self._quorum_contact = now
                self._lease_until = max(
                    self._lease_until, now + self._lease_duration()
                )
            elif now - self._quorum_contact > 2 * self.timings.election_max:
                # Check-quorum (etcd): a leader that cannot reach a quorum
                # steps down instead of heartbeat-pinning followers forever
                # — with vote stickiness, a send-only-partitioned leader
                # would otherwise block elections indefinitely.
                return effects + self._step_down(self.term, now)
            if now >= self._heartbeat_due:
                self._heartbeat_due = now + self.timings.heartbeat
                self._new_probe_round(now)
                effects += self._broadcast_append()
            if len(self.log) > self.timings.snapshot_threshold and \
                    self.last_applied >= self.log_start:
                effects.append(SnapshotNeeded())
        elif self.is_voter and now >= self._election_deadline:
            if self.timings.prevote:
                # Timed-out CANDIDATES step back through pre-vote too
                # (etcd's pre-candidate): a candidate partitioned
                # mid-election would otherwise bump its term every timeout
                # — the exact disruption pre-vote exists to prevent.
                if self.role == Role.CANDIDATE:
                    self.role = Role.FOLLOWER
                    self.votes = set()
                effects += self._start_prevote(now)
            else:
                effects += self._start_election(now)
        return effects

    # -------------------------------------------------------------- elections

    def _start_prevote(self, now: float) -> list:
        """Open a pre-vote round for term+1 — no state is changed beyond the
        round bookkeeping; a quorum of non-binding grants gates the real
        election (so an isolated node never inflates its term)."""
        self._prevote_term = self.term + 1
        self._prevotes = {self.node_id}
        self._election_deadline = now + self._election_timeout()
        effects: list = []
        voters = self.config.voters | (self.config.voters_old or frozenset())
        for peer in voters - {self.node_id}:
            effects.append(
                Send(peer, {
                    "type": "pre_vote",
                    "term": self._prevote_term,
                    "candidate_id": self.node_id,
                    "last_log_index": self.last_index,
                    "last_log_term": self.last_term,
                })
            )
        if self.config.has_quorum(self._prevotes):  # single-node cluster
            self._prevote_term = None
            effects += self._start_election(now)
        return effects

    def _start_election(self, now: float, transfer: bool = False) -> list:
        self.role = Role.CANDIDATE
        self._prevote_term = None
        self._prevotes = set()
        self.term += 1
        self.voted_for = self.node_id
        self.leader_id = None
        self.votes = {self.node_id}
        self._election_deadline = now + self._election_timeout()
        effects: list = [PersistHardState(self.term, self.voted_for)]
        voters = self.config.voters | (self.config.voters_old or frozenset())
        for peer in voters - {self.node_id}:
            effects.append(
                Send(peer, {
                    "type": "request_vote",
                    "term": self.term,
                    "candidate_id": self.node_id,
                    "last_log_index": self.last_index,
                    "last_log_term": self.last_term,
                    # Transfer elections bypass vote stickiness: the old
                    # leader asked for this election itself.
                    "transfer": transfer,
                })
            )
        if self.config.has_quorum(self.votes):  # single-node cluster
            effects += self._become_leader(now)
        return effects

    def _become_leader(self, now: float) -> list:
        self.role = Role.LEADER
        self.leader_id = self.node_id
        self.votes = set()
        self._transfer_target = None
        self.next_index = {p: self.last_index + 1 for p in self.config.all_nodes()}
        self.match_index = {p: 0 for p in self.config.all_nodes()}
        self._peer_ack_seq = {p: 0 for p in self.config.all_nodes()}
        self._pending_reads = []
        self._lease_until = float("-inf")  # no lease until own-term quorum
        self._quorum_contact = now
        self._transfer_fired = False
        self._heartbeat_due = now + self.timings.heartbeat
        effects: list = [BecameLeader(self.term)]
        # Commit-barrier no-op so this term can commit prior-term entries
        # and ReadIndex is immediately safe once it commits.
        effects += self._append_local({"_noop": True})
        self._new_probe_round(now)
        effects += self._broadcast_append()
        return effects

    def _step_down(self, term: int, now: float) -> list:
        effects: list = []
        was_leader = self.role == Role.LEADER
        if term > self.term:
            self.term = term
            self.voted_for = None
            effects.append(PersistHardState(self.term, self.voted_for))
        self.role = Role.FOLLOWER
        self.votes = set()
        self._prevote_term = None
        self._prevotes = set()
        self._pending_reads = []
        self._catchup = None
        self._transfer_target = None
        self._transfer_fired = False
        self._lease_until = float("-inf")
        self._election_deadline = now + self._election_timeout()
        if was_leader:
            effects.append(SteppedDown(self.term))
        return effects

    # ------------------------------------------------------------ proposals

    def propose(self, command: Any, now: float) -> tuple[int, list]:
        """Append a command; returns (log index, effects). Raises NotLeaderError
        with the last-known leader hint when not leader (the client-visible
        ``Not Leader|<hint>`` convention, reference mod.rs:1442-1467)."""
        indices, effects = self.propose_batch([command], now)
        return indices[0], effects

    def propose_batch(self, commands: list, now: float) -> tuple[list[int], list]:
        """Append a batch of commands as one log-append + one replication
        round (the reference drains up to 256 queued events per loop and
        batch-appends them, simple_raft.rs:1174-1185,1689-1778). Returns
        (log indices, effects) — a single AppendLog effect covers the whole
        batch, so the WAL takes one fsync for N proposals."""
        if self.role != Role.LEADER or self._transfer_target:
            raise NotLeaderError(self._transfer_target or self.leader_id)
        effects = self._append_local_batch(commands)
        # An append round is a probe round: it postpones the tick's
        # heartbeat, which is otherwise the only place a round opens. A
        # leader proposing faster than the heartbeat interval would keep
        # sending the last heartbeat's stale seq, stop renewing quorum
        # contact and its lease, and be deposed by check-quorum
        # 2 * election_max into any sustained write burst.
        self._new_probe_round(now)
        effects += self._broadcast_append()
        self._heartbeat_due = now + self.timings.heartbeat
        first = self.last_index - len(commands) + 1
        return list(range(first, self.last_index + 1)), effects

    def _append_local(self, command: Any) -> list:
        return self._append_local_batch([command])

    def _append_local_batch(self, commands: list) -> list:
        entries = []
        for command in commands:
            entry = LogEntry(self.last_index + 1, self.term, command)
            self.log.append(entry)
            cfg = self._config_of(entry)
            if cfg is not None:
                self.config = cfg
                # Quorum membership changed: a lease earned under the old
                # config must not survive into the new one (joint consensus
                # makes this redundant in theory; keep it belt-and-braces).
                self._lease_until = float("-inf")
            entries.append(entry)
        effects: list = [AppendLog(tuple(entries))]
        # Single-node: may commit immediately.
        effects += self._advance_commit()
        return effects

    # ------------------------------------------------------------- ReadIndex

    def _new_probe_round(self, now: float) -> None:
        """Open a heartbeat round: bump the probe seq and record its send
        time. An ack for seq >= s proves the follower received a message
        sent no earlier than ``_probe_sent_at[s]`` — the foundation both of
        the leader lease and of check-quorum."""
        self._probe_seq += 1
        self._probe_sent_at[self._probe_seq] = now

    def _lease_duration(self) -> float:
        return self.timings.election_min * \
            (1.0 - self.timings.clock_drift_bound)

    def _update_lease(self) -> None:
        """Extend the lease from the newest probe round a quorum has acked:
        every acked follower reset its election timer no earlier than that
        round's send time, and (vote stickiness) refuses non-transfer votes
        for election_min after — so no new leader can exist before
        sent + election_min, drift margin deducted."""
        if self.role != Role.LEADER:
            return
        for s in sorted(set(self._peer_ack_seq.values()), reverse=True):
            if s <= 0:
                continue
            supporters = {self.node_id} | {
                p for p, q in self._peer_ack_seq.items() if q >= s
            }
            if not self.config.has_quorum(supporters):
                continue
            sent = self._probe_sent_at.get(s)
            if sent is not None:
                self._quorum_contact = max(self._quorum_contact, sent)
                self._lease_until = max(
                    self._lease_until, sent + self._lease_duration()
                )
                for old in [x for x in self._probe_sent_at if x < s]:
                    del self._probe_sent_at[old]
            return

    def lease_valid(self, now: float) -> bool:
        """True iff a lease read may skip the heartbeat-quorum round-trip."""
        return (
            self.role == Role.LEADER
            and self.timings.lease_reads
            and self.timings.prevote  # stickiness alone doesn't gate
            and self._transfer_target is None
            and not self._transfer_fired
            and now < self._lease_until
        )

    def read_index(self, request_id: Any, now: float) -> list:
        """Linearizable read barrier (reference simple_raft.rs:1863-1887):
        capture commit_index, then confirm leadership with a heartbeat quorum;
        ReadReady fires once confirmed AND last_applied has caught up. When
        the leader lease is valid the quorum round-trip is skipped entirely
        (Raft §6.4.1) — same linearizability, one network round cheaper.

        A fresh leader must first commit an entry of its own term (Raft §8 /
        §6.4): until then its commit_index may lag the true cluster commit
        point, so the read index is left unassigned (None) and filled in by
        ``_check_reads`` once the current-term no-op commits."""
        if self.role != Role.LEADER:
            raise NotLeaderError(self.leader_id)
        own_term_committed = self.term_at(self.commit_index) == self.term
        if own_term_committed and self.lease_valid(now):
            index = self.commit_index
            if self.last_applied >= index:
                return [ReadReady(request_id, index)]
            read = {"id": request_id, "index": index, "seq": 0, "lease": True}
            self._pending_reads.append(read)
            return []
        index = self.commit_index if own_term_committed else None
        self._new_probe_round(now)
        read = {"id": request_id, "index": index, "seq": self._probe_seq}
        self._pending_reads.append(read)
        effects = self._broadcast_append()
        self._heartbeat_due = now + self.timings.heartbeat
        # Single-node quorum satisfies immediately.
        effects += self._check_reads()
        return effects

    def _check_reads(self) -> list:
        if self.role != Role.LEADER or not self._pending_reads:
            return []
        own_term_committed = self.term_at(self.commit_index) == self.term
        effects: list = []
        remaining: list[dict] = []
        for read in self._pending_reads:
            if read.get("lease"):
                # Lease read: index was fixed under a valid lease; it only
                # waits for the state machine to catch up, never for acks.
                if self.last_applied >= read["index"]:
                    effects.append(ReadReady(read["id"], read["index"]))
                else:
                    remaining.append(read)
                continue
            if read["index"] is None:
                if not own_term_committed:
                    remaining.append(read)
                    continue
                # commit_index now covers everything committed before this
                # leader's term, so it is a safe (conservative) read index.
                read["index"] = self.commit_index
            acks = {self.node_id} | {
                p for p, s in self._peer_ack_seq.items() if s >= read["seq"]
            }
            if self.config.has_quorum(acks) and self.last_applied >= read["index"]:
                effects.append(ReadReady(read["id"], read["index"]))
            else:
                remaining.append(read)
        self._pending_reads = remaining
        return effects

    # ----------------------------------------------------------- replication

    def _broadcast_append(self) -> list:
        effects: list = []
        for peer in self.config.all_nodes() - {self.node_id}:
            effects += self._send_append(peer)
        return effects

    def _send_append(self, peer: str) -> list:
        next_idx = self.next_index.get(peer, self.last_index + 1)
        if next_idx < self.log_start:
            assert self.snapshot is not None
            return [Send(peer, {
                "type": "install_snapshot",
                "term": self.term,
                "leader_id": self.node_id,
                "snapshot": self.snapshot.to_dict(),
                "seq": self._probe_seq,
            })]
        prev_index = next_idx - 1
        prev_term = self.term_at(prev_index)
        if prev_term is None:  # compacted concurrently; retry via snapshot
            return self._send_append_snapshot_fallback(peer)
        entries = self.entries_from(next_idx)
        return [Send(peer, {
            "type": "append_entries",
            "term": self.term,
            "leader_id": self.node_id,
            "prev_log_index": prev_index,
            "prev_log_term": prev_term,
            "entries": [e.to_dict() for e in entries],
            "leader_commit": self.commit_index,
            "seq": self._probe_seq,
        })]

    def _send_append_snapshot_fallback(self, peer: str) -> list:
        if self.snapshot is None:
            return []
        return [Send(peer, {
            "type": "install_snapshot",
            "term": self.term,
            "leader_id": self.node_id,
            "snapshot": self.snapshot.to_dict(),
            "seq": self._probe_seq,
        })]

    def _advance_commit(self) -> list:
        """Joint-majority commit rule (reference simple_raft.rs:2246-2277) with
        the current-term restriction (Raft §5.4.2)."""
        if self.role != Role.LEADER:
            return []
        for n in range(self.last_index, self.commit_index, -1):
            if self.term_at(n) != self.term:
                break
            acks = {self.node_id} | {
                p for p, m in self.match_index.items() if m >= n
            }
            if self.config.has_quorum(acks):
                return self._commit_to(n)
        return []

    def _commit_to(self, n: int) -> list:
        self.commit_index = n
        effects = self._apply_committed()
        effects += self._check_reads()
        effects += self._maybe_advance_membership()
        # A leader removed by a committed final config steps down
        # (joint-consensus exit, Raft §6).
        if (
            self.role == Role.LEADER
            and not self.config.joint
            and self.node_id not in self.config.voters
        ):
            effects += self._step_down(self.term, 0.0)
        return effects

    def _apply_committed(self) -> list:
        if self.last_applied >= self.commit_index:
            return []
        entries = [
            e for e in self.entries_from(self.last_applied + 1,
                                         self.commit_index - self.last_applied)
            if e.index <= self.commit_index
        ]
        if not entries:
            return []
        self.last_applied = entries[-1].index
        return [Apply(tuple(entries))]

    # -------------------------------------------------------- message intake

    #: Fields each message type must carry, with the types the handlers
    #: index without further checks. Malformed peer input must be rejected
    #: BEFORE any state mutation: an exception mid-handler would leave the
    #: core half-updated (e.g. a truncated log whose TruncateLog effect
    #: never reached storage). The reference gets this for free from
    #: protobuf; msgpack-over-gRPC needs an explicit envelope check.
    _REQUIRED: dict = {
        "pre_vote": ("term", "candidate_id", "last_log_index",
                     "last_log_term"),
        "pre_vote_response": ("term", "from", "vote_granted"),
        "request_vote": ("term", "candidate_id", "last_log_index",
                         "last_log_term"),
        "request_vote_response": ("term", "from", "vote_granted"),
        "append_entries": ("term", "leader_id", "prev_log_index",
                           "prev_log_term", "leader_commit"),
        "append_entries_response": ("term", "from", "success",
                                    "match_index"),
        "install_snapshot": ("term", "leader_id", "snapshot"),
        "install_snapshot_response": ("term", "from", "last_index"),
        "timeout_now": (),
    }
    _INT_FIELDS = ("term", "prev_log_index", "prev_log_term",
                   "leader_commit", "last_log_index", "last_log_term",
                   "match_index", "seq", "conflict_index", "last_index")

    def _valid_message(self, msg: Any) -> bool:
        if not isinstance(msg, dict):
            return False
        required = self._REQUIRED.get(msg.get("type"))
        if required is None:
            return False
        if any(f not in msg for f in required):
            return False
        for f in self._INT_FIELDS:
            if f in msg and not isinstance(msg[f], int):
                return False
        for f in ("from", "leader_id", "candidate_id"):
            # Handlers use these as dict/set keys and Send targets: they
            # must be strings (an unhashable value would raise mid-handler).
            if f in msg and not isinstance(msg[f], str):
                return False
        if msg["type"] == "append_entries":
            entries = msg.get("entries") or []
            if not isinstance(entries, list):
                return False
            for e in entries:
                if not isinstance(e, dict) \
                        or not isinstance(e.get("index"), int) \
                        or not isinstance(e.get("term"), int) \
                        or "command" not in e:
                    return False
        if msg["type"] == "install_snapshot":
            snap = msg["snapshot"]
            if not isinstance(snap, dict) \
                    or not isinstance(snap.get("last_index"), int) \
                    or not isinstance(snap.get("last_term"), int) \
                    or not isinstance(snap.get("config"), dict) \
                    or "data" not in snap:
                return False
            cfg = snap["config"]
            groups = [cfg.get("voters"), cfg.get("voters_old"),
                      cfg.get("learners")]
            for g in groups:
                if g is None:
                    continue
                if not isinstance(g, list) \
                        or any(not isinstance(x, str) for x in g):
                    return False
        return True

    def handle_message(self, msg: dict, now: float) -> list:
        if not self._valid_message(msg):
            return []
        mtype = msg["type"]
        term = int(msg.get("term", 0))
        effects: list = []
        # Pre-vote traffic carries the PROSPECTIVE term and must never bump
        # anyone's real term — that is the whole point of pre-vote.
        if term > self.term and mtype not in ("pre_vote",
                                              "pre_vote_response"):
            effects += self._step_down(term, now)
        handler = {
            "pre_vote": self._on_pre_vote,
            "pre_vote_response": self._on_pre_vote_response,
            "request_vote": self._on_request_vote,
            "request_vote_response": self._on_vote_response,
            "append_entries": self._on_append_entries,
            "append_entries_response": self._on_append_response,
            "install_snapshot": self._on_install_snapshot,
            "install_snapshot_response": self._on_install_snapshot_response,
            "timeout_now": self._on_timeout_now,
        }[mtype]
        return effects + handler(msg, now)

    def _on_pre_vote(self, msg: dict, now: float) -> list:
        """Grant iff we'd plausibly vote for this candidate in a real
        election AND we have not heard from a live leader within the minimum
        election timeout — a node still in contact with its leader refuses,
        which is what stops a healed stragglers' election from deposing a
        healthy leader. Grants are non-binding: no term bump, no voted_for,
        nothing persisted, any number of grants per term."""
        up_to_date = (
            int(msg["last_log_term"]) > self.last_term
            or (
                int(msg["last_log_term"]) == self.last_term
                and int(msg["last_log_index"]) >= self.last_index
            )
        )
        granted = (
            int(msg["term"]) > self.term
            and up_to_date
            and self.role != Role.LEADER
            and now - self._last_leader_contact >= self.timings.election_min
        )
        return [Send(msg["candidate_id"], {
            "type": "pre_vote_response",
            "term": int(msg["term"]),
            "from": self.node_id,
            "vote_granted": granted,
        })]

    def _on_pre_vote_response(self, msg: dict, now: float) -> list:
        if self._prevote_term is None or \
                int(msg["term"]) != self._prevote_term or \
                self._prevote_term != self.term + 1 or \
                self.role == Role.LEADER:
            return []
        if msg["vote_granted"]:
            self._prevotes.add(msg["from"])
            if self.config.has_quorum(self._prevotes):
                self._prevote_term = None
                self._prevotes = set()
                return self._start_election(now)
        return []

    def _on_request_vote(self, msg: dict, now: float) -> list:
        granted = False
        # Vote stickiness (etcd check-quorum companion; load-bearing for
        # leader leases): a node that heard from a live leader within the
        # minimum election timeout refuses to elect a new one — except for
        # leadership-transfer elections, which the old leader itself
        # initiated (and which permanently void its lease, _transfer_fired).
        sticky = (
            not msg.get("transfer")
            and now - self._last_leader_contact < self.timings.election_min
        )
        if int(msg["term"]) >= self.term and not sticky:
            up_to_date = (
                int(msg["last_log_term"]) > self.last_term
                or (
                    int(msg["last_log_term"]) == self.last_term
                    and int(msg["last_log_index"]) >= self.last_index
                )
            )
            if up_to_date and self.voted_for in (None, msg["candidate_id"]) \
                    and self.role != Role.LEADER:
                granted = True
                self.voted_for = msg["candidate_id"]
                self._election_deadline = now + self._election_timeout()
        effects: list = []
        if granted:
            effects.append(PersistHardState(self.term, self.voted_for))
        effects.append(Send(msg["candidate_id"], {
            "type": "request_vote_response",
            "term": self.term,
            "from": self.node_id,
            "vote_granted": granted,
        }))
        return effects

    def _on_vote_response(self, msg: dict, now: float) -> list:
        if self.role != Role.CANDIDATE or int(msg["term"]) != self.term:
            return []
        if msg["vote_granted"]:
            self.votes.add(msg["from"])
            if self.config.has_quorum(self.votes):
                return self._become_leader(now)
        return []

    def _on_append_entries(self, msg: dict, now: float) -> list:
        effects: list = []
        leader = msg["leader_id"]
        if int(msg["term"]) < self.term:
            return [Send(leader, self._append_response(False, msg))]
        # Valid leader for this term.
        if self.role != Role.FOLLOWER:
            effects += self._step_down(int(msg["term"]), now)
        self.leader_id = leader
        self._election_deadline = now + self._election_timeout()
        self._last_leader_contact = now
        # A live leader aborts any open pre-vote round: late-arriving
        # grants must not spring a term-bumping election on it.
        self._prevote_term = None
        self._prevotes = set()

        prev_index = int(msg["prev_log_index"])
        prev_term = int(msg["prev_log_term"])
        local_prev_term = self.term_at(prev_index)
        if prev_index > 0 and local_prev_term != prev_term:
            if local_prev_term is None and self.snapshot \
                    and prev_index < self.snapshot.last_index:
                # Already covered by our snapshot; ask from snapshot end.
                conflict = self.snapshot.last_index + 1
            elif local_prev_term is None:
                conflict = self.last_index + 1
            else:
                # First index of the conflicting term (accelerated back-off).
                conflict = prev_index
                while conflict > self.log_start and \
                        self.term_at(conflict - 1) == local_prev_term:
                    conflict -= 1
            resp = self._append_response(False, msg)
            resp["conflict_index"] = conflict
            return effects + [Send(leader, resp)]

        entries = [LogEntry.from_dict(e) for e in msg.get("entries") or []]
        new_entries: list[LogEntry] = []
        truncated_from: int | None = None
        for e in entries:
            local = self.entry(e.index)
            if local is not None and local.term != e.term:
                # Conflict: drop this and everything after (and forget any
                # config that lived only in the truncated suffix).
                pos = e.index - self.log_start
                del self.log[pos:]
                truncated_from = e.index
                local = None
            if local is None and e.index == self.last_index + 1:
                self.log.append(e)
                new_entries.append(e)
                cfg = self._config_of(e)
                if cfg is not None:
                    self.config = cfg
        if truncated_from is not None:
            effects.append(TruncateLog(truncated_from))
            self._recompute_config()
        if new_entries:
            effects.append(AppendLog(tuple(new_entries)))

        # The follower may hold a divergent tail past the leader's entries, so
        # only prev_log_index + len(entries) is CONFIRMED matched — reporting
        # last_index here would let the leader count unheld entries toward
        # quorum and commit without a real majority.
        confirmed = prev_index + len(entries)
        leader_commit = int(msg["leader_commit"])
        if leader_commit > self.commit_index:
            self.commit_index = min(leader_commit, confirmed, self.last_index)
            effects += self._apply_committed()

        effects.append(Send(leader, self._append_response(True, msg, confirmed)))
        return effects

    def _append_response(self, success: bool, msg: dict, match: int = 0) -> dict:
        return {
            "type": "append_entries_response",
            "term": self.term,
            "from": self.node_id,
            "success": success,
            "match_index": match if success else 0,
            "seq": int(msg.get("seq", 0)),
        }

    def _on_append_response(self, msg: dict, now: float) -> list:
        if self.role != Role.LEADER or int(msg["term"]) != self.term:
            return []
        peer = msg["from"]
        seq = int(msg.get("seq", 0))
        if seq > self._peer_ack_seq.get(peer, 0):
            self._peer_ack_seq[peer] = seq
            self._update_lease()
        effects: list = []
        if msg["success"]:
            match = int(msg["match_index"])
            if match > self.match_index.get(peer, 0):
                self.match_index[peer] = match
            self.next_index[peer] = max(self.next_index.get(peer, 1), match + 1)
            effects += self._advance_commit()
            effects += self._check_reads()
            effects += self._tick_catchup(peer)
            # Leader transfer: fire TimeoutNow once the target caught up
            # (reference initiate_leader_transfer, simple_raft.rs:2740-2813).
            if self._transfer_target == peer and match >= self.last_index:
                self._transfer_fired = True  # lease void until next term
                self._lease_until = float("-inf")
                effects.append(Send(peer, {"type": "timeout_now", "term": self.term}))
            # Keep streaming if the follower is still behind.
            if self.next_index[peer] <= self.last_index:
                effects += self._send_append(peer)
        else:
            conflict = int(msg.get("conflict_index", 0))
            self.next_index[peer] = max(
                1, conflict if conflict else self.next_index.get(peer, 2) - 1
            )
            effects += self._send_append(peer)
        return effects

    def _on_install_snapshot(self, msg: dict, now: float) -> list:
        effects: list = []
        if int(msg["term"]) < self.term:
            return []
        if self.role != Role.FOLLOWER:
            effects += self._step_down(int(msg["term"]), now)
        self.leader_id = msg["leader_id"]
        self._election_deadline = now + self._election_timeout()
        self._last_leader_contact = now
        # A live leader aborts any open pre-vote round: late-arriving
        # grants must not spring a term-bumping election on it.
        self._prevote_term = None
        self._prevotes = set()
        snap = Snapshot.from_dict(msg["snapshot"])
        if self.snapshot is None or snap.last_index > self.snapshot.last_index:
            # Keep any log suffix that extends past the snapshot and matches.
            if self.term_at(snap.last_index) == snap.last_term:
                self.log = [e for e in self.log if e.index > snap.last_index]
            else:
                self.log = []
            self.snapshot = snap
            self.config = snap.config
            for e in self.log:
                cfg = self._config_of(e)
                if cfg is not None:
                    self.config = cfg
            self.commit_index = max(self.commit_index, snap.last_index)
            self.last_applied = max(self.last_applied, snap.last_index)
            effects.append(SaveSnapshot(snap))
            effects.append(RestoreFromSnapshot(snap))
        effects.append(Send(msg["leader_id"], {
            "type": "install_snapshot_response",
            "term": self.term,
            "from": self.node_id,
            "last_index": self.snapshot.last_index if self.snapshot else 0,
            "seq": int(msg.get("seq", 0)),
        }))
        return effects

    def _on_install_snapshot_response(self, msg: dict, now: float) -> list:
        if self.role != Role.LEADER or int(msg["term"]) != self.term:
            return []
        peer = msg["from"]
        last = int(msg["last_index"])
        seq = int(msg.get("seq", 0))
        if seq > self._peer_ack_seq.get(peer, 0):
            self._peer_ack_seq[peer] = seq
            self._update_lease()
        self.match_index[peer] = max(self.match_index.get(peer, 0), last)
        self.next_index[peer] = last + 1
        effects = self._advance_commit()
        effects += self._check_reads()
        if self.next_index[peer] <= self.last_index:
            effects += self._send_append(peer)
        return effects

    def _on_timeout_now(self, msg: dict, now: float) -> list:
        """Immediate election for leader transfer (reference TimeoutNow route,
        bin/master.rs:163-171). Stale-term transfers are ignored so a delayed
        TimeoutNow can't depose a healthy later-term leader."""
        if int(msg.get("term", 0)) < self.term:
            return []
        if not self.is_voter or self.role == Role.LEADER:
            return []
        return self._start_election(now, transfer=True)

    # ------------------------------------------------------------ membership

    def add_server(self, node: str, now: float) -> list:
        """Begin adding a voter: the node first replicates as a non-voting
        learner; once caught up (or after N catch-up rounds) the joint config
        is proposed (reference BeginJointConsensus + CatchUpProgress,
        simple_raft.rs:72-106,241-243)."""
        if self.role != Role.LEADER:
            raise NotLeaderError(self.leader_id)
        if self.config.joint or self._catchup is not None:
            raise ValueError("membership change already in progress")
        if node in self.config.voters:
            raise ValueError(f"{node} is already a voter")
        self._catchup = {
            "node": node,
            "rounds_left": self.timings.catchup_rounds,
            "target": self.last_index,
        }
        new_cfg = replace(self.config, learners=self.config.learners | {node})
        _, effects = self.propose({"_config": new_cfg.to_dict()}, now)
        self.next_index.setdefault(node, 1)
        self.match_index.setdefault(node, 0)
        self._peer_ack_seq.setdefault(node, 0)
        return effects

    def remove_server(self, node: str, now: float) -> list:
        if self.role != Role.LEADER:
            raise NotLeaderError(self.leader_id)
        if self.config.joint or self._catchup is not None:
            raise ValueError("membership change already in progress")
        if node not in self.config.voters:
            raise ValueError(f"{node} is not a voter")
        if len(self.config.voters) == 1:
            raise ValueError("cannot remove the last voter")
        joint = Config(
            voters=self.config.voters - {node},
            voters_old=self.config.voters,
            learners=self.config.learners,
        )
        _, effects = self.propose({"_config": joint.to_dict()}, now)
        return effects

    def _tick_catchup(self, peer: str) -> list:
        """Promote a caught-up learner into joint consensus."""
        cu = self._catchup
        if cu is None or cu["node"] != peer or self.config.joint:
            return []
        if self.match_index.get(peer, 0) >= cu["target"]:
            self._catchup = None
            joint = Config(
                voters=self.config.voters | {peer},
                voters_old=self.config.voters,
                learners=self.config.learners - {peer},
            )
            _, effects = self.propose({"_config": joint.to_dict()}, 0.0)
            return effects
        cu["rounds_left"] -= 1
        cu["target"] = self.last_index
        if cu["rounds_left"] <= 0:
            self._catchup = None  # abandon: learner too slow
        return []

    def _maybe_advance_membership(self) -> list:
        """Once the joint config commits, propose the final config
        (reference FinalizeConfiguration, simple_raft.rs:2458-2512)."""
        if self.role != Role.LEADER or not self.config.joint:
            return []
        # Find the latest config entry still in the log.
        for e in reversed(self.log):
            cfg = self._config_of(e)
            if cfg is None:
                continue
            if not cfg.joint:
                return []  # final already proposed
            if e.index <= self.commit_index:
                final = Config(voters=cfg.voters, learners=cfg.learners)
                _, effects = self.propose({"_config": final.to_dict()}, 0.0)
                return effects
            return []
        # No config entry in the log: the joint config came from the snapshot,
        # hence is committed — propose the final config so the cluster doesn't
        # stay in joint consensus forever after compaction.
        cfg = self.config
        final = Config(voters=cfg.voters, learners=cfg.learners)
        _, effects = self.propose({"_config": final.to_dict()}, 0.0)
        return effects

    def transfer_leadership(self, target: str, now: float,
                            timeout: float = 5.0) -> list:
        """Stop accepting proposals, catch the target up, then TimeoutNow
        (reference simple_raft.rs:2740-2813)."""
        if self.role != Role.LEADER:
            raise NotLeaderError(self.leader_id)
        if target not in self.config.voters:
            raise ValueError(f"{target} is not a voter")
        if target == self.node_id:
            return []
        self._transfer_target = target
        self._transfer_deadline = now + timeout
        if self.match_index.get(target, 0) >= self.last_index:
            self._transfer_fired = True  # lease void until next term
            self._lease_until = float("-inf")
            return [Send(target, {"type": "timeout_now", "term": self.term})]
        return self._send_append(target)

    # -------------------------------------------------------------- snapshot

    def compact(self, state_machine_data: bytes) -> list:
        """Install a local snapshot at last_applied and drop covered entries
        (reference create_snapshot, simple_raft.rs:1033-1097)."""
        if self.last_applied < self.log_start:
            return []
        last_term = self.term_at(self.last_applied)
        assert last_term is not None
        snap = Snapshot(
            last_index=self.last_applied,
            last_term=last_term,
            config=self._config_at(self.last_applied),
            data=state_machine_data,
        )
        self.log = [e for e in self.log if e.index > self.last_applied]
        self.snapshot = snap
        return [SaveSnapshot(snap)]

    def _config_at(self, index: int) -> Config:
        cfg = self.snapshot.config if self.snapshot else self.config
        latest = None
        for e in self.log:
            if e.index > index:
                break
            c = self._config_of(e)
            if c is not None:
                latest = c
        if latest is not None:
            return latest
        # No config entry at/below index in the in-memory log.
        if self.snapshot:
            return self.snapshot.config
        return cfg

    # ------------------------------------------------------------- inspection

    def status(self, now: float | None = None) -> dict:
        d = {
            "node_id": self.node_id,
            "role": self.role.value,
            "term": self.term,
            "leader_id": self.leader_id,
            "commit_index": self.commit_index,
            "last_applied": self.last_applied,
            "last_index": self.last_index,
            "log_len": len(self.log),
            "config": self.config.to_dict(),
            "snapshot_index": self.snapshot.last_index if self.snapshot else 0,
        }
        if now is not None and self.role == Role.LEADER:
            d["lease_valid"] = self.lease_valid(now)
            d["lease_remaining_s"] = round(max(0.0, self._lease_until - now), 4)
            d["quorum_contact_age_s"] = round(max(0.0, now - self._quorum_contact), 4)
        return d
