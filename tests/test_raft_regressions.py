"""Targeted regressions for subtle consensus bugs found in review.

These drive RaftCore directly (no simulator) to pin down exact message-level
behavior."""

import random

from tpudfs.raft.core import (
    Config,
    LogEntry,
    RaftCore,
    ReadReady,
    Role,
    Send,
    Timings,
)

FAST = Timings(election_min=0.1, election_max=0.2, heartbeat=0.05,
               prevote=False)  # these tests hand-drive raw elections


def _mk(node_id, voters, log=None, term=0):
    return RaftCore(
        node_id,
        Config(voters=frozenset(voters)),
        term=term,
        log=log or [],
        timings=FAST,
        rng=random.Random(0),
    )


def _sends(effects, mtype=None):
    out = [e for e in effects if isinstance(e, Send)]
    if mtype:
        out = [e for e in out if e.msg["type"] == mtype]
    return out


def test_append_response_reports_confirmed_match_not_last_index():
    """A follower with a divergent longer tail must only ack what the leader
    actually confirmed (prev + len(entries)); acking its own last_index would
    let a leader commit entries the follower does not hold."""
    common = [LogEntry(1, 1, {"v": 1}), LogEntry(2, 1, {"v": 2})]
    stale_tail = [LogEntry(3, 2, {"v": "stale3"}), LogEntry(4, 2, {"v": "stale4"})]
    f = _mk("f", ["f", "l", "x"], log=common + stale_tail, term=2)
    # Leader of term 3 heartbeats at prev=2 (no entries).
    effects = f.handle_message(
        {
            "type": "append_entries",
            "term": 3,
            "leader_id": "l",
            "prev_log_index": 2,
            "prev_log_term": 1,
            "entries": [],
            "leader_commit": 0,
            "seq": 1,
        },
        now=0.0,
    )
    resp = _sends(effects, "append_entries_response")[0].msg
    assert resp["success"] is True
    assert resp["match_index"] == 2, "must not ack the stale tail"


def test_leader_commit_capped_to_confirmed_prefix():
    """Follower must not advance commit_index into its unconfirmed tail even
    if leader_commit is higher."""
    common = [LogEntry(1, 1, {"v": 1})]
    stale = [LogEntry(2, 2, {"v": "stale"}), LogEntry(3, 2, {"v": "stale"})]
    f = _mk("f", ["f", "l", "x"], log=common + stale, term=2)
    effects = f.handle_message(
        {
            "type": "append_entries",
            "term": 3,
            "leader_id": "l",
            "prev_log_index": 1,
            "prev_log_term": 1,
            "entries": [],
            "leader_commit": 3,  # leader has committed 3 entries of ITS log
            "seq": 1,
        },
        now=0.0,
    )
    del effects
    assert f.commit_index == 1, "commit must stop at the confirmed prefix"


def test_fresh_leader_defers_read_index_until_own_term_commit():
    """ReadIndex on a leader that has not yet committed an entry of its own
    term must wait (stale-read prevention, Raft §8)."""
    # l holds an entry committed under the old term but doesn't know it.
    log = [LogEntry(1, 1, {"v": "committed-under-old-leader"})]
    l = _mk("l", ["l", "a", "b"], log=log, term=1)
    # Win an election for term 2.
    l.tick(10.0)  # election timeout fires
    assert l.role == Role.CANDIDATE and l.term == 2
    l.handle_message(
        {"type": "request_vote_response", "term": 2, "from": "a",
         "vote_granted": True}, 10.0,
    )
    assert l.role == Role.LEADER
    assert l.last_index == 2  # no-op appended
    # Read before the no-op commits: must NOT become ready even with acks.
    effects = l.read_index("r1", 10.0)
    assert not any(isinstance(e, ReadReady) for e in effects)
    # Ack the heartbeat probe but only match up to index 1 (old entry).
    effects = l.handle_message(
        {"type": "append_entries_response", "term": 2, "from": "a",
         "success": True, "match_index": 1, "seq": l._probe_seq}, 10.0,
    )
    assert not any(isinstance(e, ReadReady) for e in effects), \
        "read served before own-term no-op committed"
    # Now a confirms the no-op too: commit advances, read becomes ready.
    effects = l.handle_message(
        {"type": "append_entries_response", "term": 2, "from": "a",
         "success": True, "match_index": 2, "seq": l._probe_seq}, 10.0,
    )
    ready = [e for e in effects if isinstance(e, ReadReady)]
    assert ready and ready[0].read_index >= 1
    assert l.commit_index == 2


def test_stale_timeout_now_ignored():
    f = _mk("f", ["f", "l", "x"], term=5)
    effects = f.handle_message({"type": "timeout_now", "term": 3}, 0.0)
    assert effects == [] and f.role == Role.FOLLOWER and f.term == 5
    # Current-term transfer works.
    effects = f.handle_message({"type": "timeout_now", "term": 5}, 0.0)
    assert f.role == Role.CANDIDATE and f.term == 6


def test_truncation_reverts_uncommitted_config():
    """A config picked up from an uncommitted entry must be forgotten when
    that entry is truncated by the new leader."""
    base = [LogEntry(1, 1, {"v": 1})]
    phantom_cfg = Config(voters=frozenset(["f", "l", "x", "ghost"]))
    phantom = [LogEntry(2, 2, {"_config": phantom_cfg.to_dict()})]
    f = _mk("f", ["f", "l", "x"], log=base + phantom, term=2)
    assert "ghost" in f.config.voters
    # New leader (term 3) overwrites index 2 with a normal entry.
    f.handle_message(
        {
            "type": "append_entries",
            "term": 3,
            "leader_id": "l",
            "prev_log_index": 1,
            "prev_log_term": 1,
            "entries": [LogEntry(2, 3, {"v": "real"}).to_dict()],
            "leader_commit": 2,
            "seq": 1,
        },
        0.0,
    )
    assert "ghost" not in f.config.voters
    assert f.config.voters == frozenset(["f", "l", "x"])


def test_joint_config_from_snapshot_still_finalizes():
    """If the joint config entry was compacted into a snapshot, a leader must
    still propose the final config (no permanent joint state)."""
    from tpudfs.raft.core import Snapshot

    joint = Config(
        voters=frozenset(["l", "a", "b", "c"]),
        voters_old=frozenset(["l", "a", "b"]),
    )
    snap = Snapshot(last_index=5, last_term=1, config=joint, data=b"")
    l = RaftCore(
        "l", joint, term=1, snapshot=snap, timings=FAST, rng=random.Random(0)
    )
    assert l.config.joint
    l.tick(10.0)
    for peer in ("a", "b", "c"):
        l.handle_message(
            {"type": "request_vote_response", "term": 2, "from": peer,
             "vote_granted": True}, 10.0,
        )
        if l.role == Role.LEADER:
            break
    assert l.role == Role.LEADER
    # Ack replication of the no-op from a quorum of both voter sets.
    for peer in ("a", "b", "c"):
        l.handle_message(
            {"type": "append_entries_response", "term": 2, "from": peer,
             "success": True, "match_index": l.last_index, "seq": 0}, 10.0,
        )
    # The leader must have proposed a final (non-joint) config.
    final_cfgs = [
        e for e in l.log
        if isinstance(e.command, dict) and "_config" in e.command
        and Config.from_dict(e.command["_config"]).joint is False
    ]
    assert final_cfgs, "cluster stuck in joint consensus after compaction"


def test_malformed_peer_messages_are_rejected_without_state_damage():
    """Garbage peer input (wrong types, missing fields, malformed entries/
    snapshots) must be dropped BEFORE any state mutation — an exception
    mid-handler would tear the core (e.g. log truncated without its
    TruncateLog effect). The reference gets this from protobuf; our
    msgpack envelope needs the explicit check."""
    import random as _random

    from tests.raft_sim import SimCluster

    c = SimCluster(3, seed=77)
    lead = c.wait_for_leader()
    c.propose_and_commit({"v": 1})
    rng = _random.Random(7)
    follower = next(n for n in c.nodes.values() if n is not lead)
    garbage = [
        None, 42, "hi", [], {},
        {"type": "nope", "term": 10**9},           # unknown type, huge term
        {"type": "append_entries"},                 # missing fields
        {"type": "append_entries", "term": "9", "leader_id": "x",
         "prev_log_index": 0, "prev_log_term": 0, "leader_commit": 0},
        {"type": "append_entries", "term": 1, "leader_id": "x",
         "prev_log_index": 0, "prev_log_term": 0, "leader_commit": 0,
         "entries": [{"bogus": True}]},
        {"type": "append_entries", "term": 1, "leader_id": "x",
         "prev_log_index": 0, "prev_log_term": 0, "leader_commit": 0,
         "entries": "not-a-list"},
        {"type": "install_snapshot", "term": 1, "leader_id": "x",
         "snapshot": {"last_index": "xx"}},
        {"type": "request_vote", "term": None, "candidate_id": "x",
         "last_log_index": 0, "last_log_term": 0},
        {"type": "append_entries_response", "term": 1, "from": "x",
         "success": True, "match_index": "lots"},
    ]
    for node in (lead, follower):
        before = (node.core.term, node.core.role, node.core.last_index,
                  node.core.commit_index)
        for msg in garbage:
            assert node.core.handle_message(msg, c.now) == []
        assert (node.core.term, node.core.role, node.core.last_index,
                node.core.commit_index) == before
    # Random structural fuzz over EVERY required field name (valid-ish
    # values mixed in so handler-reaching messages actually occur): never
    # raises, and the cluster still commits afterwards.
    all_fields = sorted({f for req in type(lead.core)._REQUIRED.values()
                         for f in req} | {"entries", "seq",
                                          "conflict_index"})
    pool = [0, 1, -5, "s", None, [], {}, True, 2**40, "n0",
            [{"index": 1, "term": 1, "command": {}}], [{"bogus": 1}],
            {"last_index": 1, "last_term": 1,
             "config": {"voters": ["n0"]}, "data": b""},
            {"last_index": "x"}, {"voters": 5}]
    types = list(type(lead.core)._REQUIRED) + ["x"]
    for _ in range(1500):
        msg = {"type": rng.choice(types)}
        for f in all_fields:
            if rng.random() < 0.6:
                msg[f] = rng.choice(pool)
        lead.core.handle_message(msg, c.now)
        follower.core.handle_message(msg, c.now)
    c.run(1.0)
    c.propose_and_commit({"v": 2})


def test_sustained_proposals_keep_quorum_contact_and_lease():
    """A leader that proposes more often than the heartbeat interval never
    reaches the tick's heartbeat branch, which used to be the only place a
    probe round opened: every append carried the last heartbeat's stale
    seq, quorum contact stopped advancing, and check-quorum deposed a
    perfectly connected leader 2 * election_max into any write burst (a
    2 GiB put at 1 MiB blocks: `AllocateBlock failed ... Not Leader`).
    An append round is a probe round too."""
    from tests.raft_sim import SimCluster

    c = SimCluster(3, seed=77)
    lead = c.wait_for_leader()
    c.propose_and_commit({"v": 0})
    term = lead.core.term
    span = 3 * 2 * lead.core.timings.election_max  # 3x the check-quorum bound
    dt = 0.01
    assert 2 * dt < lead.core.timings.heartbeat  # proposals outpace it
    for i in range(int(span / dt)):
        if i % 2 == 0:
            c.propose({"v": i})
        c.step(dt)
        assert lead.core.role == Role.LEADER and lead.core.term == term, \
            f"leader deposed {c.now:.2f}s into a sustained write burst"
    assert lead.stepdowns == 0
    # Lease reads stay available through the burst (appends renew it).
    assert lead.core.lease_valid(c.now)
    status = lead.core.status(c.now)
    assert status["quorum_contact_age_s"] < lead.core.timings.heartbeat
