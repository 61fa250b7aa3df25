"""Resilience primitives: deadline propagation (contextvar + RPC metadata),
retry-budget token buckets, circuit breakers, and load shedding. Breaker and
deadline tests drive injected clocks — nothing here sleeps more than 0.2 s."""

from __future__ import annotations

import time

import grpc
import pytest

from tpudfs.common import rpc as rpc_mod
from tpudfs.common.resilience import (
    CLOSED,
    DEADLINE_KEY,
    HALF_OPEN,
    MIN_ATTEMPT_TIMEOUT,
    OPEN,
    BreakerBoard,
    BudgetExhausted,
    CircuitBreaker,
    Deadline,
    LoadShedder,
    RetryBudget,
    TokenBucket,
    attempt_timeout,
    capped_by_key,
    current_deadline,
    deadline_scope,
    overloaded_message,
    remaining_budget,
    retry_after_from_text,
    retry_after_hint,
    seed_retry_jitter,
    set_deadline,
    shielded_from_deadline,
)
from tpudfs.common.rpc import RpcClient, RpcError, RpcServer


class FakeClock:
    def __init__(self, now: float = 1000.0):
        self.now = now

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> None:
        self.now += dt


# ------------------------------------------------------------ token buckets


def test_token_bucket_starts_full_and_exhausts():
    b = TokenBucket(ratio=0.5, burst=3.0)
    assert [b.try_spend() for _ in range(4)] == [True, True, True, False]


def test_token_bucket_refills_by_ratio_and_caps_at_burst():
    b = TokenBucket(ratio=0.5, burst=3.0)
    for _ in range(3):
        b.try_spend()
    b.deposit()  # +0.5: still under a whole token
    assert not b.try_spend()
    b.deposit()  # 1.0 — one retry earned per two first tries
    assert b.try_spend()
    for _ in range(100):
        b.deposit()
    assert b.tokens == 3.0  # burst cap holds


def test_retry_budget_amplification_bound_and_counters():
    rb = RetryBudget(ratio=0.5, burst=2.0)
    granted = 0
    for _ in range(100):
        rb.on_first_attempt("cs-a")
        if rb.acquire_retry("cs-a"):
            granted += 1
    # ≤ ratio × first tries + burst: the metastable-retry-storm bound.
    assert granted <= 0.5 * 100 + 2.0
    c = rb.counters()
    assert c["retry_budget_first_tries_total"] == 100
    assert c["retry_budget_retries_total"] == granted
    assert c["retry_budget_denied_total"] == 100 - granted


def test_retry_budget_buckets_are_per_target():
    rb = RetryBudget(ratio=0.5, burst=1.0)
    while rb.acquire_retry("cs-a"):
        pass
    assert rb.acquire_retry("cs-b")  # b's bucket untouched by a's exhaustion


# ---------------------------------------------------------- circuit breaker


def test_breaker_opens_after_threshold_and_blocks_for_window():
    clk = FakeClock()
    br = CircuitBreaker(failure_threshold=3, reset_timeout=5.0, clock=clk)
    assert br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == CLOSED
    br.record_failure()
    assert br.state == OPEN
    assert not br.allow()
    clk.advance(4.9)
    assert not br.allow()


def test_breaker_half_open_single_probe_then_close():
    clk = FakeClock()
    br = CircuitBreaker(failure_threshold=1, reset_timeout=5.0, clock=clk)
    br.record_failure()
    clk.advance(5.0)
    assert br.allow()  # the probe
    assert br.state == HALF_OPEN
    assert not br.allow()  # only one probe per window
    br.record_success()
    assert br.state == CLOSED
    assert br.allow()


def test_breaker_failed_probe_doubles_window_up_to_cap():
    clk = FakeClock()
    br = CircuitBreaker(failure_threshold=1, reset_timeout=5.0,
                        max_reset=12.0, clock=clk)
    br.record_failure()  # open #1: 5s window
    clk.advance(5.0)
    assert br.allow()
    br.record_failure()  # probe fails -> open #2: 10s window
    clk.advance(9.9)
    assert not br.allow()
    clk.advance(0.1)
    assert br.allow()
    br.record_failure()  # open #3: capped at 12s, not 20s
    clk.advance(12.0)
    assert br.allow()


def test_breaker_board_counters_and_healthy_first():
    clk = FakeClock()
    board = BreakerBoard(failure_threshold=1, clock=clk)
    board.record_failure("b")
    assert board.healthy_first(["a", "b", "c"]) == ["a", "c", "b"]
    assert not board.allow("b")
    c = board.counters()
    assert c["breaker_open_count"] == 1
    assert c["breaker_opens_total"] == 1
    assert c["breaker_short_circuits_total"] == 1
    # All-open lists come back intact: breakers bias, they never blackhole.
    board.record_failure("a")
    board.record_failure("c")
    assert board.healthy_first(["a", "b", "c"]) == ["a", "b", "c"]


def test_breaker_board_is_open_asks_without_probing():
    """``is_open`` says whether the open window runs and consumes no
    probe: the EC read leaves such a holder alone, and the first ``allow``
    after the window is still the one probe."""
    clk = FakeClock()
    board = BreakerBoard(failure_threshold=1, reset_timeout=5.0, clock=clk)
    assert not board.is_open("a")  # never seen: no breaker is made
    assert "a" not in board._breakers
    board.record_failure("a")
    assert board.is_open("a") and board.is_open("a")
    assert board.counters()["breaker_short_circuits_total"] == 0
    clk.advance(5.1)
    assert not board.is_open("a")  # window over: the next caller probes
    assert board.allow("a") and not board.allow("a")
    board.record_success("a")
    assert not board.is_open("a") and board.allow("a")


# ------------------------------------------------------------------ deadline


def test_deadline_scope_sets_and_restores():
    assert current_deadline() is None
    with deadline_scope(5.0) as d:
        assert d is not None
        assert 0 < remaining_budget() <= 5.0
    assert current_deadline() is None


def test_outer_deadline_wins_over_inner_scope():
    with deadline_scope(0.5) as outer:
        with deadline_scope(60.0) as inner:
            assert inner is outer
            assert remaining_budget() <= 0.5


def test_shielded_from_deadline_clears_and_restores():
    with deadline_scope(5.0):
        with shielded_from_deadline():
            assert remaining_budget() is None
        assert remaining_budget() is not None


def test_attempt_timeout_clamps_floors_and_exhausts():
    assert attempt_timeout(10.0) == 10.0  # no ambient deadline: untouched
    clk = FakeClock()
    token = set_deadline(Deadline(clk.now + 2.0, clk))
    try:
        assert attempt_timeout(10.0) == 2.0
        assert attempt_timeout(1.0) == 1.0
        assert attempt_timeout(None) == 2.0
        clk.advance(1.999)
        assert attempt_timeout(10.0) == MIN_ATTEMPT_TIMEOUT
        clk.advance(0.002)
        with pytest.raises(BudgetExhausted):
            attempt_timeout(10.0)
    finally:
        from tpudfs.common import resilience as _r
        _r._deadline.reset(token)


def test_overloaded_message_round_trip():
    msg = overloaded_message(0.25, "cs at admission limit")
    assert retry_after_hint(msg) == 0.25
    assert retry_after_hint("Not Leader|1.2.3.4") is None
    assert retry_after_hint("Overloaded|bogus|x") is None


# -------------------------------------------------------------- load shedder


def test_load_shedder_admits_to_limit_then_sheds():
    s = LoadShedder(max_inflight=2, base_retry_after=0.1)
    assert s.try_acquire() and s.try_acquire()
    assert not s.try_acquire()
    s.release()
    assert s.try_acquire()
    c = s.counters()
    assert c["shed_total"] == 1
    assert c["shed_admitted_total"] == 3
    assert c["shed_peak_inflight"] == 2
    # Hints are jittered ±25% so shed clients don't retry in lockstep.
    assert s.retry_after() >= 0.75 * s.base_retry_after


def test_retry_after_jitter_spreads_but_stays_bounded():
    seed_retry_jitter(42)
    s = LoadShedder(max_inflight=2, base_retry_after=0.1)
    s.inflight = 2
    hints = [s.retry_after() for _ in range(200)]
    lo, hi = 0.75 * 0.15, 1.25 * 0.15  # pressure-scaled base ± 25%
    assert all(lo <= h <= hi for h in hints)
    assert len({round(h, 6) for h in hints}) > 10  # actually spread
    seed_retry_jitter(None)


def test_retry_after_from_text_finds_embedded_hint():
    assert retry_after_from_text(
        "GetFile shed by cs-a: Overloaded|0.250|limit") == 0.25
    assert retry_after_from_text(overloaded_message(0.1, "x")) == 0.1
    assert retry_after_from_text("no hint here") is None


# ------------------------------------------- metrics cardinality capping


def test_capped_by_key_top_n_plus_other_rollup():
    counts = {f"t{i:02d}": float(i) for i in range(12)}
    out = capped_by_key("qos_tenant", counts, top_n=3, suffix="_shed_total")
    # Top 3 by value export individually; the other 9 roll up.
    assert out["qos_tenant_t11_shed_total"] == 11.0
    assert out["qos_tenant_t10_shed_total"] == 10.0
    assert out["qos_tenant_t09_shed_total"] == 9.0
    assert out["qos_tenant_other_shed_total"] == float(sum(range(9)))
    assert len(out) == 4


def test_retry_budget_counters_cap_per_target_keys():
    rb = RetryBudget(ratio=0.0, burst=0.0)  # every retry denied
    for i in range(RetryBudget.EXPORT_TOP_N + 5):
        rb.acquire_retry(f"cs-{i:02d}")
    c = rb.counters()
    per_target = [k for k in c if k.startswith("retry_budget_denied_by_target")]
    # Top-N individually + one _other rollup, never unbounded.
    assert len(per_target) == RetryBudget.EXPORT_TOP_N + 1
    assert "retry_budget_denied_by_target_other_total" in c
    assert sum(c[k] for k in per_target) == RetryBudget.EXPORT_TOP_N + 5


def test_breaker_board_counters_cap_per_addr_keys():
    clk = FakeClock()
    board = BreakerBoard(failure_threshold=1, clock=clk)
    n = RetryBudget.EXPORT_TOP_N + 4
    for i in range(n):
        board.record_failure(f"10.0.0.{i}:70{i:02d}")
    c = board.counters()
    per_addr = [k for k in c if k.startswith("breaker_opens_by_addr")]
    assert len(per_addr) == RetryBudget.EXPORT_TOP_N + 1
    assert sum(c[k] for k in per_addr) == n


# ------------------------------------------- deadline over the wire (RpcServer)


async def _make_server(handlers):
    server = RpcServer()
    server.add_service("TestService", handlers)
    await server.start()
    return server


async def test_deadline_metadata_reaches_handler():
    seen = []

    async def peek(_):
        seen.append(remaining_budget())
        return {}

    server = await _make_server({"Peek": peek})
    client = RpcClient()
    try:
        with deadline_scope(5.0):
            await client.call(server.address, "TestService", "Peek", {})
        await client.call(server.address, "TestService", "Peek", {})
    finally:
        await client.close()
        await server.stop()
    # Budgeted call: the server adopted a remaining budget ≤ what we sent.
    assert seen[0] is not None and 0 < seen[0] <= 5.0
    # Unbudgeted call: no deadline leaks across requests.
    assert seen[1] is None


async def test_server_rejects_expired_budget_before_executing():
    ran = []

    async def work(_):
        ran.append(1)
        return {}

    server = await _make_server({"Work": work})
    # A well-behaved client never sends ≤0, so speak raw gRPC to prove the
    # server-side guard: metadata says the budget is already spent.
    channel = grpc.aio.insecure_channel(server.address)
    try:
        call = channel.unary_unary(
            "/TestService/Work",
            request_serializer=rpc_mod._dumps,
            response_deserializer=rpc_mod._loads,
        )
        with pytest.raises(grpc.aio.AioRpcError) as ei:
            await call({}, metadata=((DEADLINE_KEY, "0.0"),), timeout=5.0)
        assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
        assert "before" in ei.value.details()
        assert ran == []  # rejected pre-execution, not after doing the work

        # Malformed metadata is advisory: ignored, the handler runs.
        await call({}, metadata=((DEADLINE_KEY, "bogus"),), timeout=5.0)
        assert ran == [1]
    finally:
        await channel.close()
        await server.stop()


async def test_client_refuses_to_send_already_expired_work():
    async def echo(req):
        return req

    server = await _make_server({"Echo": echo})
    client = RpcClient()
    clk = FakeClock()
    token = set_deadline(Deadline(clk.now - 1.0, clk))  # already expired
    try:
        with pytest.raises(RpcError) as ei:
            await client.call(server.address, "TestService", "Echo", {})
        assert ei.value.code == grpc.StatusCode.DEADLINE_EXCEEDED
    finally:
        from tpudfs.common import resilience as _r
        _r._deadline.reset(token)
        await client.close()
        await server.stop()


async def test_blockport_rejects_expired_budget():
    from tpudfs.common.blocknet import BlockPortServer

    ran = []

    async def handler(req):
        ran.append(1)
        return {"ok": True}

    bp = BlockPortServer({"Ping": handler})
    await bp.start()
    import asyncio
    import msgpack

    reader, writer = await asyncio.open_connection("127.0.0.1", bp.port)
    try:
        # Wire format (little-endian): u32 header_len | msgpack | u64 plen.
        header = msgpack.packb({"m": "Ping", "_db": 0.0})
        writer.write(len(header).to_bytes(4, "little") + header
                     + (0).to_bytes(8, "little"))
        await writer.drain()
        hlen = int.from_bytes(await reader.readexactly(4), "little")
        resp = msgpack.unpackb(await reader.readexactly(hlen))
        await reader.readexactly(8)  # payload length frame
        assert resp["ok"] is False
        assert resp["code"] == "DEADLINE_EXCEEDED"
        assert ran == []
    finally:
        writer.close()
        await bp.stop()


def test_admission_controlled_decorator_sheds_and_releases():
    import asyncio

    class Svc:
        def __init__(self):
            self.shedder = LoadShedder(max_inflight=1)

        async def rpc_op(self, req):
            return {"ok": True}

    from tpudfs.common.resilience import admission_controlled
    Svc.rpc_op = admission_controlled(Svc.rpc_op)

    async def drive():
        svc = Svc()
        assert (await svc.rpc_op({}))["ok"]
        svc.shedder.inflight = 1  # a stuck request holds the only slot
        with pytest.raises(RpcError) as ei:
            await svc.rpc_op({})
        assert ei.value.code == grpc.StatusCode.RESOURCE_EXHAUSTED
        assert ei.value.retry_after is not None
        svc.shedder.release()
        assert (await svc.rpc_op({}))["ok"]  # slot freed -> admitted again

    asyncio.run(drive())


# --------------------------------------------- S3 gateway SlowDown mapping


async def test_s3_gateway_maps_shed_to_503_slowdown():
    """An OverloadedError escaping the op maps to S3's throttling contract
    (503 SlowDown) at the HTTP layer — real S3 clients back off and retry
    on SlowDown, while a 500 InternalError makes them give up."""
    from types import SimpleNamespace

    from tpudfs.client.client import OverloadedError
    from tpudfs.s3.server import Gateway

    gw = Gateway(object(), auth_enabled=False)

    async def shed(_req):
        raise OverloadedError("shed by cs-a: Overloaded|0.100|limit")

    gw.handle = shed

    class FakeHttpRequest:
        method = "GET"
        path = "/bucket/key"
        rel_url = SimpleNamespace(query={})
        headers = {}
        secure = False
        remote = "127.0.0.1"

        async def read(self):
            return b""

    resp = await gw._dispatch_http(FakeHttpRequest())
    assert resp.status == 503
    assert b"SlowDown" in resp.body
