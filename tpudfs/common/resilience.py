"""End-to-end resilience primitives: deadlines, retry budgets, breakers, shedding.

The chaos tiers prove this DFS survives kills and partitions; this module
defends against the *other* production failure mode — overload and metastable
retry storms. Four cooperating mechanisms, each usable on its own:

- **Deadline propagation.** The client's per-op budget lives in a contextvar
  (same pattern as the request id in :mod:`tpudfs.common.telemetry`) and rides
  outgoing RPC metadata as *remaining seconds* (relative, so clock skew between
  hosts is irrelevant — the same choice gRPC makes with ``grpc-timeout``).
  ``RpcClient.call`` clamps each attempt's timeout to the remaining budget and
  refuses to send already-expired work; ``RpcServer`` adopts the budget and
  rejects expired requests with DEADLINE_EXCEEDED *before* running the handler,
  so a queue of doomed work drains instead of executing.

- **Retry budgets.** A token bucket per target address: every first attempt
  deposits ``ratio`` tokens, every retry/hedge withdraws one. Retry volume is
  thereby capped at ``ratio`` × first-try volume (plus a fixed burst), which is
  what breaks the metastable feedback loop where retries against a slow server
  become the majority of its load.

- **Circuit breakers.** Per-address closed → open → half-open state machines.
  ``failure_threshold`` consecutive failures open the breaker; after
  ``reset_timeout`` (doubling per consecutive open, capped) exactly one
  half-open probe is admitted, and its outcome closes or re-opens the breaker.

- **Load shedding.** An inflight-bounded admission controller for server
  handlers. Over the limit, requests fail fast with RESOURCE_EXHAUSTED carrying
  a machine-readable retry-after hint (``Overloaded|<seconds>|...``, same
  message-prefix convention as ``Not Leader|``), mapped to S3 503 SlowDown at
  the gateway.

- **Tenant QoS.** A tenant identity contextvar (propagated like the deadline
  budget: ``x-tenant`` gRPC metadata / ``_tn`` blockport header) plus a
  tenant-aware admission controller (:class:`QosShedder`): per-tenant
  time-refilled token buckets and a deficit-round-robin weighted-fair queue
  over per-tenant FIFOs. Overload degrades per tenant in order — queue
  (bounded depth, deadline-expired waiters evicted), then rate-limit with a
  per-tenant retry-after, then shed with the same ``Overloaded|`` message —
  so one flooding tenant saturates its own queue while everyone else keeps
  their fair share. Disabled (the default), admission is the flat
  :class:`LoadShedder`, bit-for-bit.

Everything here is clock-injectable so unit tests never sleep.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import os
import time
from collections import deque
from collections.abc import Callable, Iterator, Mapping
from typing import Any

#: Metadata key carrying the remaining deadline budget in seconds (relative).
DEADLINE_KEY = "x-deadline-budget"

#: Metadata key carrying the tenant identity on the gRPC plane; the blockport
#: twin is the ``_tn`` header field (same split as DEADLINE_KEY / ``_db``).
TENANT_KEY = "x-tenant"

#: Blockport frame-header key for the tenant identity.
TENANT_FRAME_KEY = "_tn"

#: The implicit tenant: control-plane traffic, background maintenance
#: (re-replication, checkpoint staging GC), and clients that never configured
#: an identity. Never rate-limited — throttling the cluster's own upkeep
#: turns overload into data loss.
SYSTEM_TENANT = "system"

#: Floor for derived per-attempt timeouts: a nearly-expired budget still gets
#: a short real timeout rather than a degenerate zero that can never succeed.
MIN_ATTEMPT_TIMEOUT = 0.01


class Deadline:
    """An absolute give-up point on the monotonic clock."""

    __slots__ = ("expires_at", "_clock")

    def __init__(self, expires_at: float, clock: Callable[[], float] = time.monotonic):
        self.expires_at = expires_at
        self._clock = clock

    @classmethod
    def after(cls, budget: float,
              clock: Callable[[], float] = time.monotonic) -> "Deadline":
        return cls(clock() + budget, clock)

    def remaining(self) -> float:
        return self.expires_at - self._clock()

    @property
    def expired(self) -> bool:
        return self.remaining() <= 0


_deadline: contextvars.ContextVar[Deadline | None] = contextvars.ContextVar(
    "tpudfs_deadline", default=None
)


def current_deadline() -> Deadline | None:
    return _deadline.get()


def set_deadline(d: Deadline | None) -> contextvars.Token:
    return _deadline.set(d)


def remaining_budget() -> float | None:
    """Seconds left on the ambient deadline, or None when no deadline is set."""
    d = _deadline.get()
    return None if d is None else d.remaining()


@contextlib.contextmanager
def deadline_scope(budget: float | None) -> Iterator[Deadline | None]:
    """Establish a per-op deadline unless one is already active.

    An outer deadline always wins — a caller that budgeted the whole operation
    must not have its clamp loosened by an inner hop's more generous default.
    """
    if budget is None or _deadline.get() is not None:
        yield _deadline.get()
        return
    d = Deadline.after(budget)
    token = _deadline.set(d)
    try:
        yield d
    finally:
        _deadline.reset(token)


@contextlib.contextmanager
def shielded_from_deadline() -> Iterator[None]:
    """Clear the ambient deadline for background work.

    Tasks spawned from a request context (silent re-replication, shared
    metadata-batch drainers) inherit the spawning request's contextvars; their
    RPCs must not die when *that* caller's budget runs out.
    """
    token = _deadline.set(None)
    try:
        yield
    finally:
        _deadline.reset(token)


def attempt_timeout(timeout: float | None) -> float | None:
    """Clamp a per-attempt timeout to the ambient deadline's remaining budget.

    Raises :class:`BudgetExhausted` when the budget is already spent, so the
    caller fails fast instead of sending doomed work.
    """
    rem = remaining_budget()
    if rem is None:
        return timeout
    if rem <= 0:
        raise BudgetExhausted("deadline budget exhausted")
    rem = max(rem, MIN_ATTEMPT_TIMEOUT)
    return rem if timeout is None else min(timeout, rem)


class BudgetExhausted(Exception):
    """The ambient deadline expired before the next attempt could be sent."""


# ---------------------------------------------------------------------------
# Tenant identity
# ---------------------------------------------------------------------------

_tenant: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "tpudfs_tenant", default=None
)


def raw_tenant() -> str | None:
    """The ambient tenant, or None when none was ever established."""
    return _tenant.get()


def current_tenant() -> str:
    """The tenant this work is accounted to; :data:`SYSTEM_TENANT` when no
    identity was established anywhere up the chain."""
    return _tenant.get() or SYSTEM_TENANT


def set_tenant(tenant: str | None) -> contextvars.Token:
    return _tenant.set(tenant)


@contextlib.contextmanager
def tenant_scope(tenant: str | None) -> Iterator[str]:
    """Attribute the enclosed work to ``tenant`` unless an identity is
    already ambient.

    Outer wins, same rule as :func:`deadline_scope`: the S3 gateway sets the
    auth principal per request, and the DFS client library (which may carry
    its own configured identity) runs *inside* that request — the principal
    must not be overwritten by the library's default."""
    if tenant is None or _tenant.get() is not None:
        yield current_tenant()
        return
    token = _tenant.set(tenant)
    try:
        yield tenant
    finally:
        _tenant.reset(token)


@contextlib.contextmanager
def as_system_tenant() -> Iterator[None]:
    """FORCE the system tenant for background/maintenance work.

    The counterpart of :func:`shielded_from_deadline`: a GC or healer task
    spawned from a request context inherits the requester's tenant, and its
    cleanup must not be queued/throttled against that tenant's quota — the
    overload that produced the garbage would then starve its own cleanup."""
    token = _tenant.set(SYSTEM_TENANT)
    try:
        yield
    finally:
        _tenant.reset(token)


# ---------------------------------------------------------------------------
# Retry-after jitter + metrics-cardinality helpers
# ---------------------------------------------------------------------------

class SplitMix64:
    """Deterministic jitter PRNG, algorithm-identical to the native engine's
    ``SplitMix64`` (native/dataplane.cc): same state advance, same finalizer,
    same 53-bit double in [0, 1). Seeding Python and the engine with one seed
    therefore yields the SAME jitter stream — the QoS parity tests compare
    ``retry_after`` values across engines draw-for-draw."""

    __slots__ = ("_state",)

    MASK64 = (1 << 64) - 1

    def __init__(self, seed: int | None = None):
        self.seed(seed)

    def seed(self, seed: int | None = None) -> None:
        if seed is None:
            seed = int.from_bytes(os.urandom(8), "little")
        self._state = seed & self.MASK64

    def random(self) -> float:
        self._state = (self._state + 0x9E3779B97F4A7C15) & self.MASK64
        z = self._state
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & self.MASK64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & self.MASK64
        z ^= z >> 31
        return (z >> 11) * 2.0 ** -53


#: Module RNG for retry-after jitter; tests seed it for determinism.
_jitter_rng = SplitMix64()

#: Last explicit seed handed to :func:`seed_retry_jitter` (0 = entropy
#: seeded). Pushed to the native engine with the QoS config so both planes
#: draw the same jitter stream under a seeded chaos/parity run.
_jitter_seed = 0


def seed_retry_jitter(seed: int | None) -> None:
    """Re-seed the retry-after jitter RNG (tests/chaos determinism)."""
    global _jitter_seed
    if seed is None:
        _jitter_rng.seed(None)
        _jitter_seed = 0
        return
    s = seed if isinstance(seed, int) else hash(seed)
    _jitter_rng.seed(s)
    _jitter_seed = s & SplitMix64.MASK64


def jitter_seed() -> int:
    """The seed behind the jitter stream (0 when entropy-seeded)."""
    return _jitter_seed


def jittered(seconds: float, spread: float = 0.25) -> float:
    """``seconds`` ±``spread`` (uniform), floored at 0.

    Every retry-after hint a server hands out is jittered: a shed wave
    answered with identical hints makes every client retry in lockstep,
    re-creating the spike the shed was defending against."""
    return max(0.0, seconds * (1.0 + spread * (2.0 * _jitter_rng.random() - 1.0)))


def metric_key(raw: str) -> str:
    """Sanitize an arbitrary tenant/address into a metric-name fragment."""
    return "".join(c if c.isalnum() or c == "_" else "_" for c in raw) or "_"


def capped_by_key(prefix: str, counts: Mapping[str, float], *,
                  top_n: int = 8, suffix: str = "_total") -> dict[str, float]:
    """Per-key counters capped for the metrics page: the ``top_n`` largest
    keys export individually, everything else rolls up into
    ``{prefix}_other{suffix}`` — a many-tenant (or many-target) run must not
    bloat /metrics without bound."""
    out: dict[str, float] = {}
    ranked = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
    other = 0.0
    for i, (key, value) in enumerate(ranked):
        if i < top_n:
            out[f"{prefix}_{metric_key(key)}{suffix}"] = float(value)
        else:
            other += value
    if other:
        out[f"{prefix}_other{suffix}"] = other
    return out


# ---------------------------------------------------------------------------
# Retry budgets
# ---------------------------------------------------------------------------


class TokenBucket:
    """Deposit-per-first-try retry throttle (The Tail at Scale / gRPC style).

    First attempts deposit ``ratio`` tokens (capped at ``burst``); each retry
    withdraws one whole token. Long-run retry volume is therefore at most
    ``ratio`` × first-try volume + ``burst``.
    """

    __slots__ = ("ratio", "burst", "tokens")

    def __init__(self, ratio: float = 0.5, burst: float = 10.0):
        self.ratio = ratio
        self.burst = burst
        self.tokens = burst  # start full: isolated failures always get retries

    def deposit(self) -> None:
        self.tokens = min(self.burst, self.tokens + self.ratio)

    def try_spend(self) -> bool:
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False


class RetryBudget:
    """Per-target token buckets with aggregate counters.

    ``first_tries``/``retries``/``denied`` feed both the overload chaos
    assertions (retry amplification ≤ 2×) and the ops /metrics endpoint.
    """

    #: Per-target keys exported through /metrics (top-N by denial count +
    #: ``_other`` rollup) — see :func:`capped_by_key`.
    EXPORT_TOP_N = 8

    def __init__(self, ratio: float = 0.5, burst: float = 10.0):
        self.ratio = ratio
        self.burst = burst
        self._buckets: dict[str, TokenBucket] = {}
        self.first_tries = 0
        self.retries = 0
        self.denied = 0
        self._denied_by_key: dict[str, int] = {}

    def _bucket(self, key: str) -> TokenBucket:
        b = self._buckets.get(key)
        if b is None:
            b = self._buckets[key] = TokenBucket(self.ratio, self.burst)
        return b

    def on_first_attempt(self, key: str) -> None:
        self.first_tries += 1
        self._bucket(key).deposit()

    def acquire_retry(self, key: str) -> bool:
        if self._bucket(key).try_spend():
            self.retries += 1
            return True
        self.denied += 1
        self._denied_by_key[key] = self._denied_by_key.get(key, 0) + 1
        return False

    def counters(self) -> dict[str, float]:
        return {
            "retry_budget_first_tries_total": float(self.first_tries),
            "retry_budget_retries_total": float(self.retries),
            "retry_budget_denied_total": float(self.denied),
            **capped_by_key("retry_budget_denied_by_target",
                            self._denied_by_key, top_n=self.EXPORT_TOP_N),
        }


# ---------------------------------------------------------------------------
# Circuit breakers
# ---------------------------------------------------------------------------

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"


class CircuitBreaker:
    """Closed → open → half-open → closed, with exponential open windows.

    ``allow()`` answers "may I send traffic here right now?": always in
    CLOSED, never while the open window runs, and exactly once per window in
    HALF_OPEN (the probe). ``record_success``/``record_failure`` resolve the
    probe and drive the state machine.
    """

    __slots__ = ("failure_threshold", "reset_timeout", "max_reset", "_clock",
                 "state", "_failures", "_open_until", "_consecutive_opens",
                 "_probe_inflight")

    def __init__(self, failure_threshold: int = 3, reset_timeout: float = 5.0,
                 max_reset: float = 30.0,
                 clock: Callable[[], float] = time.monotonic):
        self.failure_threshold = failure_threshold
        self.reset_timeout = reset_timeout
        self.max_reset = max_reset
        self._clock = clock
        self.state = CLOSED
        self._failures = 0
        self._open_until = 0.0
        self._consecutive_opens = 0
        self._probe_inflight = False

    def allow(self) -> bool:
        if self.state == CLOSED:
            return True
        if self.state == OPEN:
            if self._clock() < self._open_until:
                return False
            self.state = HALF_OPEN
            self._probe_inflight = True
            return True
        # HALF_OPEN: one probe at a time
        if self._probe_inflight:
            return False
        self._probe_inflight = True
        return True

    def record_success(self) -> None:
        self.state = CLOSED
        self._failures = 0
        self._consecutive_opens = 0
        self._probe_inflight = False

    def record_failure(self) -> None:
        self._probe_inflight = False
        if self.state == HALF_OPEN:
            self._trip()
            return
        self._failures += 1
        if self._failures >= self.failure_threshold:
            self._trip()

    def _trip(self) -> None:
        self.state = OPEN
        self._failures = 0
        self._consecutive_opens += 1
        window = min(self.max_reset,
                     self.reset_timeout * (2 ** (self._consecutive_opens - 1)))
        self._open_until = self._clock() + window


class BreakerBoard:
    """Per-address circuit breakers sharing one configuration."""

    def __init__(self, failure_threshold: int = 3, reset_timeout: float = 5.0,
                 max_reset: float = 30.0,
                 clock: Callable[[], float] = time.monotonic):
        self._cfg = (failure_threshold, reset_timeout, max_reset)
        self._clock = clock
        self._breakers: dict[str, CircuitBreaker] = {}
        self.opens_total = 0
        self.short_circuits_total = 0
        self._opens_by_addr: dict[str, int] = {}

    def get(self, addr: str) -> CircuitBreaker:
        br = self._breakers.get(addr)
        if br is None:
            ft, rt, mr = self._cfg
            br = self._breakers[addr] = CircuitBreaker(ft, rt, mr, self._clock)
        return br

    def allow(self, addr: str) -> bool:
        ok = self.get(addr).allow()
        if not ok:
            self.short_circuits_total += 1
        return ok

    def is_open(self, addr: str) -> bool:
        """True while ``addr``'s open window runs. Consumes no probe, so a
        caller may ask it to leave a peer alone that ``allow`` would turn
        away anyhow; once the window is over the next ``allow`` probes."""
        br = self._breakers.get(addr)
        return br is not None and br.state == OPEN \
            and self._clock() < br._open_until

    def record_success(self, addr: str) -> None:
        self.get(addr).record_success()

    def record_failure(self, addr: str) -> None:
        br = self.get(addr)
        was_open = br.state == OPEN
        br.record_failure()
        if br.state == OPEN and not was_open:
            self.opens_total += 1
            self._opens_by_addr[addr] = self._opens_by_addr.get(addr, 0) + 1

    def healthy_first(self, addrs: list[str]) -> list[str]:
        """Stable partition: addresses with non-open breakers first.

        Ordering only — an all-open list is returned intact, so availability
        never depends on breaker state (the breaker biases, the retry loop
        decides).
        """
        good = [a for a in addrs if self.get(a).state != OPEN]
        bad = [a for a in addrs if self.get(a).state == OPEN]
        return good + bad

    def counters(self) -> dict[str, float]:
        return {
            "breaker_open_count": float(
                sum(1 for b in self._breakers.values() if b.state == OPEN)),
            "breaker_opens_total": float(self.opens_total),
            "breaker_short_circuits_total": float(self.short_circuits_total),
            **capped_by_key("breaker_opens_by_addr", self._opens_by_addr,
                            top_n=RetryBudget.EXPORT_TOP_N),
        }


# ---------------------------------------------------------------------------
# Load shedding
# ---------------------------------------------------------------------------

#: Message prefix for RESOURCE_EXHAUSTED errors carrying a retry-after hint,
#: mirroring the ``Not Leader|<hint>`` convention from the reference.
OVERLOADED_PREFIX = "Overloaded|"


def overloaded_message(retry_after: float, detail: str = "") -> str:
    return f"{OVERLOADED_PREFIX}{retry_after:.3f}|{detail}"


def retry_after_hint(message: str) -> float | None:
    """Parse the retry-after seconds out of an ``Overloaded|…`` message."""
    if not message.startswith(OVERLOADED_PREFIX):
        return None
    parts = message.split("|", 2)
    try:
        return float(parts[1])
    except (IndexError, ValueError):
        return None


def retry_after_from_text(message: str) -> float | None:
    """Like :func:`retry_after_hint` but finds ``Overloaded|…`` anywhere in
    the text — client-side error messages wrap the server hint in context
    (e.g. ``"GetFile shed by target: Overloaded|0.100|…"``), and the S3
    gateway needs the seconds back out for its ``Retry-After`` header."""
    idx = message.find(OVERLOADED_PREFIX)
    if idx < 0:
        return None
    return retry_after_hint(message[idx:])


class LoadShedder:
    """Inflight-bounded admission control for server handlers.

    Not a queue: over the limit we fail *fast* — queueing doomed work is
    exactly the behavior that turns a slow server into a dead one. The
    retry-after hint scales with pressure so shed clients spread their
    comebacks instead of thundering back in lockstep.
    """

    def __init__(self, max_inflight: int = 64, base_retry_after: float = 0.1):
        self.max_inflight = max_inflight
        self.base_retry_after = base_retry_after
        self.inflight = 0
        self.admitted_total = 0
        self.shed_total = 0
        self.peak_inflight = 0

    def try_acquire(self) -> bool:
        if self.inflight >= self.max_inflight:
            self.shed_total += 1
            return False
        self.inflight += 1
        self.admitted_total += 1
        self.peak_inflight = max(self.peak_inflight, self.inflight)
        return True

    def release(self) -> None:
        self.inflight -= 1

    def retry_after(self) -> float:
        over = max(0, self.inflight - self.max_inflight + 1)
        hint = self.base_retry_after * (1.0 + over / max(1, self.max_inflight))
        # ±25% jitter so a shed wave's clients spread their comebacks
        # instead of thundering back in lockstep at hint expiry.
        return jittered(hint)

    def counters(self) -> dict[str, float]:
        return {
            "shed_inflight": float(self.inflight),
            "shed_peak_inflight": float(self.peak_inflight),
            "shed_admitted_total": float(self.admitted_total),
            "shed_total": float(self.shed_total),
        }


def admission_controlled(fn: Any) -> Any:
    """Decorator for service RPC methods: admit through ``self.shedder``.

    Services opt in per-method (heartbeats, liveness and raft traffic stay
    exempt — shedding those turns overload into a false partition). The
    wrapped method keeps its ``(self, request)`` shape so the rpc-contract
    lint still resolves handler signatures.

    Two admission planes share this decorator: the flat :class:`LoadShedder`
    (``try_acquire``/``release``, the default — behavior unchanged) and the
    tenant-aware :class:`QosShedder`, detected by its async ``acquire``
    method, which may *queue* the request in the weighted-fair queue before
    admitting or rejecting it.
    """

    async def wrapped(self: Any, request: Any) -> Any:
        shedder: LoadShedder | None = getattr(self, "shedder", None)
        if shedder is None:
            return await fn(self, request)
        acquire = getattr(shedder, "acquire", None)
        if acquire is not None:
            # Tenant-aware plane: per-tenant fair queueing + rate limits.
            tenant = current_tenant()
            t_queued = time.monotonic()
            try:
                await acquire(tenant)
            except QosRejected as e:
                # Local import: rpc.py imports this module for deadline
                # clamping, so the top-level dependency must stay
                # rpc -> resilience only.
                from tpudfs.common.rpc import RpcError
                raise RpcError.resource_exhausted(
                    f"{type(self).__name__} {e.detail} (tenant={tenant})",
                    retry_after=e.retry_after,
                ) from None
            t0 = time.monotonic()
            waited = getattr(self, "admission_waited", None)
            if waited is not None:
                waited(fn.__name__, t0 - t_queued)
            try:
                return await fn(self, request)
            finally:
                shedder.release(tenant, time.monotonic() - t0)
        if not shedder.try_acquire():
            from tpudfs.common.rpc import RpcError
            raise RpcError.resource_exhausted(
                f"{type(self).__name__} at admission limit "
                f"({shedder.max_inflight} inflight)",
                retry_after=shedder.retry_after(),
            )
        try:
            return await fn(self, request)
        finally:
            shedder.release()

    wrapped.__name__ = fn.__name__
    wrapped.__qualname__ = fn.__qualname__
    wrapped.__doc__ = fn.__doc__
    wrapped.__wrapped__ = fn
    return wrapped


# ---------------------------------------------------------------------------
# Tenant QoS: rate buckets, weighted-fair queueing, tenant-aware admission
# ---------------------------------------------------------------------------

#: QoS plane defaults shared value-for-value with the native engine
#: (native/dataplane.cc ``kQosDrrQuantum``/``kQosQueueDepthDefault``/
#: ``kQosMinBurst``; TPL041 pairs them): the DRR per-visit credit, the
#: per-tenant admission-queue bound, and the rate-bucket burst floor.
QOS_DRR_QUANTUM = 1
QOS_QUEUE_DEPTH_DEFAULT = 32
QOS_MIN_BURST = 1


class QosFailpoints:
    """Env-selected fault injection for the QoS admission plane
    (``TPUDFS_QOS_FAILPOINT``, comma-separated directives) — honored by BOTH
    the Python shedder and the native engine, so the chaos tiers can drive
    either plane through the same degraded regimes:

    - ``freeze_refill``: rate buckets stop refilling (their clock freezes at
      construction). Limited tenants drain their burst and stay drained —
      and retry-after hints become a pure function of the token deficit,
      which is what makes cross-engine parity assertable.
    - ``delay_admit=<seconds>``: every admitted request stalls before the
      handler runs (a degraded disk/NIC *behind* admission — queue pressure
      builds while admission itself stays honest).
    - ``force_shed=<n>``: the next ``n`` acquires are refused unconditionally
      with detail ``"failpoint forced shed"`` (client retry-path drills).
    """

    __slots__ = ("freeze_refill", "delay_admit", "force_shed")

    def __init__(self, freeze_refill: bool = False, delay_admit: float = 0.0,
                 force_shed: int = 0):
        self.freeze_refill = freeze_refill
        self.delay_admit = delay_admit
        self.force_shed = force_shed

    @classmethod
    def from_env(cls, raw: str | None = None) -> "QosFailpoints":
        if raw is None:
            raw = os.environ.get("TPUDFS_QOS_FAILPOINT", "")
        fp = cls()
        for part in raw.split(","):
            name, _, value = part.strip().partition("=")
            if name == "freeze_refill":
                fp.freeze_refill = True
            elif name == "delay_admit":
                try:
                    fp.delay_admit = float(value or 0.0)
                except ValueError:
                    pass
            elif name == "force_shed":
                try:
                    fp.force_shed = int(value or 0)
                except ValueError:
                    pass
        return fp

    def any(self) -> bool:
        return bool(self.freeze_refill or self.delay_admit > 0
                    or self.force_shed > 0)


class RateBucket:
    """Time-refilled token bucket for per-tenant request-rate limits.

    Distinct from :class:`TokenBucket` (the *retry* throttle, refilled by
    first attempts): this one refills with wall time at ``rate`` tokens/s up
    to ``burst``. Refill is monotone — a clock that stalls or steps backwards
    never drains tokens — which is what makes retry-after hints derived from
    it trustworthy."""

    __slots__ = ("rate", "burst", "tokens", "_last", "_clock")

    def __init__(self, rate: float, burst: float,
                 clock: Callable[[], float] = time.monotonic):
        if rate <= 0:
            raise ValueError("rate must be > 0 (omit the bucket for "
                             "unlimited tenants)")
        self.rate = float(rate)
        self.burst = max(float(burst), float(QOS_MIN_BURST))
        self.tokens = self.burst
        self._last = clock()
        self._clock = clock

    def _refill(self) -> None:
        now = self._clock()
        if now > self._last:
            self.tokens = min(self.burst,
                              self.tokens + (now - self._last) * self.rate)
            self._last = now
        # now <= _last: clock stall/regression — tokens unchanged, and
        # _last keeps its high-water mark so the lost interval is never
        # double-counted when the clock recovers.

    def try_spend(self, n: float = 1.0) -> bool:
        self._refill()
        if self.tokens >= n:
            self.tokens -= n
            return True
        return False

    def retry_after(self, n: float = 1.0) -> float:
        """Seconds until ``n`` tokens will be available (0 if they are)."""
        self._refill()
        if self.tokens >= n:
            return 0.0
        return (n - self.tokens) / self.rate


class _Waiter:
    """One queued admission request in the weighted-fair queue."""

    __slots__ = ("future", "tenant", "enqueued_at", "deadline", "cost")

    def __init__(self, future: Any, tenant: str, enqueued_at: float,
                 deadline: Deadline | None, cost: float = 1.0):
        self.future = future
        self.tenant = tenant
        self.enqueued_at = enqueued_at
        self.deadline = deadline
        self.cost = cost


class DeficitRoundRobin:
    """Deficit round-robin over per-tenant FIFOs (Shreedhar & Varghese).

    Each tenant owns a FIFO; a round-robin ring visits tenants with queued
    items, crediting ``quantum × weight`` per visit and serving while the
    deficit covers the head item's cost. A tenant with weight 2 therefore
    drains twice as fast as one with weight 1, and an arbitrarily deep
    queue buys a tenant *zero* extra service — exactly the noisy-neighbor
    property a flat FIFO lacks."""

    def __init__(self, quantum: float = float(QOS_DRR_QUANTUM),
                 default_weight: float = 1.0):
        self.quantum = quantum
        self.default_weight = default_weight
        self.weights: dict[str, float] = {}
        self._queues: dict[str, deque] = {}
        self._ring: deque[str] = deque()
        self._deficit: dict[str, float] = {}

    def weight(self, tenant: str) -> float:
        return max(self.weights.get(tenant, self.default_weight), 1e-6)

    def __len__(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def depth(self, tenant: str) -> int:
        q = self._queues.get(tenant)
        return len(q) if q is not None else 0

    def tenants(self) -> list[str]:
        return list(self._ring)

    def push(self, tenant: str, item: Any, cost: float = 1.0) -> None:
        q = self._queues.get(tenant)
        if q is None:
            q = self._queues[tenant] = deque()
            self._ring.append(tenant)
            self._deficit.setdefault(tenant, 0.0)
        q.append((cost, item))

    def push_front(self, tenant: str, item: Any, cost: float = 1.0) -> None:
        """Return an item to the head of its FIFO (dispatch backed out —
        e.g. the tenant's rate bucket was empty at dispatch time)."""
        q = self._queues.get(tenant)
        if q is None:
            q = self._queues[tenant] = deque()
            self._ring.append(tenant)
            self._deficit.setdefault(tenant, 0.0)
        q.appendleft((cost, item))

    def _retire(self, tenant: str) -> None:
        if not self._queues.get(tenant):
            self._queues.pop(tenant, None)
            self._deficit.pop(tenant, None)
            try:
                self._ring.remove(tenant)
            except ValueError:
                pass

    def pop(self, skip: set[str] | None = None) -> tuple[str, Any] | None:
        """Next (tenant, item) by DRR order; None when empty or every
        queued tenant is in ``skip`` (rate-limited this dispatch round)."""
        if not self._ring:
            return None
        # Termination: every eligible visit grows that tenant's deficit by
        # quantum*weight > 0, so within bounded cycles some head is served.
        visits = 0
        max_visits = len(self._ring) * (
            2 + int(1.0 / min(self.weight(t) for t in self._ring)))
        while self._ring and visits <= max_visits:
            visits += 1
            tenant = self._ring[0]
            if skip and tenant in skip:
                if all(t in skip for t in self._ring):
                    return None
                self._ring.rotate(-1)
                continue
            q = self._queues[tenant]
            cost = q[0][0]
            if self._deficit[tenant] >= cost:
                _, item = q.popleft()
                self._deficit[tenant] -= cost
                if not q:
                    # A drained tenant forfeits its leftover deficit: credit
                    # must not accumulate while idle (classic DRR rule).
                    self._deficit[tenant] = 0.0
                    self._retire(tenant)
                return tenant, item
            self._deficit[tenant] += self.quantum * self.weight(tenant)
            self._ring.rotate(-1)
        return None

    def evict(self, pred: Callable[[Any], bool]) -> list[Any]:
        """Remove and return every queued item matching ``pred`` (expired
        waiters); tenants left empty retire from the ring."""
        evicted: list[Any] = []
        for tenant in list(self._queues):
            q = self._queues[tenant]
            kept: deque = deque()
            for cost, item in q:
                if pred(item):
                    evicted.append(item)
                else:
                    kept.append((cost, item))
            self._queues[tenant] = kept
            self._retire(tenant)
        return evicted


class QosRejected(Exception):
    """Admission refused by the QoS plane — carries the per-tenant hint."""

    def __init__(self, detail: str, retry_after: float, tenant: str):
        super().__init__(detail)
        self.detail = detail
        self.retry_after = retry_after
        self.tenant = tenant


#: p99 is computed over a bounded ring of recent handler latencies, so a
#: quiet tenant's ancient spike ages out instead of pinning the gauge.
_LATENCY_RING = 256


class QosShedder:
    """Tenant-aware admission: weighted-fair queue + per-tenant rate limits.

    Drop-in replacement for :class:`LoadShedder` behind
    :func:`admission_controlled` (detected by the async ``acquire``).
    Degradation order per tenant when the inflight budget is full or the
    tenant is over its rate:

    1. **Queue** — the request parks in a deficit-round-robin weighted-fair
       queue (bounded per-tenant depth; deadline-expired waiters evicted).
    2. **Rate-limit** — a waiter that times out (its ambient deadline or
       ``max_queue_wait``) is refused with that *tenant's* retry-after, from
       its refill schedule.
    3. **Shed** — a tenant whose queue slice is full fails fast with the
       same ``Overloaded|`` message the flat shedder uses.

    The ``system`` tenant (control plane, background maintenance, clients
    with no configured identity) is never rate-limited and carries a higher
    default weight, so enabling QoS cluster-wide changes nothing for
    untenanted traffic until real tenants start competing.
    """

    def __init__(self, max_inflight: int = 64, base_retry_after: float = 0.1,
                 *, weights: Mapping[str, float] | None = None,
                 default_weight: float = 1.0, rate: float = 0.0,
                 burst: float | None = None,
                 queue_depth: int = QOS_QUEUE_DEPTH_DEFAULT,
                 max_queue_wait: float = 0.25,
                 clock: Callable[[], float] = time.monotonic,
                 failpoints: "QosFailpoints | None" = None):
        self.max_inflight = max_inflight
        self.base_retry_after = base_retry_after
        self.inflight = 0
        self.admitted_total = 0
        self.shed_total = 0
        self.peak_inflight = 0
        self.queue = DeficitRoundRobin(default_weight=default_weight)
        self.queue.weights = dict(weights or {})
        # System outweighs any single default-weight tenant unless the
        # operator explicitly pinned it.
        self.queue.weights.setdefault(SYSTEM_TENANT, max(4.0, default_weight))
        self.rate = float(rate)
        self.burst = float(burst) if burst else max(2.0 * self.rate, 1.0)
        self.queue_depth = queue_depth
        self.max_queue_wait = max_queue_wait
        self._clock = clock
        self.failpoints = failpoints
        # freeze_refill failpoint: buckets see a clock pinned at the
        # shedder's construction instant, so they never refill — the
        # admission ladder past the burst becomes deterministic.
        self._bucket_clock = clock
        if failpoints is not None and failpoints.freeze_refill:
            frozen = clock()
            self._bucket_clock = lambda: frozen
        self._buckets: dict[str, RateBucket] = {}
        self._admitted_by_tenant: dict[str, int] = {}
        self._shed_by_tenant: dict[str, int] = {}
        self._queued_by_tenant: dict[str, int] = {}
        self._rate_limited_by_tenant: dict[str, int] = {}
        self._latency_by_tenant: dict[str, deque] = {}
        self.queued_total = 0
        self.rate_limited_total = 0
        self.evicted_total = 0
        self._kick_scheduled = False

    # -- per-tenant plumbing ------------------------------------------------

    def _bucket(self, tenant: str) -> RateBucket | None:
        if self.rate <= 0 or tenant == SYSTEM_TENANT:
            return None
        b = self._buckets.get(tenant)
        if b is None:
            b = self._buckets[tenant] = RateBucket(
                self.rate, self.burst, self._bucket_clock)
        return b

    def retry_after_for(self, tenant: str) -> float:
        """Per-tenant retry-after: the tenant's refill schedule when it has
        one, else the pressure-scaled global hint."""
        b = self._bucket(tenant)
        if b is not None:
            hinted = b.retry_after()
            if hinted > 0:
                return jittered(max(hinted, self.base_retry_after))
        over = max(0, self.inflight - self.max_inflight + 1) + len(self.queue)
        hint = self.base_retry_after * (1.0 + over / max(1, self.max_inflight))
        return jittered(hint)

    def _admit(self, tenant: str) -> None:
        self.inflight += 1
        self.admitted_total += 1
        self.peak_inflight = max(self.peak_inflight, self.inflight)
        self._admitted_by_tenant[tenant] = (
            self._admitted_by_tenant.get(tenant, 0) + 1)

    def _count_shed(self, tenant: str) -> None:
        self.shed_total += 1
        self._shed_by_tenant[tenant] = self._shed_by_tenant.get(tenant, 0) + 1

    def _evict_expired(self) -> int:
        """Drop queued waiters whose ambient deadline already expired —
        admitting doomed work just burns an inflight slot."""
        def expired(w: _Waiter) -> bool:
            if w.future.done():
                return True  # timed out / cancelled; just reap the slot
            return w.deadline is not None and w.deadline.expired

        evicted = self.queue.evict(expired)
        n = 0
        for w in evicted:
            if w.future.done():
                continue
            n += 1
            self._count_shed(w.tenant)
            w.future.set_exception(QosRejected(
                "deadline expired in admission queue",
                retry_after=self.retry_after_for(w.tenant), tenant=w.tenant))
        self.evicted_total += n
        return len(evicted)

    # -- the acquire/release pair used by admission_controlled --------------

    async def acquire(self, tenant: str) -> None:
        """Admit, queue, or refuse one request for ``tenant``.

        Raises :class:`QosRejected` (rate-limited or shed); returns when
        admitted. Callers must pair with :meth:`release`.
        """
        fp = self.failpoints
        if fp is not None and fp.force_shed > 0:
            fp.force_shed -= 1
            self._count_shed(tenant)
            raise QosRejected(
                "failpoint forced shed",
                retry_after=self.retry_after_for(tenant), tenant=tenant)
        bucket = self._bucket(tenant)
        if (self.inflight < self.max_inflight and len(self.queue) == 0
                and (bucket is None or bucket.try_spend())):
            self._admit(tenant)
            if fp is not None and fp.delay_admit > 0:
                await asyncio.sleep(fp.delay_admit)
            return
        # Contended (or over-rate): degrade to the fair queue.
        if self.queue.depth(tenant) >= self.queue_depth:
            self._evict_expired()
            if self.queue.depth(tenant) >= self.queue_depth:
                self._count_shed(tenant)
                raise QosRejected(
                    "tenant queue full",
                    retry_after=self.retry_after_for(tenant), tenant=tenant)
        loop = asyncio.get_running_loop()
        waiter = _Waiter(loop.create_future(), tenant, self._clock(),
                         current_deadline())
        self.queue.push(tenant, waiter)
        self.queued_total += 1
        self._queued_by_tenant[tenant] = (
            self._queued_by_tenant.get(tenant, 0) + 1)
        self._kick()
        timeout = self.max_queue_wait
        rem = remaining_budget()
        if rem is not None:
            timeout = min(timeout, max(rem, 0.0))
        try:
            await asyncio.wait_for(waiter.future, timeout=timeout)
        except asyncio.TimeoutError:
            # Reap our queue slot now rather than waiting for a sweep.
            self.queue.evict(lambda w: w is waiter)
            self.rate_limited_total += 1
            self._rate_limited_by_tenant[tenant] = (
                self._rate_limited_by_tenant.get(tenant, 0) + 1)
            self._count_shed(tenant)
            raise QosRejected(
                "rate limited",
                retry_after=self.retry_after_for(tenant),
                tenant=tenant) from None
        if fp is not None and fp.delay_admit > 0:
            await asyncio.sleep(fp.delay_admit)

    def release(self, tenant: str, elapsed: float = 0.0) -> None:
        self.inflight -= 1
        ring = self._latency_by_tenant.get(tenant)
        if ring is None:
            ring = self._latency_by_tenant[tenant] = deque(
                maxlen=_LATENCY_RING)
        ring.append(elapsed)
        self._kick()

    # -- dispatch -----------------------------------------------------------

    def _kick(self) -> None:
        """Dispatch queued waiters into free inflight slots, DRR order.

        Tenants whose rate bucket is empty are skipped this round (their
        waiter returns to its FIFO head) and a timer re-kicks at the earliest
        refill, so rate-limited waiters don't rely on unrelated traffic to
        get unparked."""
        skip: set[str] = set()
        min_refill: float | None = None
        while self.inflight < self.max_inflight:
            nxt = self.queue.pop(skip=skip)
            if nxt is None:
                break
            tenant, waiter = nxt
            if waiter.future.done():
                continue  # timed out while parked; slot already charged
            if waiter.deadline is not None and waiter.deadline.expired:
                self._count_shed(tenant)
                self.evicted_total += 1
                waiter.future.set_exception(QosRejected(
                    "deadline expired in admission queue",
                    retry_after=self.retry_after_for(tenant), tenant=tenant))
                continue
            bucket = self._bucket(tenant)
            if bucket is not None and not bucket.try_spend():
                self.queue.push_front(tenant, waiter)
                skip.add(tenant)
                refill = bucket.retry_after()
                if min_refill is None or refill < min_refill:
                    min_refill = refill
                continue
            self._admit(tenant)
            waiter.future.set_result(None)
        if min_refill is not None and len(self.queue) and not self._kick_scheduled:
            self._kick_scheduled = True
            asyncio.get_running_loop().call_later(
                max(min_refill, 0.005), self._timer_kick)

    def _timer_kick(self) -> None:
        self._kick_scheduled = False
        self._evict_expired()
        self._kick()

    # -- metrics ------------------------------------------------------------

    def _p99(self, ring: deque) -> float:
        if not ring:
            return 0.0
        ordered = sorted(ring)
        return ordered[min(len(ordered) - 1, int(0.99 * (len(ordered) - 1)))]

    def counters(self) -> dict[str, float]:
        out = {
            # Same keys as LoadShedder.counters() so dashboards and the
            # overload chaos assertions read either plane unchanged.
            "shed_inflight": float(self.inflight),
            "shed_peak_inflight": float(self.peak_inflight),
            "shed_admitted_total": float(self.admitted_total),
            "shed_total": float(self.shed_total),
            "qos_queue_depth": float(len(self.queue)),
            "qos_queued_total": float(self.queued_total),
            "qos_rate_limited_total": float(self.rate_limited_total),
            "qos_evicted_total": float(self.evicted_total),
        }
        top = RetryBudget.EXPORT_TOP_N
        out.update(capped_by_key("qos_tenant", self._admitted_by_tenant,
                                 top_n=top, suffix="_admitted_total"))
        out.update(capped_by_key("qos_tenant", self._shed_by_tenant,
                                 top_n=top, suffix="_shed_total"))
        out.update(capped_by_key("qos_tenant", self._rate_limited_by_tenant,
                                 top_n=top, suffix="_rate_limited_total"))
        depths = {t: float(self.queue.depth(t)) for t in self.queue.tenants()}
        out.update(capped_by_key("qos_tenant", depths,
                                 top_n=top, suffix="_queue_depth"))
        p99s = {t: self._p99(ring)
                for t, ring in self._latency_by_tenant.items()}
        # Gauge rollup by max, not sum — an averaged-away p99 is a lie.
        ranked = sorted(p99s.items(), key=lambda kv: (-kv[1], kv[0]))
        for i, (t, v) in enumerate(ranked):
            if i < top:
                out[f"qos_tenant_{metric_key(t)}_p99_seconds"] = float(v)
            else:
                key = "qos_tenant_other_p99_seconds"
                out[key] = max(out.get(key, 0.0), float(v))
        return out


def shedder_from_env(inflight_env: str, default_inflight: int
                     ) -> "LoadShedder | QosShedder":
    """Build a service's admission controller from the environment.

    ``TPUDFS_QOS=1`` opts into the tenant-aware plane; anything else returns
    the flat :class:`LoadShedder` so existing deployments (and the overload
    chaos tier) keep today's behavior bit-for-bit. Knobs:

    - ``inflight_env`` (e.g. ``TPUDFS_CS_MAX_INFLIGHT``): inflight budget.
    - ``TPUDFS_QOS_WEIGHTS``: ``"tenantA=4,tenantB=1"`` fair-share weights.
    - ``TPUDFS_QOS_RATE`` / ``TPUDFS_QOS_BURST``: per-tenant req/s + burst
      (rate 0 = unlimited; ``system`` is always unlimited).
    - ``TPUDFS_QOS_QUEUE_DEPTH`` / ``TPUDFS_QOS_QUEUE_WAIT``: per-tenant
      queue bound and max park time before the rate-limited refusal.
    - ``TPUDFS_QOS_JITTER_SEED``: seed the retry-after jitter stream (pushed
      to the native engine too — parity/chaos determinism).
    - ``TPUDFS_QOS_FAILPOINT``: fault injection, see :class:`QosFailpoints`.
    """
    max_inflight = int(os.environ.get(inflight_env, str(default_inflight)))
    if os.environ.get("TPUDFS_QOS", "0") != "1":
        return LoadShedder(max_inflight=max_inflight)
    seed_raw = os.environ.get("TPUDFS_QOS_JITTER_SEED", "")
    if seed_raw:
        try:
            seed_retry_jitter(int(seed_raw))
        except ValueError:
            pass
    weights: dict[str, float] = {}
    for part in os.environ.get("TPUDFS_QOS_WEIGHTS", "").split(","):
        if "=" not in part:
            continue
        name, value = part.split("=", 1)
        try:
            weights[name.strip()] = float(value)
        except ValueError:
            continue
    rate = float(os.environ.get("TPUDFS_QOS_RATE", "0") or 0.0)
    burst_raw = os.environ.get("TPUDFS_QOS_BURST", "")
    failpoints = QosFailpoints.from_env()
    return QosShedder(
        max_inflight=max_inflight,
        weights=weights,
        rate=rate,
        burst=float(burst_raw) if burst_raw else None,
        queue_depth=int(os.environ.get("TPUDFS_QOS_QUEUE_DEPTH",
                                       str(QOS_QUEUE_DEPTH_DEFAULT))),
        max_queue_wait=float(os.environ.get("TPUDFS_QOS_QUEUE_WAIT", "0.25")),
        failpoints=failpoints if failpoints.any() else None,
    )


def qos_wire_config(shedder: "LoadShedder | QosShedder") -> dict:
    """The QoS control contract as a FLAT msgpack-able dict for the native
    engine (``tpudfs_dataplane_set_qos``). Flat on purpose: the engine's
    header parser reads scalar values and string arrays only, so tenant
    weights travel as ``"tenant=weight"`` strings rather than a nested map.
    A :class:`LoadShedder` maps to ``{"enabled": 0}`` — pushing it after a
    config change switches the engine's admission plane off."""
    if getattr(shedder, "acquire", None) is None:
        return {"enabled": 0}
    return {
        "enabled": 1,
        "max_inflight": int(shedder.max_inflight),
        "base_retry_after": float(shedder.base_retry_after),
        "rate": float(shedder.rate),
        "burst": float(shedder.burst),
        "queue_depth": int(shedder.queue_depth),
        "queue_wait": float(shedder.max_queue_wait),
        "default_weight": float(shedder.queue.default_weight),
        "weights": [f"{t}={w:g}" for t, w in
                    sorted(shedder.queue.weights.items())],
        "jitter_seed": jitter_seed(),
    }
