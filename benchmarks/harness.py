"""One run of one cell: bring the deployment up, warm the cell's shapes,
measure for ``--seconds``, decide ``correct``, tear everything down and
print the contract's one last line.

Everything that belongs to one configuration, one traffic mix, one kind of
traffic or one per-layer metric is a file of its own, found by name:

- ``BENCHMARK.json`` ``workloads[].name`` -> the cell; its ``config`` ->
  ``benchmarks/configs/<config>.json``; its ``traffic`` ->
  ``benchmarks/workloads/<traffic>.json`` (the mix: data only);
- the mix's ``"kind"`` -> ``benchmarks/traffic/<kind>.py`` (the generator);
- the configuration's ``"bringup"`` -> ``deployments.BRINGUPS``;
- ``per_layer[].name`` -> ``benchmarks/layer_metrics/<name>.py``.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import importlib
import itertools
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from benchmarks import reference, trace_reduce
from benchmarks.deployments import BRINGUPS, read_replica
from benchmarks.peaks import peaks_for
from benchmarks.spans import CURRENT_OP, SpanRpcClient, Spans

REPO = Path(__file__).resolve().parent.parent


# ------------------------------------------------------------ what is run


def load_cell(workload: str, root: Path = REPO) -> dict:
    """The cell with its configuration and its mix, from the files the
    names in ``root``'s ``BENCHMARK.json`` point at."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "benchmarks" / "workloads"
                      / f"{cell['traffic']}.json").read_text())

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell, "cfg": cfg, "mix": mix,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def require_devices(chips: int) -> list:
    """The fatal device check: no option, environment variable or handler
    lets a run go on with another backend or fewer chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX reports platform "
                         f"{devices[0].platform!r}, not 'tpu'; no result")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {len(devices)}; no result")
    return devices[:chips]


def place_compile_cache() -> None:
    """JAX's persistent cache where the program puts it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), and
    every program kept: the cell's programs compile in under JAX's 1 s
    threshold, so by default none would be kept and every run would
    compile them all."""
    import jax

    from tpudfs.tpu import place_compile_cache as place

    place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache hits included), so a
    run can show that none happened inside its window."""

    def __init__(self) -> None:
        import jax.monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.count = 0
        self.seconds = 0.0

        def on_event(event: str, duration: float, **_kw) -> None:
            if event == BACKEND_COMPILE_EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


# ------------------------------------------------------------- operations


@dataclass
class Op:
    """One operation of the window: a file read, a put, a sweep call."""

    start: float  # perf_counter seconds
    end: float
    ok: bool
    nbytes: int
    what: object = None  # the traffic's own handle (path, stream, ...)

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e3


def rate(ops: list[Op], t0: float, t1: float) -> float:
    """Bytes per second of the operations that succeeded and ended inside
    [t0, t1]: all the work over all the time of the window."""
    done = sum(o.nbytes for o in ops if o.ok and o.end <= t1)
    return done / (t1 - t0)


#: what a tail reads when it falls on a failed operation (JSON has no inf)
MISSED_MS = 1e9


def p95_ms(ops: list[Op]) -> float:
    """95th percentile (nearest rank) over every operation issued in the
    window; a failed one misses any limit, so it sorts last."""
    if not ops:
        return MISSED_MS
    lat = sorted(o.ms if o.ok else MISSED_MS for o in ops)
    return lat[max(0, math.ceil(0.95 * len(lat)) - 1)]


async def closed_loop(clients: int, seconds: float, one_op, on_close=None
                      ) -> tuple[list[Op], float, float]:
    """``clients`` callers, each issuing its next operation when the last
    one answered, until ``seconds`` have passed; operations in flight at the
    close are awaited (their latency counts, their bytes do not).
    ``one_op(client, k)`` returns ``(nbytes, what)`` or raises."""
    ops: list[Op] = []
    ids = itertools.count()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    async def client(c: int) -> None:
        k = 0
        while time.perf_counter() < deadline:
            op_id = next(ids)
            CURRENT_OP.set(op_id)
            start = time.perf_counter()
            try:
                nbytes, what = await one_op(c, k)
                ops.append(Op(start, time.perf_counter(), True, nbytes,
                              (op_id, what)))
            except Exception as e:  # an operation that fails is counted
                print(f"benchmark: operation failed: {e!r}", file=sys.stderr)
                ops.append(Op(start, time.perf_counter(), False, 0,
                              (op_id, None)))
            k += 1

    tasks = [asyncio.create_task(client(c)) for c in range(clients)]
    await asyncio.sleep(max(0.0, deadline - time.perf_counter()))
    if on_close is not None:
        await on_close()
    await asyncio.gather(*tasks)
    return ops, t0, deadline


# ------------------------------------------------------------ correctness


@dataclass
class Check:
    """One number compared, beside its limit (``value <= limit`` passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class Expect:
    """The plain reference's side of the comparison for one deployment:
    regenerates what every stream of the seed holds and counts where the
    served path says otherwise. Nothing here calls the program except
    through the three reads a client of the deployment has (metadata,
    one named replica, bytes D2H'd from the device)."""

    def __init__(self, ctx: "Context"):
        self.ctx = ctx
        self.block_bytes = ctx.cfg["block_bytes"]
        self.replication = ctx.cfg["replication"]
        self.counts = dict.fromkeys(
            ("meta_missing", "meta_size_wrong", "meta_crc_wrong",
             "meta_replicas_short", "replica_bytes_wrong",
             "device_blocks_missing", "device_blocks_unverified",
             "device_bytes_wrong"), 0)
        self.compared = dict.fromkeys(
            ("files", "meta_blocks", "replica_reads", "device_blocks"), 0)

    def data(self, stream: int, nbytes: int) -> bytes:
        return reference.seeded_bytes(self.ctx.seed, stream, nbytes)

    def wrong(self, number: str, what: str, by: int = 1) -> None:
        """Counts one mismatch and says on standard error what it was (the
        driver's record keeps the end of it)."""
        self.counts[number] += by
        print(f"benchmark: {number}: {what}"[:400], file=sys.stderr)

    async def metadata(self, client, path: str, data: bytes) -> dict | None:
        """The master's record of ``path`` against the reference's: size,
        block list, every block's recorded CRC32C, ``replication`` distinct
        locations."""
        self.compared["files"] += 1
        meta = await client.get_file_info(path)
        want = reference.expected_file(data, self.block_bytes,
                                       self.replication)
        if meta is None:
            self.wrong("meta_missing", path)
            return None
        blocks = meta.get("blocks") or []
        if int(meta.get("size", -1)) != want["size"] or \
                [int(b.get("size") or 0) for b in blocks] \
                != want["block_sizes"]:
            self.wrong("meta_size_wrong",
                       f"{path}: size {meta.get('size')} blocks "
                       f"{[b.get('size') for b in blocks]} against size "
                       f"{want['size']} blocks {want['block_sizes']}; "
                       f"{ {k: v for k, v in meta.items() if k != 'blocks'} }")
            return None
        for b, crc in zip(blocks, want["block_crcs"]):
            self.compared["meta_blocks"] += 1
            if int(b.get("checksum_crc32c") or -1) != crc:
                self.wrong("meta_crc_wrong", f"{path} {b['block_id']}: "
                           f"{b.get('checksum_crc32c')} against {crc}")
            if len({a for a in b.get("locations") or [] if a}) \
                    < want["replicas"]:
                self.wrong("meta_replicas_short", f"{path} "
                           f"{b['block_id']}: {b.get('locations')}")
        return meta

    async def replicas(self, meta: dict, data: bytes, picked) -> None:
        """Every replica the master names for the ``picked`` blocks,
        asked directly, returns the reference's bytes."""
        view = memoryview(data)
        for j in picked:
            block = meta["blocks"][j]
            want = view[j * self.block_bytes:(j + 1) * self.block_bytes]
            for addr in sorted({a for a in block["locations"] if a}):
                self.compared["replica_reads"] += 1
                try:
                    got = await read_replica(self.ctx.rpc, addr,
                                             block["block_id"])
                except Exception as e:
                    print(f"benchmark: replica {addr} of "
                          f"{block['block_id']}: {e!r}", file=sys.stderr)
                    got = None
                if got != want:
                    self.wrong("replica_bytes_wrong",
                               f"{addr} {block['block_id']}")

    def device(self, held: list, data: bytes) -> None:
        """The blocks of one file as they sit in HBM (``DeviceBlock``s the
        timed entry returned) against the reference's bytes: every block
        there, confirmed verified, and D2H'd bytes identical."""
        nblocks = max(1, -(-len(data) // self.block_bytes))
        self.compared["device_blocks"] += nblocks
        if len(held) != nblocks:
            self.wrong("device_blocks_missing",
                       f"{len(held)} held of {nblocks}",
                       abs(nblocks - len(held)))
        view = memoryview(data)
        for j, b in enumerate(held[:nblocks]):
            want = view[j * self.block_bytes:(j + 1) * self.block_bytes]
            if not b.verified:
                self.wrong("device_blocks_unverified", b.block_id)
            # ``b.array`` takes the block out of its fused round ON the
            # device, as a consumer of one block does; then D2H.
            words = np.asarray(b.array)
            got = words.reshape(-1).view(np.uint8)[:b.size]
            if b.size != len(want) or \
                    not np.array_equal(got, np.frombuffer(want, np.uint8)):
                self.wrong("device_bytes_wrong", b.block_id)

    def checks(self) -> list[Check]:
        return [Check(k, v, 0) for k, v in self.counts.items()]


# ---------------------------------------------------------------- context


@dataclass
class Context:
    cell: dict
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    bringup: object
    rpc: SpanRpcClient
    spans: Spans
    workdir: Path
    rng: np.random.Generator
    #: set-up readings of per-layer readers that have a ``setup`` hook
    setup_readings: dict = field(default_factory=dict)
    #: ``fn() -> dict`` of the traffic's local counters (no RPC)
    local_counters: object = None

    @property
    def device(self):
        return self.devices[0]


@dataclass
class Window:
    """What the per-layer readers read."""

    ctx: Context
    ops: list[Op]
    t0: float
    t1: float
    #: counters (bring-up's and traffic's) at the window's ends
    before: dict
    after: dict
    #: local counters at the traced part's ends
    trace_before: dict
    trace_after: dict
    trace: trace_reduce.Trace | None
    #: the traced part on the profile's clock, ns
    lo_ns: float
    hi_ns: float
    peaks: dict

    def delta(self, key: str) -> float | None:
        if key not in self.before or key not in self.after:
            return None
        return self.after[key] - self.before[key]

    def trace_delta(self, key: str) -> float | None:
        if key not in self.trace_before or key not in self.trace_after:
            return None
        return self.trace_after[key] - self.trace_before[key]


class Tracer:
    """Profiles the last ``trace_seconds`` of the window (and the check,
    where the mix says the window itself runs nothing on the device)."""

    def __init__(self, ctx: Context, t_start: float):
        self.ctx = ctx
        self.dir = ctx.workdir / "trace"
        self.t_start = t_start
        self.task: asyncio.Task | None = None
        self.on = False
        self.mark_wall_ns = 0
        self.start_wall_ns = 0
        self.stop_wall_ns = 0
        self.before: dict = {}
        self.after: dict = {}

    def arm(self) -> None:
        if self.ctx.trace:
            self.task = asyncio.create_task(self._start_later())

    async def _start_later(self) -> None:
        import jax

        lead = min(self.ctx.mix.get("trace_seconds", 5), self.ctx.seconds)
        await asyncio.sleep(max(
            0.0, self.t_start + self.ctx.seconds - lead
            - time.perf_counter()))
        # No Python tracer (it would slow the event loop the cell measures)
        # and only the host events the clock mark needs.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        await asyncio.to_thread(
            lambda: jax.profiler.start_trace(str(self.dir),
                                             profiler_options=options))
        self.on = True
        with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_MARK):
            self.mark_wall_ns = time.time_ns()
        self.start_wall_ns = time.time_ns()
        self.before = self.ctx.local_counters()

    async def stop(self) -> None:
        import jax

        if self.task is not None:
            await self.task
            self.task = None
        if self.on:
            self.on = False
            self.after = self.ctx.local_counters()
            self.stop_wall_ns = time.time_ns()
            await asyncio.to_thread(jax.profiler.stop_trace)

    def load(self) -> tuple[trace_reduce.Trace, float, float]:
        trace = trace_reduce.load(trace_reduce.find_xplane(str(self.dir)))
        if trace.mark_ns is None:
            raise RuntimeError("the trace holds no clock mark")
        shift = trace.mark_ns - self.mark_wall_ns
        return (trace, self.start_wall_ns + shift,
                self.stop_wall_ns + shift)


# -------------------------------------------------------------------- run


def _workdir(cfg: dict) -> Path:
    """The directory under the cluster: ``assumed.data_dir`` says
    ``tmpdir`` (``TMPDIR``, which the driver gives each side) or
    ``checkout``. Never a fixed path outside both."""
    where = cfg.get("assumed", {}).get("data_dir", "tmpdir")
    if where == "checkout":
        base = REPO / ".bench_work"
        base.mkdir(exist_ok=True)
        return Path(tempfile.mkdtemp(prefix="run-", dir=base))
    return Path(tempfile.mkdtemp(prefix="tpudfs-bench-"))


def _memory_peak(devices: list) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _summary(values: list[float]) -> dict:
    if not values:
        return {}
    v = sorted(values)
    return {"n": len(v), "min": v[0], "p50": v[len(v) // 2],
            "p95": v[max(0, math.ceil(0.95 * len(v)) - 1)], "max": v[-1]}


def _longest_gap(ops: list[Op], t0: float) -> float:
    """The longest time in which no operation completed: a stall of the
    whole served path shows here where the tail only hints at it."""
    ends = sorted(o.end for o in ops)
    return max((b - a for a, b in zip([t0] + ends, ends)), default=0.0)


def _layer_reader(name: str):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


async def run_cell(workload: str, seed: int, seconds: float, trace: bool,
                   t_process_start: float, *, sabotage=None,
                   root: Path = REPO) -> dict:
    """Runs the cell and returns the result line as a dict. ``sabotage``
    (controls and tests only; ``run.py`` never passes one) is an object
    whose hooks break a guarantee underneath the timed path; ``root``
    (tests only) holds another ``BENCHMARK.json`` with its data files."""
    loaded = load_cell(workload, root)
    cell, cfg, mix = loaded["cell"], loaded["cfg"], loaded["mix"]
    workdir = _workdir(cfg)
    bringup = BRINGUPS[cfg["bringup"]](cfg, workdir)
    spans = Spans()
    rpc = None
    traffic = None
    try:
        # The servers come up while this process starts JAX.
        bringup.launch()
        devices = require_devices(cell["chips"])
        place_compile_cache()
        clock = CompileClock()
        rpc = SpanRpcClient(spans)
        ctx = Context(cell=cell, cfg=cfg, mix=mix, seed=seed,
                      seconds=seconds, trace=trace, devices=devices,
                      bringup=bringup, rpc=rpc, spans=spans, workdir=workdir,
                      rng=np.random.default_rng([seed, 0xC0FFEE]))
        await bringup.ready(devices, rpc)
        ready_s = time.perf_counter() - t_process_start
        kind = importlib.import_module(f"benchmarks.traffic.{mix['kind']}")
        traffic = kind.Traffic(ctx)
        if sabotage is not None:
            sabotage.attach(ctx, traffic)
        ctx.local_counters = lambda: {**bringup.local_counters(),
                                      **traffic.counters()}
        await traffic.prepare()
        if sabotage is not None:
            await sabotage.after_prepare()
        readers = {m["name"]: _layer_reader(m["name"])
                   for m in loaded["per_layer"]} if trace else {}
        for name, reader in readers.items():
            if hasattr(reader, "setup"):
                ctx.setup_readings[name] = await asyncio.to_thread(
                    reader.setup, ctx)
        gc.collect()

        async def counters() -> dict:
            return {**await bringup.counters(rpc), **ctx.local_counters()}

        before = await counters()
        compiles_before = clock.count
        setup_s = time.perf_counter() - t_process_start

        # ------------------------------------------------- the window
        tracer = Tracer(ctx, time.perf_counter())
        tracer.arm()
        covers_check = bool(mix.get("trace_covers_check"))
        ops, t0, t1 = await traffic.window(
            seconds, None if covers_check else tracer.stop)
        compiles_in_window = clock.count - compiles_before
        after = await counters()
        memory_peak = _memory_peak(devices)

        # ----------------------------------------------- the comparison
        if sabotage is not None:
            await sabotage.after_window(ops)
        expect = Expect(ctx)
        t_check = time.perf_counter()
        await traffic.check(ops, expect)
        await tracer.stop()
        check_s = time.perf_counter() - t_check
        checks = [Check("ops_failed", sum(not o.ok for o in ops), 0),
                  Check("compiles_in_window", compiles_in_window, 0),
                  *expect.checks()]

        metrics: dict = {}
        units = {m["name"]: m["unit"]
                 for m in loaded["end_to_end"] + loaded["per_layer"]}
        device_line = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}
        line: dict = {"correct": all(c.ok for c in checks),
                      "attempted": len(ops),
                      "failed": sum(not o.ok for o in ops)}
        if trace:
            tr, lo_ns, hi_ns = await asyncio.to_thread(tracer.load)
            win = Window(ctx, ops, t0, t1, before, after, tracer.before,
                         tracer.after, tr, lo_ns, hi_ns,
                         peaks_for(devices[0].device_kind))
            for name, reader in readers.items():
                value = reader.read(win)
                if value is not None:
                    metrics[name] = {"value": value, "unit": units[name]}
            device_line["busy_s"] = trace_reduce.busy_seconds(
                tr, lo_ns, hi_ns)
            device_line["window_s"] = (hi_ns - lo_ns) / 1e9
            shift = lo_ns - tracer.start_wall_ns
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(tr, lo_ns, hi_ns),
                "idle_gaps": trace_reduce.idle_by_host_activity(
                    tr, lo_ns, hi_ns,
                    [(n, s + shift, e + shift)
                     for n, _op, s, e in spans.rows]),
            }
        else:
            measured = traffic.end_to_end(ops, t0, t1)
            measured["setup_s"] = setup_s
            for m in loaded["end_to_end"]:
                if m["name"] in measured:
                    metrics[m["name"]] = {"value": measured[m["name"]],
                                          "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = device_line
        line["window"] = {
            "seconds": t1 - t0, "setup_s": setup_s, "ready_s": ready_s,
            "dataset_write_s": getattr(traffic, "dataset_write_s", None),
            "check_s": check_s, "compared": expect.compared,
            "op_ms": _summary([o.ms for o in ops if o.ok]),
            "longest_completion_gap_s": _longest_gap(ops, t0),
            "counters": {k: after[k] - before[k] for k in sorted(after)
                         if k in before and after[k] != before[k]},
        }
        line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in checks}
        return line
    finally:
        if traffic is not None:
            with contextlib.suppress(Exception):
                await traffic.close()
        if rpc is not None:
            with contextlib.suppress(Exception):
                await rpc.close()
        await bringup.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def print_result(line: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error; the result as the last line of standard output."""
    for name, c in line["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"check {name}: value {c['value']} limit {c['limit']} "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
