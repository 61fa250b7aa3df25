"""dfs CLI (reference dfs/client/src/bin/dfs_cli.rs).

Subcommands: put / get / inspect / ls / rm / rename / safe-mode / cluster /
benchmark (write|read|stress-write) / workload / check-history
(reference dfs_cli.rs:46-128; benchmark harness with a concurrency cap and
avg/p50/p95/p99 + MB/s stats, dfs_cli.rs:579-700,868).

Run: python -m tpudfs.client.cli --masters 127.0.0.1:50051 put local.bin /dst
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

from tpudfs.client.checker import check_linearizability, load_history
from tpudfs.client.client import Client, DfsError
from tpudfs.client.workload import WorkloadConfig, dump_history, run_workload
from tpudfs.common.rpc import add_tls_args, tls_from_args
from tpudfs.common import telemetry
from tpudfs.common.telemetry import setup_logging


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("tpudfs")
    p.add_argument("--masters", default="", help="comma-separated master addresses")
    p.add_argument("--config-servers", default="")
    add_tls_args(p)
    p.add_argument("--hedge-delay", type=float, default=None,
                   help="enable hedged reads with this delay in seconds")
    p.add_argument("--etag-mode", choices=["md5", "crc64"], default="md5",
                   help="put-path ETag: md5 (S3 conformance) or hardware "
                        "CRC-64/NVME (~50x cheaper, '-crc64' suffix)")
    p.add_argument("--trace-out", default="", metavar="FILE",
                   help="record this command's stage spans and write them "
                        "as Chrome trace-event JSON (load in Perfetto)")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("put", help="upload a local file")
    sp.add_argument("src")
    sp.add_argument("dest")
    sp.add_argument("--ec", default="", help="k,m for erasure coding (e.g. 6,3)")

    sp = sub.add_parser("get", help="download a file")
    sp.add_argument("src")
    sp.add_argument("dest")
    sp.add_argument("--offset", type=int, default=None)
    sp.add_argument("--length", type=int, default=None)

    sp = sub.add_parser("inspect", help="print file metadata as JSON")
    sp.add_argument("path")

    sp = sub.add_parser("shardmap", help="print the cluster shard map as "
                        "JSON (fetched from the config servers)")

    sp = sub.add_parser("ls", help="list files by prefix")
    sp.add_argument("prefix", nargs="?", default="")

    sp = sub.add_parser("rm", help="delete a file")
    sp.add_argument("path")

    sp = sub.add_parser("rename", help="rename/move a file")
    sp.add_argument("src")
    sp.add_argument("dest")

    sp = sub.add_parser("safe-mode")
    sp.add_argument("action", choices=["status", "enter", "exit"])

    sp = sub.add_parser("cluster", help="raft membership admin")
    sp.add_argument("action", choices=["add-server", "remove-server",
                                       "transfer-leader", "state"])
    sp.add_argument("address", nargs="?", default="")

    sp = sub.add_parser("shuffle", help="re-spread a prefix's blocks across "
                        "chunkservers (reference dfs_cli shuffle)")
    sp.add_argument("prefix")

    sp = sub.add_parser("benchmark")
    sp.add_argument("action", choices=["write", "read", "stress-write"])
    sp.add_argument("--files", type=int, default=100)
    sp.add_argument("--size", type=int, default=1024 * 1024)
    sp.add_argument("--concurrency", type=int, default=10)
    sp.add_argument("--prefix", default="/bench/")
    sp.add_argument("--duration", type=float, default=60.0)

    sp = sub.add_parser("workload", help="run a concurrent workload, save history")
    sp.add_argument("--clients", type=int, default=6)
    sp.add_argument("--ops", type=int, default=40)
    sp.add_argument("--keys", type=int, default=8)
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--out", default="history.jsonl")

    sp = sub.add_parser("check-history", help="linearizability-check a history")
    sp.add_argument("history")

    sp = sub.add_parser("presign", help="generate a presigned S3 URL "
                        "(creds from AWS_ACCESS_KEY_ID/AWS_SECRET_ACCESS_KEY; "
                        "reference dfs_cli.rs:471-520)")
    sp.add_argument("method", choices=["GET", "PUT", "DELETE", "HEAD"])
    sp.add_argument("endpoint", help="e.g. http://127.0.0.1:9000")
    sp.add_argument("path", help="e.g. /bucket/key")
    sp.add_argument("--expires", type=int, default=3600)
    return p


def make_client(args) -> Client:
    masters = [m for m in args.masters.split(",") if m]
    configs = [c for c in args.config_servers.split(",") if c]
    if not masters and not configs:
        print("error: pass --masters and/or --config-servers", file=sys.stderr)
        sys.exit(2)
    _stls, ctls = tls_from_args(args)
    return Client(masters or None, configs or None,
                  hedge_delay=args.hedge_delay, tls=ctls,
                  etag_mode=getattr(args, "etag_mode", "md5"))


def print_stats(label: str, latencies: list[float], total_bytes: int,
                wall: float) -> None:
    """avg/p50/p95/p99 + MB/s (reference print_stats dfs_cli.rs:868)."""
    lat = np.array(sorted(latencies))
    mbps = (total_bytes / (1024 * 1024)) / wall if wall > 0 else 0.0
    print(f"{label}: n={len(lat)} wall={wall:.2f}s throughput={mbps:.2f} MB/s")
    if len(lat):
        print(
            f"  latency avg={lat.mean() * 1000:.1f}ms "
            f"p50={np.percentile(lat, 50) * 1000:.1f}ms "
            f"p95={np.percentile(lat, 95) * 1000:.1f}ms "
            f"p99={np.percentile(lat, 99) * 1000:.1f}ms"
        )


async def bench_write(client: Client, args) -> None:
    data = np.random.default_rng(0).integers(
        0, 256, args.size, dtype=np.uint8
    ).tobytes()
    sem = asyncio.Semaphore(args.concurrency)
    latencies: list[float] = []

    async def one(i: int) -> None:
        async with sem:
            t0 = time.monotonic()
            await client.create_file(f"{args.prefix}f{i:06d}", data)
            latencies.append(time.monotonic() - t0)

    t0 = time.monotonic()
    await asyncio.gather(*(one(i) for i in range(args.files)))
    print_stats("write", latencies, args.size * args.files, time.monotonic() - t0)


async def bench_read(client: Client, args) -> None:
    paths = await client.list_files(args.prefix)
    if not paths:
        print("no files to read; run `benchmark write` first", file=sys.stderr)
        return
    sem = asyncio.Semaphore(args.concurrency)
    latencies: list[float] = []
    total = 0

    async def one(path: str) -> None:
        nonlocal total
        async with sem:
            t0 = time.monotonic()
            data = await client.get_file(path)
            latencies.append(time.monotonic() - t0)
            total += len(data)

    t0 = time.monotonic()
    await asyncio.gather(*(one(p) for p in paths))
    print_stats("read", latencies, total, time.monotonic() - t0)


async def bench_stress_write(client: Client, args) -> None:
    data = np.random.default_rng(0).integers(
        0, 256, args.size, dtype=np.uint8
    ).tobytes()
    latencies: list[float] = []
    stop = time.monotonic() + args.duration
    counter = [0]

    async def worker(w: int) -> None:
        while time.monotonic() < stop:
            i = counter[0]
            counter[0] += 1
            t0 = time.monotonic()
            try:
                await client.create_file(f"{args.prefix}stress-{w}-{i}", data)
                latencies.append(time.monotonic() - t0)
            except DfsError as e:
                print(f"write error: {e}", file=sys.stderr)

    t0 = time.monotonic()
    await asyncio.gather(*(worker(w) for w in range(args.concurrency)))
    print_stats("stress-write", latencies, args.size * len(latencies),
                time.monotonic() - t0)


async def amain(args) -> int:
    client = make_client(args)
    try:
        if args.cmd == "put":
            with open(args.src, "rb") as f:
                data = f.read()
            ec = None
            if args.ec:
                try:
                    k, m = (int(x) for x in args.ec.split(","))
                except ValueError:
                    print(f"error: --ec expects 'k,m' (e.g. 6,3), got {args.ec!r}",
                          file=sys.stderr)
                    return 2
                ec = (k, m)
            await client.create_file(args.dest, data, ec=ec)
            print(f"put {args.src} -> {args.dest} ({len(data)} bytes)")
        elif args.cmd == "get":
            if args.offset is not None or args.length is not None:
                data = await client.read_file_range(
                    args.src, args.offset or 0, args.length or (1 << 62)
                )
            else:
                data = await client.get_file(args.src)
            with open(args.dest, "wb") as f:
                f.write(data)
            print(f"get {args.src} -> {args.dest} ({len(data)} bytes)")
        elif args.cmd == "inspect":
            meta = await client.get_file_info(args.path)
            if meta is None:
                print("not found", file=sys.stderr)
                return 1
            print(json.dumps(meta, indent=2))
        elif args.cmd == "shardmap":
            await client.refresh_shard_map()
            if client.shard_map is None:
                print("no shard map (pass --config-servers)", file=sys.stderr)
                return 1
            print(json.dumps(client.shard_map.to_dict(), indent=2))
        elif args.cmd == "ls":
            for p in await client.list_files(args.prefix):
                print(p)
        elif args.cmd == "rm":
            await client.delete_file(args.path)
            print(f"deleted {args.path}")
        elif args.cmd == "rename":
            await client.rename_file(args.src, args.dest)
            print(f"renamed {args.src} -> {args.dest}")
        elif args.cmd == "safe-mode":
            if args.action == "status":
                print(json.dumps(await client.safe_mode_status()))
            else:
                await client.set_safe_mode(args.action == "enter")
                print(f"safe mode {args.action} requested")
        elif args.cmd == "cluster":
            if args.action == "state":
                for m in client.master_addrs:
                    try:
                        print(m, json.dumps(await client.raft_state(m)))
                    except Exception as e:
                        print(m, f"unreachable: {e}")
            else:
                if args.action == "add-server":
                    await client.cluster_add_server(args.address)
                elif args.action == "remove-server":
                    await client.cluster_remove_server(args.address)
                elif args.action == "transfer-leader":
                    await client.cluster_transfer_leadership(args.address)
                print("ok")
        elif args.cmd == "shuffle":
            await client.initiate_shuffle(args.prefix)
            print(f"shuffle initiated for {args.prefix}")
        elif args.cmd == "benchmark":
            if args.action == "write":
                await bench_write(client, args)
            elif args.action == "read":
                await bench_read(client, args)
            else:
                await bench_stress_write(client, args)
        elif args.cmd == "workload":
            cfg = WorkloadConfig(clients=args.clients,
                                 ops_per_client=args.ops,
                                 keys=args.keys, seed=args.seed)
            entries = await run_workload(client, cfg)
            dump_history(entries, args.out)
            print(f"recorded {len(entries)} ops to {args.out}")
        elif args.cmd == "check-history":
            result = check_linearizability(load_history(args.history))
            print(result.message)
            if result.linearizable:
                return 0
            return 2 if result.exhausted else 1
        return 0
    except DfsError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    finally:
        await client.close()


def cmd_presign(args) -> int:
    """Offline: no DFS connection needed, just env credentials."""
    import os

    from tpudfs.auth.presign import presign_url

    ak = os.environ.get("AWS_ACCESS_KEY_ID", "")
    sk = os.environ.get("AWS_SECRET_ACCESS_KEY", "")
    if not ak or not sk:
        print("error: set AWS_ACCESS_KEY_ID and AWS_SECRET_ACCESS_KEY",
              file=sys.stderr)
        return 2
    print(presign_url(args.method, args.endpoint, args.path, ak, sk,
                      expires_seconds=args.expires))
    return 0


def main(argv=None) -> None:
    setup_logging()
    args = build_parser().parse_args(argv)
    if args.cmd == "presign":
        sys.exit(cmd_presign(args))
    if not args.trace_out:
        sys.exit(asyncio.run(amain(args)))
    telemetry.enable()
    try:
        code = asyncio.run(amain(args))
    finally:
        telemetry.disable()
        with open(args.trace_out, "w") as f:
            json.dump(telemetry.chrome_trace(telemetry.drain()), f)
        if telemetry.dropped():
            print(f"trace: {telemetry.dropped()} spans dropped (buffer "
                  f"holds {telemetry.BUFFER_CAP})", file=sys.stderr)
    sys.exit(code)


if __name__ == "__main__":
    main()
