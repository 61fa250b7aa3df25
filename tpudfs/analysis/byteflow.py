"""tpuflow: a static byte-cost ledger for the data-plane routes.

TPL030-034 catch *local* copy shapes (a slice in a hot loop, a
``bytes(mv)`` under a lock). What they cannot see is the whole-route
picture: how many times one payload byte is copied, checksummed and
(de)serialized between the client API and the disk or HBM it lands in.
This module builds that view statically, on top of the existing layers:

- the call graph (:mod:`tpudfs.analysis.callgraph`) resolves each named
  route entry point and the helpers it reaches,
- the CFG + dataflow solver (:mod:`tpudfs.analysis.cfg`,
  :mod:`tpudfs.analysis.dataflow`) orders the statements,
- buffer provenance (:mod:`tpudfs.analysis.bufferflow`) tells a payload
  buffer from a header int.

A **route** is a named slice of the data plane — client chain write,
warm-infeed read, chunkserver cache hit, EC encode/scatter, checkpoint
stage→publish — pinned by entry-function qualnames and bounded by the
modules the route's bytes actually traverse. For every function on a
route the walker counts, with ``file:line`` attribution ("hops"):

- **copies** — full-buffer O(n) events: ``bytes(mv)``, slicing a
  ``bytes``, concat, ``b"".join``, ``struct.pack``/msgpack of a payload
  buffer, ``.tobytes()``/``.hex()``/``.decode()`` on payloads;
- **crc_passes** — calls into :mod:`tpudfs.common.checksum`;
- **serializations** — pack/unpack/dumps/loads crossings.

The result is the committed ledger ``tpudfs/analysis/copy_ledger.json``.
CI recomputes it and fails when any route's copy count rises above the
committed budget (see :func:`check_ledger`), turning "we added a copy to
the hot path" into a red diff the same way the suppression ratchet turns
"we silenced a rule" into one. ``python -m tpudfs.analysis
--write-ledger`` regenerates the file but refuses silent growth.
"""

from __future__ import annotations

import ast
import json
import pathlib
import re
from dataclasses import dataclass

from tpudfs.analysis import bufferflow
from tpudfs.analysis.bufferflow import CRC_CALLS, PAYLOAD_NAME_RE
from tpudfs.analysis.callgraph import FunctionInfo, Project
from tpudfs.analysis.cfg import cfg_for

__all__ = [
    "CACHE_ROUTE",
    "DIRECT_ROUTE",
    "LEDGER_REL_PATH",
    "LEDGER_VERSION",
    "ROUTES",
    "RouteSpec",
    "check_ledger",
    "compute_ledger",
    "ledger_for_project",
    "load_committed_ledger",
    "load_project",
    "route_functions",
    "routes_for_files",
    "write_ledger_file",
]

LEDGER_REL_PATH = "tpudfs/analysis/copy_ledger.json"
LEDGER_VERSION = 1

#: Route names TPL064 compares: the cache-hit path must not cost more
#: copies per byte than the direct (warm-infeed) read path it shortcuts.
CACHE_ROUTE = "cache_hit_read"
DIRECT_ROUTE = "warm_infeed_read"


@dataclass(frozen=True)
class RouteSpec:
    """One named data-plane route.

    ``entries`` are full-match regexes over function qualnames; the
    route's function set is those entries plus everything they reach
    over resolved call edges within ``modules``, ``depth`` hops deep
    (nested ``def``s of a member are always included — their statements
    live outside the enclosing function's own CFG nodes). ``exclude``
    patterns reject qualnames that share a module with the route but
    belong to a different route's budget (e.g. the EC degraded-read
    helpers reachable from the cache-hit entry).
    """

    name: str
    title: str
    entries: tuple[str, ...]
    modules: tuple[str, ...]
    depth: int = 2
    exclude: tuple[str, ...] = ()


ROUTES: tuple[RouteSpec, ...] = (
    RouteSpec(
        name="chain_write",
        title="client chain write -> frame pipeline -> staged disk",
        entries=(
            r"tpudfs\.client\.client\.Client\.create_file",
            r"tpudfs\.client\.client\.Client\._write_blocks_and_complete",
            r"tpudfs\.client\.client\.Client\._write_replicated_block",
            r"tpudfs\.common\.writestream\.send_block_stream",
            r"tpudfs\.chunkserver\.service\.ChunkServer\.rpc_write_stream",
            r"tpudfs\.chunkserver\.service\.ChunkServer\.rpc_write_block",
        ),
        modules=(
            "tpudfs/client/client.py",
            "tpudfs/common/writestream.py",
            "tpudfs/common/blocknet.py",
            "tpudfs/chunkserver/service.py",
            "tpudfs/chunkserver/blockstore.py",
        ),
    ),
    RouteSpec(
        name="warm_infeed_read",
        title="HBM / warm-infeed read (fused ReadBlocks scatter)",
        entries=(
            r"tpudfs\.tpu\.hbm_reader\.HbmReader\.sweep_metas_to_device",
            r"tpudfs\.tpu\.read_combiner\.ReadCombiner\._fetch_remote",
            r"tpudfs\.chunkserver\.service\.ChunkServer\.rpc_read_blocks",
            # The one per-block fallback of the sweep and of the rounds.
            r"tpudfs\.tpu\.hbm_reader\.HbmReader\.read_block_to_device",
        ),
        modules=(
            "tpudfs/tpu/hbm_reader.py",
            "tpudfs/tpu/read_combiner.py",
            "tpudfs/tpu/device_block.py",
            "tpudfs/tpu/host_buffers.py",
            "tpudfs/chunkserver/service.py",
            "tpudfs/common/blocknet.py",
            "tpudfs/chunkserver/blockstore.py",
        ),
        # As on the cache-hit route: the EC read is the EC route's budget.
        exclude=(r".*\._ec_block_to_device(\..*)?",),
    ),
    RouteSpec(
        name="cache_hit_read",
        title="chunkserver cache hit (per-block ReadBlock)",
        entries=(
            r"tpudfs\.tpu\.hbm_reader\.HbmReader\._read_block_inner",
            r"tpudfs\.client\.client\.Client\._read_block_range",
            r"tpudfs\.chunkserver\.service\.ChunkServer\.rpc_read_block",
            # the handler's body, under the read clocks' wrapper
            r"tpudfs\.chunkserver\.service\.ChunkServer\._read_block",
        ),
        modules=(
            "tpudfs/tpu/hbm_reader.py",
            "tpudfs/client/client.py",
            "tpudfs/chunkserver/service.py",
            "tpudfs/common/blocknet.py",
        ),
        # Reaches the blockport transport: _read_block_range ->
        # _data_call -> BlockConnPool.call -> _call_blockport ->
        # _pack_frame/_read_frame.
        depth=4,
        # EC degraded-read helpers are reachable from _read_block_inner
        # but their copies are the EC route's budget, not the cache
        # hit's (TPL064 compares cache vs direct on like-for-like hops).
        exclude=(
            r".*\._ec_block_to_device(\..*)?",
            r".*\._read_ec_shards(\..*)?",
            r".*\._read_ec_block(\..*)?",
        ),
    ),
    RouteSpec(
        name="ec_encode_scatter",
        title="EC encode/scatter write + degraded shard read",
        entries=(
            r"tpudfs\.client\.client\.Client\._write_ec_block",
            r"tpudfs\.client\.client\.Client\._read_ec_shards",
            r"tpudfs\.client\.client\.Client\._read_ec_block",
            # The degraded read into HBM: a fused round's shard fetch and
            # its upload, and the per-block path it falls back to.
            r"tpudfs\.tpu\.read_combiner\.ReadCombiner\._fetch_ec",
            r"tpudfs\.tpu\.read_combiner\.ReadCombiner\._reconstruct",
            r"tpudfs\.tpu\.hbm_reader\.HbmReader\._ec_block_to_device",
            r"tpudfs\.common\.erasure\.encode",
        ),
        modules=(
            "tpudfs/client/client.py",
            "tpudfs/common/erasure.py",
            "tpudfs/common/blocknet.py",
            "tpudfs/tpu/hbm_reader.py",
            "tpudfs/tpu/read_combiner.py",
        ),
    ),
    RouteSpec(
        name="ckpt_stage_publish",
        title="checkpoint stage -> verify -> publish",
        entries=(
            r"tpudfs\.tpu\.checkpoint\.CheckpointManager\.save_shard",
            r"tpudfs\.tpu\.checkpoint\.CheckpointManager\._put_if_absent",
            r"tpudfs\.tpu\.checkpoint\.CheckpointManager\.commit",
        ),
        modules=("tpudfs/tpu/checkpoint.py",),
    ),
)

#: pack/unpack family: every call is a serialization crossing; with a
#: payload-provenance argument it is additionally a full-buffer copy.
_SER_CALLS = {
    "pack", "packb", "dumps", "loads", "unpack", "unpackb",
    "pack_into", "unpack_from",
}
#: attribute calls that materialize a fresh full-size buffer.
_COPY_ATTR_CALLS = {"tobytes", "hex", "decode"}
#: repo helpers that are known full-buffer materializations when fed a
#: payload (checksum.bytes_to_words zero-pads + casts into a new array).
_COPY_HELPERS = {"bytes_to_words": "pad-cast"}


def _callee(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _payloadish(expr: ast.AST, env: dict[str, set[str]]) -> bool:
    """Does ``expr`` plausibly hold a full data payload? Deliberately
    name-anchored: an inline ``readexactly(4)`` header read is a bytes
    *producer* but not a payload, so serialize calls over it are a wire
    crossing, not a full-buffer copy."""
    if isinstance(expr, ast.Name):
        return bool(PAYLOAD_NAME_RE.match(expr.id)) or bool(env.get(expr.id))
    if isinstance(expr, ast.Attribute):
        return bool(PAYLOAD_NAME_RE.match(expr.attr))
    return False


def _rx_rebuffer(call: ast.Call) -> bool:
    """A ``Read*`` data call without a ``payload_into`` scatter target:
    the response payload materializes in a fresh ``bytes`` (blockport
    ``readexactly`` or the gRPC plane) instead of landing in the caller's
    buffer — one full-buffer copy attributable to the call site."""
    if _callee(call) != "_data_call":
        return False
    method = next((a.value for a in call.args
                   if isinstance(a, ast.Constant)
                   and isinstance(a.value, str)), "")
    if not method.startswith("Read"):
        return False
    for kw in call.keywords:
        if kw.arg == "payload_into" and not (
                isinstance(kw.value, ast.Constant)
                and kw.value.value is None):
            return False
    return True


def _classify(expr: ast.AST,
              env: dict[str, set[str]]) -> list[tuple[str, str]]:
    """Byte-cost events a single expression incurs:
    ``[(kind, label)]`` with kind in {"copy", "crc", "serialize"}."""
    events: list[tuple[str, str]] = []
    label = bufferflow.is_copy_expr(expr, env)
    if label is not None:
        events.append(("copy", label))
    if not isinstance(expr, ast.Call):
        return events
    name = _callee(expr)
    if name in CRC_CALLS:
        events.append(("crc", name))
    if name in _SER_CALLS:
        events.append(("serialize", name))
        if any(_payloadish(a, env) for a in expr.args):
            events.append(("copy", f"{name}(payload)"))
    if name == "tobytes" and isinstance(expr.func, ast.Attribute) \
            and not expr.args:
        # Always a full materialization — that is the method's purpose.
        events.append(("copy", name))
    elif name in _COPY_ATTR_CALLS and isinstance(expr.func, ast.Attribute) \
            and not expr.args and _payloadish(expr.func.value, env):
        events.append(("copy", name))
    if name in _COPY_HELPERS \
            and any(_payloadish(a, env) for a in expr.args):
        events.append(("copy", _COPY_HELPERS[name]))
    if _rx_rebuffer(expr):
        events.append(("copy", "rx-rebuffer"))
    return events


def _walk_own(top: ast.AST):
    """``ast.walk`` that does not descend into nested ``def`` bodies —
    those are separate route members with their own CFGs, and walking
    them here would double-count every hop they contain."""
    nested = (ast.FunctionDef, ast.AsyncFunctionDef)
    if isinstance(top, nested):
        # A nested-def statement: its decorators/defaults run here, the
        # body belongs to the nested function's own cost walk.
        stack: list[ast.AST] = [*top.decorator_list,
                                *top.args.defaults, *top.args.kw_defaults]
        stack = [n for n in stack if n is not None]
    else:
        stack = [top]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, nested):
                continue
            stack.append(child)


def function_costs(fn: FunctionInfo) -> list[dict]:
    """Byte-cost hops inside one function, ordered by line."""
    module = fn.module
    flow = bufferflow.buffer_flow(module, fn.node)
    cfg = cfg_for(module, fn.node)
    hops: list[dict] = []
    seen: set[tuple[int, int, str, str]] = set()
    for node in cfg.nodes:
        in_facts, _out = flow.get(node.index, (None, None))
        env = bufferflow.env_from(in_facts)
        for top in node.exprs():
            for expr in _walk_own(top):
                events = _classify(expr, env)
                if not events:
                    continue
                line = getattr(expr, "lineno", node.lineno)
                col = getattr(expr, "col_offset", 0)
                for kind, label in events:
                    key = (line, col, kind, label)
                    if key in seen:
                        continue
                    seen.add(key)
                    hops.append({
                        "file": module.rel_path, "line": line,
                        "kind": kind, "label": label, "fn": fn.short(),
                    })
    hops.sort(key=lambda h: (h["file"], h["line"], h["kind"], h["label"]))
    return hops


def route_functions(project: Project,
                    spec: RouteSpec) -> list[FunctionInfo]:
    """Entry functions plus scope-bounded BFS over resolved call edges,
    plus the nested ``def``s of every member (their bodies are separate
    CFGs)."""
    pats = [re.compile(p) for p in spec.entries]
    excl = [re.compile(p) for p in spec.exclude]
    members: dict[str, FunctionInfo] = {}
    by_prefix = sorted(project.functions.items())

    def _admit(fn: FunctionInfo, frontier: list[FunctionInfo]) -> None:
        """Add ``fn`` and its nested defs (scatter callbacks, hedged
        read-closure bodies — separate CFGs, same logical hop)."""
        if fn.qualname in members:
            return
        if any(x.fullmatch(fn.qualname) for x in excl):
            return
        members[fn.qualname] = fn
        frontier.append(fn)
        prefix = fn.qualname + "."
        for qual, nested in by_prefix:
            if qual.startswith(prefix):
                _admit(nested, frontier)

    frontier: list[FunctionInfo] = []
    for qual, fn in by_prefix:
        if any(p.fullmatch(qual) for p in pats):
            _admit(fn, frontier)
    for _hop in range(spec.depth):
        nxt: list[FunctionInfo] = []
        for fn in frontier:
            for edge in fn.calls:
                if edge.callee.module.rel_path in spec.modules:
                    _admit(edge.callee, nxt)
        frontier = nxt
    return [members[q] for q in sorted(members)]


def compute_ledger(project: Project) -> dict:
    """The full per-route byte-cost ledger for one parsed project.
    Memoized on the project: TPL064 and the CLI gate share one walk."""
    cached = getattr(project, "_byteflow_ledger", None)
    if cached is not None:
        return cached
    routes: dict[str, dict] = {}
    for spec in ROUTES:
        fns = route_functions(project, spec)
        hops: list[dict] = []
        for fn in fns:
            hops.extend(function_costs(fn))
        hops.sort(key=lambda h: (h["file"], h["line"], h["kind"],
                                 h["label"]))
        routes[spec.name] = {
            "title": spec.title,
            "copies": sum(h["kind"] == "copy" for h in hops),
            "crc_passes": sum(h["kind"] == "crc" for h in hops),
            "serializations": sum(h["kind"] == "serialize" for h in hops),
            "functions": sorted(fn.qualname for fn in fns),
            "hops": [
                f"{h['file']}:{h['line']} {h['kind']}:{h['label']}"
                f" [{h['fn']}]"
                for h in hops
            ],
        }
    ledger = {"version": LEDGER_VERSION, "routes": routes}
    project._byteflow_ledger = ledger
    return ledger


def load_project(root: pathlib.Path) -> Project:
    """Parse the ``tpudfs`` package under ``root`` (or the whole root
    when there is no package dir) into one Project, with module paths
    relative to ``root`` so they match the route specs."""
    from tpudfs.analysis import linter

    pkg = root / "tpudfs"
    base = pkg if pkg.is_dir() else root
    modules = {}
    for path in linter.iter_python_files(base):
        module, _errors = linter._load_module(path, root)
        if module is not None:
            modules[module.rel_path] = module
    return Project(modules)


def ledger_for_project(root: pathlib.Path) -> dict:
    return compute_ledger(load_project(root))


def load_committed_ledger(root: pathlib.Path) -> dict | None:
    path = root / LEDGER_REL_PATH
    if not path.is_file():
        return None
    return json.loads(path.read_text(encoding="utf-8"))


def write_ledger_file(root: pathlib.Path, ledger: dict) -> None:
    path = root / LEDGER_REL_PATH
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(ledger, indent=2, sort_keys=True) + "\n",
                    encoding="utf-8")


def check_ledger(computed: dict, committed: dict) -> list[str]:
    """Budget breaches: any route whose copy count rose above the
    committed budget (or a committed route that vanished). Returns
    human-readable messages; empty means the budget holds."""
    breaches: list[str] = []
    committed_routes = committed.get("routes", {})
    computed_routes = computed.get("routes", {})
    for name, budget in sorted(committed_routes.items()):
        live = computed_routes.get(name)
        if live is None:
            breaches.append(f"route {name}: present in committed ledger "
                            "but no longer computed")
            continue
        if live["copies"] > budget["copies"]:
            known = set(budget["hops"])
            new_copy = [h for h in live["hops"]
                        if " copy:" in h and h not in known]
            detail = "; ".join(new_copy[:4])
            breaches.append(
                f"route {name}: {live['copies']} copies > committed "
                f"budget {budget['copies']}"
                + (f" (new: {detail})" if detail else "")
            )
    return breaches


def ledger_is_stale(computed: dict, committed: dict | None) -> bool:
    """Exact-sync gate: the committed ledger must match the tree."""
    return committed != computed


def routes_for_files(rel_paths) -> list[str]:
    """Route names whose module scope intersects ``rel_paths`` (plus
    every route when the committed ledger itself changed). Static — no
    project build needed, so ``--changed`` stays inside its budget."""
    paths = set(rel_paths)
    out = []
    for spec in ROUTES:
        if LEDGER_REL_PATH in paths or paths.intersection(spec.modules):
            out.append(spec.name)
    return out
