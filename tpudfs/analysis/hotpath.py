"""Hot-path annotation for tpulint's performance rules (tpuperf).

BENCH r01-r05 located the system's cost in a handful of code paths: the
block transports, the chunkserver read/write handlers, the client bulk
API, and the TPU infeed. A performance finding is only worth a
developer's time when it sits on one of those paths *and* runs more
than once per request — an O(n) copy in a config loader is noise; the
same copy per frame of a chain write is the whole write-pipeline gap.

This module computes, once per :class:`~tpudfs.analysis.callgraph.Project`:

- **hot-path membership** — reachability over resolved call edges from a
  fixed root set of bench/data-plane entry points (``BlockPortServer``
  frame loop, chunkserver ``rpc_*`` handlers, the client's bulk
  read/write API, the TPU infeed/combiner/write-group classes, the
  blockstore primitives those offload to). ``thread``/``task`` edges
  propagate: ``to_thread(store.read, ...)`` moves the bytes, not the
  heat.
- **entry loop depth** — how many loops already enclose a function's
  *call sites* when execution reaches it. A helper called from a
  per-frame ``while`` loop inherits depth 1 even though its own body is
  loop-free; the TPL03x rules add the local CFG depth on top, so "copy
  in a hot loop" means the effective depth, not the lexical one.

Loop depth at a statement comes from the CFG (:attr:`Node.loop_depth`),
with comprehension nesting counted on top — ``[f(x) for x in frames]``
runs ``f`` per frame exactly like the spelled-out loop.

Everything is conservative in the *finding-suppressing* direction:
unresolved calls propagate nothing, so a function is only "hot" when a
resolved chain from a root actually reaches it.
"""

from __future__ import annotations

import ast
import re
from collections import deque

from tpudfs.analysis.callgraph import FunctionInfo, Project
from tpudfs.analysis.cfg import cfg_for

__all__ = ["HotPaths", "hot_paths", "loop_depth_at"]

#: Effective-depth cap: bounds the fixpoint and keeps a pathological
#: loop-in-loop-in-loop chain from dominating every report.
_DEPTH_CAP = 4

#: Qualname patterns of the data-plane roots: what the benchmark's traffic
#: (benchmarks/, outside the linted tree) enters through, the client bulk
#: API and the readers into HBM, and the transports and chunkserver
#: handlers those fan out to.
_ROOT_PATTERNS = [
    # Block transport: the per-frame serve loop and the client pool call.
    r"^tpudfs\.common\.blocknet\.BlockPortServer\._handle$",
    r"^tpudfs\.common\.blocknet\.BlockConnPool\.call$",
    r"^tpudfs\.common\.blocknet\._call_blockport$",
    # Chunkserver request handlers (both transports dispatch here) and
    # the collective-write persist entry.
    r"^tpudfs\.chunkserver\.service\.ChunkServer\."
    r"(rpc_\w+|persist_ici_replica)$",
    # Blockstore primitives: handlers offload to them per block.
    r"^tpudfs\.chunkserver\.blockstore\.BlockStore\."
    r"(read\w*|write\w*|verify\w*|publish\w*)$",
    # Client bulk data API (what `put`/`get`/benchmark drive).
    r"^tpudfs\.client\.client\.Client\."
    r"(create_file|read_file\w*|_read_\w+|_write_\w+)$",
    # TPU data plane: infeed sources, HBM reader, combiner, write group.
    r"^tpudfs\.tpu\.grain_infeed\.(DfsSourceBase|DfsRecordSource|"
    r"_ClientLoop)\.\w+$",
    r"^tpudfs\.tpu\.hbm_reader\.HbmReader\.\w+$",
    r"^tpudfs\.tpu\.read_combiner\.ReadCombiner\.\w+$",
    r"^tpudfs\.tpu\.device_block\.(DeviceBlock|DeviceBatch)\.\w+$",
    r"^tpudfs\.tpu\.host_buffers\.\w+$",
    r"^tpudfs\.tpu\.write_group\.IciWriteGroup\.\w+$",
]

_ROOT_RE = re.compile("|".join(f"(?:{p})" for p in _ROOT_PATTERNS))

_COMPREHENSIONS = (ast.ListComp, ast.SetComp, ast.DictComp,
                   ast.GeneratorExp)


def _stmt_depths(module, fn: ast.AST) -> dict[int, int]:
    """``id(stmt) -> loop_depth`` over the function's CFG nodes; a stmt
    represented by several nodes (with_enter/with_exit) takes the max."""
    cfg = cfg_for(module, fn)
    depths = getattr(cfg, "_stmt_depths", None)
    if depths is None:
        depths = {}
        for node in cfg.nodes:
            if node.stmt is None:
                continue
            key = id(node.stmt)
            if node.loop_depth > depths.get(key, -1):
                depths[key] = node.loop_depth
        cfg._stmt_depths = depths
    return depths


def loop_depth_at(module, fn: ast.AST, node: ast.AST) -> int:
    """Lexical loop-nesting depth of ``node`` inside ``fn``: the CFG
    depth of its enclosing statement, plus one per comprehension between
    the statement and ``node``."""
    depths = _stmt_depths(module, fn)
    comp = 0
    cur: ast.AST | None = node
    while cur is not None and cur is not fn:
        if id(cur) in depths:
            return depths[id(cur)] + comp
        if isinstance(cur, _COMPREHENSIONS):
            comp += 1
        cur = module.parent(cur)
    return comp


class HotPaths:
    """Hot-path membership + entry loop depth for every reachable fn."""

    __slots__ = ("roots", "_depth")

    def __init__(self, roots: set[FunctionInfo],
                 depth: dict[FunctionInfo, int]) -> None:
        self.roots = roots
        self._depth = depth

    def is_hot(self, fn: FunctionInfo) -> bool:
        return fn in self._depth

    def entry_depth(self, fn: FunctionInfo) -> int:
        """Loops already enclosing execution when ``fn`` is entered (max
        over resolved call chains from the roots); 0 for roots and for
        functions that are not hot at all — combine with :meth:`is_hot`."""
        return self._depth.get(fn, 0)

    def effective_depth(self, fn: FunctionInfo, local_depth: int) -> int:
        """Entry depth + the CFG depth of a statement inside ``fn``."""
        return min(_DEPTH_CAP, self.entry_depth(fn) + local_depth)


def hot_paths(project: Project) -> HotPaths:
    """Memoized hot-path computation for the project (one BFS-to-fixpoint
    over call edges; depths only grow and are capped, so it terminates)."""
    cached = getattr(project, "_hotpaths", None)
    if cached is not None:
        return cached

    roots = {fn for qual, fn in project.functions.items()
             if _ROOT_RE.match(qual)}
    depth: dict[FunctionInfo, int] = {fn: 0 for fn in roots}
    work: deque[FunctionInfo] = deque(roots)
    while work:
        fn = work.popleft()
        base = depth[fn]
        for edge in fn.calls:
            site_depth = loop_depth_at(fn.module, fn.node, edge.site)
            new = min(_DEPTH_CAP, base + site_depth)
            if new > depth.get(edge.callee, -1):
                depth[edge.callee] = new
                work.append(edge.callee)

    hp = HotPaths(roots, depth)
    project._hotpaths = hp
    return hp
