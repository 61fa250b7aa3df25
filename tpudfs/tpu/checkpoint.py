"""Fault-tolerant sharded checkpoints over tpudfs.

The production scenario: a data-parallel training job on a TPU pod
checkpoints every N steps. Each replica owns one shard of the
weight/optimizer state (ZeRO-style partitioning — "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training", PAPERS.md), writes
only that shard, and any of the moving parts can die mid-save: a replica
is preempted, a chunkserver is SIGKILLed, a shard master is deposed. The
contract this module provides under all of that:

- **All-or-nothing visibility.** Shard payloads land under a per-step
  staging prefix (``{base}/.ckpt/{step}/``, see
  :mod:`tpudfs.common.ckptpaths`); the checkpoint becomes visible through
  exactly one atomic master command — ``publish_checkpoint`` renames the
  staged manifest to ``{base}/MANIFEST-{step}``. Readers list manifests
  only, so a crash at any point leaves either the previous checkpoint or
  the new one, never a blend. This mirrors the blockstore's stage→publish
  discipline (blockstore.py write_staged/publish_staged_batch) one level
  up the stack.
- **Resumable, idempotent saves.** Progress is the namespace itself: a
  shard whose hot copy already carries the payload's content ETag
  (``ckpt-{crc32c:08x}-{size}``) is skipped on re-save, so a restarted
  replica re-puts only incomplete shards, under resilience.py deadline
  budgets. A replayed commit converges through the master's idempotent
  publish; a zombie writer replaying an OLD step is rejected by the
  monotonic-step fence at apply time.
- **Gracefully degrading restore.** Shards restore in parallel, optionally
  straight into device HBM via :class:`~tpudfs.tpu.hbm_reader.HbmReader`:
  blocks are read lazily-verified (on a reader with ``batch_reads`` they
  ride the read combiner's fused rounds), ONE ``confirm`` a shard resolves
  every block's on-device CRC before any tensor reaches JAX, and the
  tensors are assembled on the device (below). Per shard the read falls
  back: hot 3x-replicated copy (replica failover inside the client/reader)
  → erasure-coded cold copy (RS reconstruction when chunkservers are dead)
  → :class:`DegradedRestoreError`. Every path is CRC-verified end-to-end
  against the manifest.

Shard payload format: tensors sorted by name, each serialized raw
(C-order, little-endian) at a 512-byte-aligned offset (``_ALIGN`` = the
CRC chunk size = one 128-word row of a block's device grid, so every
tensor starts on a row). The per-shard spec records
name/dtype/shape/offset/size/crc32c per tensor plus the whole-payload
CRC; the manifest aggregates the specs of all shards. ``dtype`` is the
numpy NAME (``"bfloat16"``, ``"float32"``): ``dtype.str`` of an
``ml_dtypes`` type is a void (``"<V2"``). Manifests that carry ``.str``
codes (``"<f4"``) still load. A rank of a sharded job may save PIECES of
global tensors (``pieces={name: Piece(global_shape, start)}``): the spec of
a piece adds ``global_shape`` and ``start``, and ``name`` is the global
tensor's; a tensor saved whole carries neither key, so manifests of the
format as it was (``tpudfs-ckpt-1``, unchanged) load and restore as before.

Device assembly (:mod:`tpudfs.tpu.ckpt_assemble`): the rounds (and single
blocks) a shard's read left in HBM are gathered into one row buffer in
file order (``ckpt_assemble_gather``, in place, one call a round) and ONE
program per shard layout (``ckpt_assemble``) cuts that buffer into typed
tensors. Every move is made on unsigned integers and the dtype comes last:
a 4-byte dtype is a bitcast, a 2-byte one splits each word into its halves
(little-endian: the low half first); on a TPU, whose vector unit cannot
write bfloat16 without flushing denormals and quieting NaNs, a bf16 tensor
is relabelled by a Mosaic kernel instead. 1- and 8-byte dtypes (and, on a
TPU, float16 and bf16 scalars or ragged vectors) bounce through the host
(``stats["tensor_bytes_host_bounce"]``). Rounds and buffer are dropped when
the program has them: during a shard's restore HBM holds at most twice the
shard beside the rounds in flight.

Restore under another layout (``restore(target=...)``,
:mod:`tpudfs.tpu.ckpt_reshard`): a mesh, a ``PartitionSpec`` per global
tensor and this host's index range of each; the planned blocks land on the
chips that need them, one ``confirm`` and the combined CRC a shard file,
one chip-to-chip move and one assembly a chip, then ``{name: jax.Array}``.

Spans (``tpudfs.common.telemetry``; sites and attrs in
``docs/operations.md``): ``ckpt.restore`` with children
``ckpt.latest_step``, ``ckpt.manifest`` and, per shard,
``ckpt.read_shard``, ``ckpt.confirm``, ``ckpt.combined_crc``,
``ckpt.assemble``; under another layout ``ckpt.plan``, the per-shard
three, ``ckpt.redistribute`` and ``ckpt.assemble`` per chip.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import json
import logging
import time

import numpy as np

from tpudfs.client.client import (
    ChecksumMismatchError,
    Client,
    DfsError,
)
from tpudfs.common import ckptpaths, telemetry
from tpudfs.common.checksum import crc32c, crc32c_combine
from tpudfs.common.resilience import (
    BudgetExhausted,
    as_system_tenant,
    deadline_scope,
    shielded_from_deadline,
    tenant_scope,
)

logger = logging.getLogger(__name__)

FORMAT = "tpudfs-ckpt-1"
#: Tensor alignment inside a shard payload: the 512-byte CRC chunk size.
#: Keeps every tensor offset chunk-aligned (device CRC granularity) and
#: word-aligned (the HBM restore path slices a uint32 word stream).
_ALIGN = 512

#: Errors a shard read can die with before its fallback is consulted.
_READ_ERRORS = (DfsError, ChecksumMismatchError, BudgetExhausted,
                asyncio.TimeoutError, OSError)


class CheckpointError(DfsError):
    """Base for checkpoint-layer failures."""


class CheckpointNotFoundError(CheckpointError):
    """No published manifest matches the requested step (or none exist)."""


class IncompleteCheckpointError(CheckpointError):
    """Commit refused: some shard is missing or not durably complete."""


class DegradedRestoreError(CheckpointError):
    """A shard is unreadable through the hot copy AND the EC cold copy."""


def _align(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def _dtype_of(name: str) -> np.dtype:
    """A spec's dtype: the numpy name (an ``ml_dtypes`` one included), or
    the ``.str`` code of a manifest written before names were."""
    try:
        return np.dtype(name)
    except TypeError:
        # numpy learns "bfloat16" and the float8s when ml_dtypes is imported.
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


@dataclasses.dataclass
class TensorSpec:
    """One tensor's placement inside a shard payload."""

    name: str
    dtype: str  # numpy dtype name, e.g. "bfloat16" (old manifests: "<f4")
    shape: tuple[int, ...]
    offset: int
    size: int
    crc32c: int
    #: a piece of a global tensor (``name`` is the global tensor's): its
    #: shape and where the piece starts in it; None for a tensor saved
    #: whole, and then not written, so such a spec is what it always was
    global_shape: tuple[int, ...] | None = None
    start: tuple[int, ...] | None = None

    def to_dict(self) -> dict:
        d = self.__dict__.copy()
        d["shape"] = list(self.shape)
        for key in ("global_shape", "start"):
            if d[key] is None:
                del d[key]
            else:
                d[key] = list(d[key])
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TensorSpec":
        d = dict(d)
        for key in ("shape", "global_shape", "start"):
            if d.get(key) is not None:
                d[key] = tuple(d[key])
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class Piece:
    """Where a saved array lies in its global tensor: the global shape and
    the index the piece starts at (a rank's slice of a sharded tensor)."""

    global_shape: tuple[int, ...]
    start: tuple[int, ...]


def pack_shard(tree: dict, pieces: dict | None = None
               ) -> tuple[bytes, list[TensorSpec]]:
    """Serialize a flat ``{name: array}`` tree into one payload.
    ``pieces[name]`` (a :class:`Piece`) marks an array as a piece of the
    global tensor ``name``; a piece that is the whole tensor is recorded as
    a whole tensor.

    Deterministic: tensors in sorted name order at aligned offsets, so the
    same tree always produces byte-identical payloads — which is what
    makes the content-ETag resume probe (and the chaos tier's bit-exact
    assertions) sound."""
    pieces = pieces or {}
    if set(pieces) - set(tree):
        raise ValueError(f"pieces of tensors not in the tree: "
                         f"{sorted(set(pieces) - set(tree))}")
    buf = bytearray()
    specs: list[TensorSpec] = []
    for name in sorted(tree):
        arr = np.asarray(tree[name])
        if arr.dtype.byteorder == ">":
            arr = arr.astype(arr.dtype.newbyteorder("<"))
        raw = arr.tobytes()
        offset = _align(len(buf))
        buf.extend(b"\x00" * (offset - len(buf)))
        spec = TensorSpec(name=name, dtype=arr.dtype.name,
                          shape=tuple(arr.shape), offset=offset,
                          size=len(raw), crc32c=crc32c(raw))
        piece = pieces.get(name)
        if piece is not None:
            gshape, start = tuple(piece.global_shape), tuple(piece.start)
            if len(gshape) != arr.ndim or len(start) != arr.ndim or any(
                    a < 0 or a + n > g
                    for a, n, g in zip(start, arr.shape, gshape)):
                raise ValueError(f"{name}: a piece of {arr.shape} at "
                                 f"{start} does not fit in {gshape}")
            if gshape != arr.shape:
                spec.global_shape, spec.start = gshape, start
        specs.append(spec)
        buf.extend(raw)
    return bytes(buf), specs


def unpack_shard(payload: bytes, tensors: list[dict]) -> dict:
    """Payload bytes → ``{name: np.ndarray}``, CRC-verifying every tensor
    (defense in depth on top of the whole-shard CRC — a bug in offset
    bookkeeping surfaces as a checksum error, not silently sheared
    weights)."""
    out: dict[str, np.ndarray] = {}
    for t in tensors:
        spec = TensorSpec.from_dict(t) if isinstance(t, dict) else t
        raw = payload[spec.offset:spec.offset + spec.size]
        if len(raw) != spec.size or crc32c(raw) != spec.crc32c:
            raise ChecksumMismatchError(
                f"tensor {spec.name!r} failed CRC inside its shard payload"
            )
        out[spec.name] = np.frombuffer(raw, dtype=_dtype_of(spec.dtype)) \
            .reshape(spec.shape)
    return out


def _validate_manifest(body: bytes) -> dict:
    """Parse + structurally validate a manifest body (the bytes themselves
    arrive through the client's CRC-verified read path)."""
    manifest = json.loads(body)
    if manifest.get("format") != FORMAT:
        raise CheckpointError(
            f"unknown checkpoint format {manifest.get('format')!r}")
    for key in ("base", "step", "num_shards", "shards"):
        if key not in manifest:
            raise CheckpointError(f"manifest missing required key {key!r}")
    if len(manifest["shards"]) != int(manifest["num_shards"]):
        raise CheckpointError(
            f"manifest lists {len(manifest['shards'])} shard specs for "
            f"num_shards={manifest['num_shards']}")
    return manifest


def _whole_on_one(manifest: dict, target) -> bool:
    """Whether ``target`` is the saved layout on one chip: one device,
    every tensor saved whole (once) and wanted whole."""
    if target.mesh.size != 1:
        return False
    names = set()
    for spec in manifest["shards"]:
        for t in spec["tensors"]:
            shape = tuple(t["shape"])
            if t.get("global_shape") is not None or t["name"] in names:
                return False
            names.add(t["name"])
            rng = target.host_index.get(t["name"])
            if rng and tuple(map(tuple, rng)) != tuple((0, n) for n in shape):
                return False
    return True


class CheckpointManager:
    """Save/commit/restore partitioned checkpoints under ``base``.

    ``ec=(k, m)`` shapes the cold copy (RS(k, m); None disables it);
    ``hot_copies=False`` drops the replicated hot copy and saves the EC
    copy only (the archival/bench-degraded configuration). ``reader`` is
    an optional :class:`~tpudfs.tpu.hbm_reader.HbmReader` used when
    ``restore(..., device=...)`` asks for tensors in HBM (build it with
    ``batch_reads=16`` and the shards' blocks ride the read combiner's
    fused rounds); without it (or without a device) restore assembles
    host numpy arrays.

    Budgets: ``save_budget_s``/``restore_budget_s`` install a resilience
    deadline scope around each public op unless an outer scope is already
    active (the training loop's own deadline always wins)."""

    def __init__(self, client: Client, base: str, *, num_shards: int,
                 ec: tuple[int, int] | None = (3, 2), hot_copies: bool = True,
                 reader=None, save_budget_s: float | None = None,
                 restore_budget_s: float | None = None,
                 tenant: str | None = None):
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        if not hot_copies and not ec:
            raise ValueError("need hot copies, an EC shape, or both")
        self.client = client
        self.base = base.rstrip("/")
        self.num_shards = num_shards
        self.ec = tuple(ec) if ec else None
        self.hot_copies = hot_copies
        if reader is not None and client.block_size % _ALIGN:
            # The HBM restore path lays the blocks out by rows of 512 bytes
            # and finds a tensor by its payload offset, which is only sound
            # when every non-final block is a whole number of rows.
            raise ValueError(
                f"block_size {client.block_size} must be a multiple of "
                f"{_ALIGN} for device restore")
        self.reader = reader
        self.save_budget_s = save_budget_s
        self.restore_budget_s = restore_budget_s
        #: Tenant identity stamped on save/restore RPCs (QoS attribution of
        #: the training job). Falls back to the client's configured tenant;
        #: staging GC always runs as ``system`` regardless (maintenance must
        #: not be rate-limited against a tenant quota).
        self.tenant = tenant
        #: Observability for tests/chaos: how work actually happened.
        self.stats = {
            "shards_written": 0,    # payload puts that hit the wire
            "shards_skipped": 0,    # resume probe proved the shard durable
            "commits": 0,
            "already_published": 0,  # idempotent re-publish converged
            "restored_shards": 0,
            "degraded_shard_reads": 0,  # hot copy dead -> EC cold copy
            "gc_deleted": 0,
            # device restore: tensor bytes cut out of the blocks on the
            # device, and those that went through the host on the way
            "tensor_bytes_device": 0,
            "tensor_bytes_host_bounce": 0,
            # restore under another layout (``restore(target=...)``):
            # bytes of the needed blocks, each once; bytes uploaded host to
            # device (re-reads included); bytes moved chip to chip; pieces
            # cut (a saved piece's part in one chip's shard)
            "reshard_unique_bytes": 0,
            "reshard_h2d_bytes": 0,
            "reshard_ici_bytes": 0,
            "reshard_pieces": 0,
        }

    @contextlib.contextmanager
    def _op_scope(self, budget: float | None):
        """Deadline + tenant scope for one public op (ambient values from
        the training loop's own scope always win)."""
        with deadline_scope(budget), tenant_scope(self.tenant):
            yield

    # ------------------------------------------------------------------ save

    @staticmethod
    def _content_etag(crc: int, size: int) -> str:
        """Content ETag stored on every checkpoint file: the resume probe
        compares it (plus size) against a re-packed payload, so "is this
        shard already durable?" is one metadata round-trip, no reread."""
        return f"ckpt-{crc:08x}-{size}"

    async def save_shard(self, step: int, shard: int, tree: dict, *,
                         pieces: dict | None = None) -> dict:
        """Durably write one shard's payload (hot + EC copies) and its
        spec. Idempotent: a payload already durable under the same content
        ETag is skipped, so a preempted replica that restarts re-puts only
        what is incomplete. ``pieces`` as :func:`pack_shard` takes it.
        Returns the shard spec dict."""
        if not 0 <= shard < self.num_shards:
            raise ValueError(f"shard {shard} out of range")
        payload, tensors = pack_shard(tree, pieces)
        crc = crc32c(payload)
        etag = self._content_etag(crc, len(payload))
        attrs = {"ckpt_step": str(step), "ckpt_shard": str(shard),
                 "ckpt_crc32c": f"{crc:08x}"}
        data_path = ckptpaths.shard_data_path(self.base, step, shard) \
            if self.hot_copies else None
        ec_path = ckptpaths.shard_ec_path(self.base, step, shard) \
            if self.ec else None
        with self._op_scope(self.save_budget_s):
            if data_path is not None:
                await self._put_if_absent(data_path, payload, etag, attrs,
                                          ec=None)
            if ec_path is not None:
                await self._put_if_absent(ec_path, payload, etag, attrs,
                                          ec=self.ec)
            spec = {
                "shard": shard, "path": data_path, "ec_path": ec_path,
                "size": len(payload), "crc32c": crc, "etag": etag,
                "tensors": [t.to_dict() for t in tensors],
            }
            body = json.dumps(spec, sort_keys=True).encode()
            await self.client.create_file(
                ckptpaths.shard_spec_path(self.base, step, shard), body,
                overwrite=True)
        return spec

    async def _put_if_absent(self, path: str, payload: bytes, etag: str,
                             attrs: dict, ec: tuple[int, int] | None) -> None:
        """The resume primitive: probe, then put only when the durable
        state doesn't already match. ``overwrite=True`` on the put makes a
        half-written victim of an earlier crash (invisible to the probe —
        incomplete files are never listed or stat-able) simply get
        replaced, and turns the retry of an IndeterminateError into a
        clean last-writer-wins replay."""
        info = await self.client.get_file_info(path)
        if info is not None and info.get("etag_md5") == etag \
                and int(info.get("size", -1)) == len(payload):
            self.stats["shards_skipped"] += 1
            return
        await self.client.create_file(path, payload, ec=ec, etag=etag,
                                      overwrite=True, attrs=attrs)
        self.stats["shards_written"] += 1

    async def commit(self, step: int) -> dict:
        """Phase two: verify every shard is durable, then publish.

        The durability check (:meth:`_verify_staged`) re-stats every shard
        against its spec BEFORE anything becomes visible — the manifest is
        only built from shards proven complete, then staged as a durable
        file itself, then atomically renamed by the master. tpulint TPL025
        proves this ordering on the CFG. Any replica (or an external
        coordinator) may call commit; it needs no tensor data, only the
        staged specs."""
        with self._op_scope(self.save_budget_s):
            shards = await self._verify_staged(step)
            manifest = {
                "format": FORMAT, "base": self.base, "step": step,
                "num_shards": self.num_shards,
                "ec": list(self.ec) if self.ec else None,
                "created_at_ms": int(time.time() * 1000),
                "shards": shards,
            }
            body = json.dumps(manifest, sort_keys=True).encode()
            staged = ckptpaths.staged_manifest_path(self.base, step)
            await self.client.create_file(staged, body, overwrite=True)
            fresh = await self.client.publish_checkpoint(
                self.base, step, src=staged,
                dst=ckptpaths.manifest_path(self.base, step))
            self.stats["commits"] += 1
            if not fresh:
                self.stats["already_published"] += 1
        return manifest

    async def _verify_staged(self, step: int) -> list[dict]:
        """Every shard's spec present + payload files durably complete
        with matching size/ETag; raises :class:`IncompleteCheckpointError`
        naming what is missing."""
        async def one(shard: int) -> dict:
            spec_path = ckptpaths.shard_spec_path(self.base, step, shard)
            try:
                spec = json.loads(await self.client.get_file(spec_path))
            except DfsError as e:
                raise IncompleteCheckpointError(
                    f"step {step} shard {shard}: spec missing ({e})"
                ) from e
            for path in (spec.get("path"), spec.get("ec_path")):
                if path is None:
                    continue
                info = await self.client.get_file_info(path)
                if info is None or info.get("etag_md5") != spec["etag"] \
                        or int(info.get("size", -1)) != spec["size"]:
                    raise IncompleteCheckpointError(
                        f"step {step} shard {shard}: {path} is not "
                        "durably complete"
                    )
            return spec

        specs = await asyncio.gather(*(one(s) for s in range(self.num_shards)))
        return sorted(specs, key=lambda s: s["shard"])

    async def save(self, step: int, trees: dict[int, dict], *,
                   pieces: dict[int, dict] | None = None) -> dict:
        """Convenience single-caller save: write every shard, then commit.
        ``trees`` maps shard id -> tensor tree and must cover all shards;
        ``pieces`` maps shard id -> that shard's ``pieces``."""
        if sorted(trees) != list(range(self.num_shards)):
            raise ValueError(
                f"save(step={step}) needs trees for shards "
                f"0..{self.num_shards - 1}, got {sorted(trees)}")
        with self._op_scope(self.save_budget_s):
            await asyncio.gather(*(
                self.save_shard(step, shard, tree,
                                pieces=(pieces or {}).get(shard))
                for shard, tree in trees.items()
            ))
            return await self.commit(step)

    # --------------------------------------------------------------- listing

    async def list_steps(self) -> list[int]:
        """Published steps, ascending. ONLY the manifest listing decides —
        staging files are never consulted, so an in-flight or torn save is
        invisible here by construction."""
        entries = await self.client.list_files_with_meta(
            ckptpaths.manifest_list_prefix(self.base), meta=False)
        steps = []
        for path, _ in entries:
            parsed = ckptpaths.parse_manifest_path(path)
            if parsed is not None and parsed[0] == self.base:
                steps.append(parsed[1])
        return sorted(steps)

    async def latest_step(self) -> int | None:
        steps = await self.list_steps()
        return steps[-1] if steps else None

    async def read_manifest(self, step: int | None = None) -> dict:
        if step is None:
            step = await self.latest_step()
            if step is None:
                raise CheckpointNotFoundError(
                    f"no published checkpoints under {self.base}")
        try:
            body = await self.client.get_file(
                ckptpaths.manifest_path(self.base, step))
        except DfsError as e:
            raise CheckpointNotFoundError(
                f"checkpoint step {step} is not published under "
                f"{self.base}: {e}"
            ) from e
        return _validate_manifest(body)

    # --------------------------------------------------------------- restore

    async def restore(self, step: int | None = None, *,
                      shards: list[int] | None = None,
                      device=None, target=None) -> dict:
        """Parallel shard-wise restore of ``step`` (default: latest).
        Returns ``{shard: {name: array}}``; arrays are host numpy unless
        ``device`` (and a reader) put them in HBM.

        With ``target`` (a :class:`~tpudfs.tpu.ckpt_reshard.Target`: a mesh,
        a ``PartitionSpec`` per global tensor, this host's index range of
        each) it returns ``{global name: jax.Array}`` sharded as the target
        says, whatever layout the pieces were saved in: planned blocks read
        through the reader's combiner of the chip each lands on, one
        ``confirm`` and the combined CRC a shard file, then one chip-to-chip
        move and one assembly a chip (:mod:`tpudfs.tpu.ckpt_reshard`). A
        target that is the saved layout on one chip restores as
        ``restore(device=that chip)`` does, array for array."""
        with telemetry.span("ckpt.restore") as whole:
            if step is None:
                with telemetry.span("ckpt.latest_step"):
                    step = await self.latest_step()
                if step is None:
                    raise CheckpointNotFoundError(
                        f"no published checkpoints under {self.base}")
            with telemetry.span("ckpt.manifest", step=step):
                manifest = await self.read_manifest(step)
            if target is not None:
                whole.set(step=step, shards=len(manifest["shards"]))
                with self._op_scope(self.restore_budget_s):
                    return await self._restore_target(manifest, target)
            by_id = {s["shard"]: s for s in manifest["shards"]}
            want = sorted(by_id) if shards is None else list(shards)
            whole.set(step=step, shards=len(want))
            with self._op_scope(self.restore_budget_s):
                trees = await asyncio.gather(*(
                    self.restore_shard(manifest, s, device=device)
                    for s in want
                ))
            return dict(zip(want, trees))

    async def restore_shard(self, manifest: dict, shard: int, *,
                            device=None) -> dict:
        """One shard's tensors, CRC-verified end-to-end, degrading from
        the hot copy (replica failover inside the read path) to the EC
        cold copy (RS reconstruction) before giving up."""
        spec = next((s for s in manifest["shards"] if s["shard"] == shard),
                    None)
        if spec is None:
            raise CheckpointNotFoundError(
                f"manifest step {manifest['step']} has no shard {shard}")
        with self._op_scope(self.restore_budget_s):
            if device is not None and self.reader is not None:
                tree = await self._restore_shard_device(spec, device)
            else:
                payload = await self._read_shard_payload(spec)
                tree = unpack_shard(payload, spec["tensors"])
            self.stats["restored_shards"] += 1
            return tree

    async def _read_shard_payload(self, spec: dict) -> bytes:
        """Host-side shard bytes with the full fallback chain, whole-shard
        CRC checked against the manifest on every path."""
        sources = [p for p in (spec.get("path"), spec.get("ec_path"))
                   if p is not None]
        last: Exception | None = None
        for i, path in enumerate(sources):
            if i > 0:
                self.stats["degraded_shard_reads"] += 1
                logger.warning(
                    "shard %s: hot copy unreadable (%s); reconstructing "
                    "from EC cold copy %s", spec["shard"], last, path)
            try:
                payload = await self.client.get_file(path)
            except _READ_ERRORS as e:
                last = e
                continue
            if len(payload) == spec["size"] \
                    and crc32c(payload) == spec["crc32c"]:
                return payload
            last = ChecksumMismatchError(
                f"{path}: payload failed whole-shard CRC")
        raise DegradedRestoreError(
            f"shard {spec['shard']} unrestorable: every copy failed "
            f"({last})")

    async def _restore_shard_device(self, spec: dict, device) -> dict:
        """HBM restore: blocks land on ``device`` lazily verified (fused
        rounds where the reader batches), ONE ``confirm`` resolves every
        block's on-device CRC, the whole-shard CRC is reconciled from the
        per-block checksums via the GF(2) combine (no host byte pass), and
        only then are the tensors cut out of the blocks, on the device
        (:mod:`tpudfs.tpu.ckpt_assemble`)."""
        from tpudfs.tpu import ckpt_assemble

        shard = spec["shard"]
        blocks, _uploaded = await self._read_confirmed(spec)
        tensors = spec["tensors"]
        with telemetry.span("ckpt.assemble", shard=shard,
                            tensors=len(tensors), bytes=spec["size"]):
            tree, on_device, bounced = ckpt_assemble.assemble_shard(
                tensors, [_dtype_of(t["dtype"]) for t in tensors],
                spec["size"], blocks, device,
                self.client.block_size // _ALIGN)
        self.stats["tensor_bytes_device"] += on_device
        self.stats["tensor_bytes_host_bounce"] += bounced
        return tree

    async def _read_confirmed(self, spec: dict, device_of=None
                              ) -> tuple[list, int]:
        """One shard file's blocks in HBM, every one CRC-verified on the
        device it landed on (ONE ``confirm``) and the whole-shard CRC
        reconciled from the master's per-block checksums, degrading from
        the hot copy to the EC cold copy. ``device_of(i)``: the device
        block ``i`` lands on, None to leave it unread (default: the
        reader's own placement). Returns the blocks in file order (None
        where left) and the bytes uploaded, re-reads included."""
        shard = spec["shard"]
        sources = [(p, kind) for p, kind in ((spec.get("path"), "hot"),
                                             (spec.get("ec_path"), "ec"))
                   if p is not None]
        uploaded = 0
        last: Exception | None = None
        for i, (path, kind) in enumerate(sources):
            if i > 0:
                self.stats["degraded_shard_reads"] += 1
                logger.warning(
                    "shard %s: hot copy unreadable in HBM path (%s); "
                    "reconstructing from EC cold copy %s", shard, last, path)
            try:
                with telemetry.span("ckpt.read_shard", shard=shard,
                                    source=kind) as reading:
                    if device_of is None:
                        blocks = await self.reader.read_file_to_device_blocks(
                            path, verify="lazy")
                    else:
                        blocks = await self.reader.read_file_to_device_blocks(
                            path, verify="lazy", placement=device_of)
                        reading.set(devices=len({id(b.device) for b in blocks
                                                 if b is not None}))
                    reading.set(blocks=len(blocks))
                held = [b for b in blocks if b is not None]
                # what each block's bytes are now: a re-read in confirm
                # replaces them, and is another upload
                before = [b.batch if b.batch is not None else b.array
                          for b in held]
                uploaded += sum(b.size for b in held)
                try:
                    with telemetry.span("ckpt.confirm", shard=shard,
                                        blocks=len(held)):
                        await self.reader.confirm(held)
                finally:
                    uploaded += sum(
                        b.size for b, was in zip(held, before)
                        if (b.batch if b.batch is not None else b.array)
                        is not was)
                with telemetry.span("ckpt.combined_crc", shard=shard):
                    if len(held) == len(blocks):
                        listed = [b.source for b in held]
                    else:
                        listed = (await self.client.get_file_info(path)
                                  or {}).get("blocks") or []
                    self._check_combined_crc(path, spec, listed)
                return blocks, uploaded
            except _READ_ERRORS as e:
                last = e
        raise DegradedRestoreError(
            f"shard {shard} unrestorable into HBM: every copy "
            f"failed ({last})")

    async def _restore_target(self, manifest: dict, target) -> dict:
        """``restore(target=...)``: plan, read, move, assemble."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from tpudfs.tpu import ckpt_reshard

        if self.reader is None:
            raise ValueError("a restore onto a target needs a reader")
        devices = list(target.mesh.devices.flat)
        if _whole_on_one(manifest, target):
            trees = await asyncio.gather(*(
                self.restore_shard(manifest, s["shard"], device=devices[0])
                for s in manifest["shards"]))
            return {name: jax.make_array_from_single_device_arrays(
                        arr.shape,
                        NamedSharding(target.mesh,
                                      target.specs.get(name,
                                                       PartitionSpec())),
                        [arr])
                    for tree in trees for name, arr in tree.items()}
        with telemetry.span("ckpt.plan") as planning:
            plan = ckpt_reshard.plan(manifest, target, _dtype_of,
                                     self.client.block_size)
            planning.set(pieces=plan.pieces, reads=plan.uploads,
                         devices=len(devices))

        async def one(spec: dict):
            where = plan.placement[spec["shard"]]
            if all(d is None for d in where):
                return spec["shard"], [], 0

            def device_of(j: int):
                d = where[j] if j < len(where) else None
                return None if d is None else devices[d]

            blocks, uploaded = await self._read_confirmed(spec, device_of)
            return spec["shard"], blocks, uploaded

        read = await asyncio.gather(*(one(s) for s in manifest["shards"]))
        with telemetry.span("ckpt.redistribute", bytes=plan.ici_bytes):
            stages = ckpt_reshard.stage(plan, {s: b for s, b, _u in read})
            inboxes = ckpt_reshard.redistribute(plan, stages)
        self.stats["reshard_h2d_bytes"] += sum(u for _s, _b, u in read)
        self.stats["restored_shards"] += sum(1 for _s, b, _u in read if b)
        del read
        per_device = []
        for i, device in enumerate(devices):
            with telemetry.span("ckpt.assemble", device=device.id,
                                tensors=len(plan.outputs),
                                bytes=plan.resident):
                shards, on_dev, bounced = ckpt_reshard.assemble(
                    plan, i, stages[i], inboxes[i])
            stages[i] = inboxes[i] = None  # the program holds them
            per_device.append(shards)
            self.stats["tensor_bytes_device"] += on_dev
            self.stats["tensor_bytes_host_bounce"] += bounced
        self.stats["reshard_unique_bytes"] += plan.unique_bytes
        self.stats["reshard_ici_bytes"] += plan.ici_bytes
        self.stats["reshard_pieces"] += plan.pieces
        return ckpt_reshard.arrays(plan, per_device)

    async def warm_restore(self, device=None, step: int | None = None, *,
                           target=None) -> None:
        """Pre-compile what ``restore(step, device=device)`` (or
        ``restore(step, target=target)``) dispatches on the device after
        its blocks are in: per shard, the gather at every round size the
        reader's combiner ships and the assembly of the shard's layout
        (H2D-free, on zeros); for a target, every chip's gathers, the
        chip-to-chip move and every chip's assembly. The read's own
        programs are the reader's to warm (``HbmReader.warm_batches``)."""
        from tpudfs.tpu import ckpt_assemble

        manifest = await self.read_manifest(step)
        if target is not None:
            if not _whole_on_one(manifest, target):
                await self._warm_target(manifest, target)
                return
            device = next(iter(target.mesh.devices.flat))
        for spec in manifest["shards"]:
            tensors = spec["tensors"]
            await asyncio.to_thread(
                ckpt_assemble.warm_shard, tensors,
                [_dtype_of(t["dtype"]) for t in tensors], spec["size"],
                device, self.client.block_size // _ALIGN,
                getattr(self.reader, "batch_reads", 0))

    async def _warm_target(self, manifest: dict, target) -> None:
        from tpudfs.tpu import ckpt_reshard

        bb = self.client.block_size
        plan = ckpt_reshard.plan(manifest, target, _dtype_of, bb)
        short = {-(-(s["size"] - (-(-s["size"] // bb) - 1) * bb) // _ALIGN)
                 for s in manifest["shards"] if s["size"]}
        await asyncio.to_thread(ckpt_reshard.warm, plan,
                                getattr(self.reader, "batch_reads", 0), short)

    @staticmethod
    def _check_combined_crc(path: str, spec: dict,
                            blocks: list[dict]) -> None:
        """Whole-shard CRC from the master-recorded per-block checksums via
        ``crc32c_combine`` — metadata math only, no byte reread. ``blocks``
        is the master's block list the read itself went by (each
        ``DeviceBlock.source``). Applies when the block metadata reconciles
        to the payload length (the hot copy always does; EC block records
        may carry coded sizes)."""
        crc, total = 0, 0
        for b in blocks:
            size = int(b.get("original_size") or b.get("size") or 0)
            if not size or not b.get("checksum_crc32c"):
                return  # pre-checksum metadata: per-block verify covers it
            crc = crc32c_combine(crc, int(b["checksum_crc32c"]), size)
            total += size
        if total != spec["size"]:
            return  # coded sizes don't reconcile; per-block verify covers it
        if crc != spec["crc32c"]:
            raise ChecksumMismatchError(
                f"{path}: combined block CRCs disagree with the manifest "
                "whole-shard CRC")

    # -------------------------------------------------------------- cleanup

    async def prune(self, keep: int = 2) -> list[int]:
        """Delete all but the newest ``keep`` published checkpoints. The
        manifest goes FIRST — from that moment readers resolve to the next
        older (or newer) published step — then the step's data files; a
        crash between the two leaves only invisible garbage for GC."""
        if keep < 1:
            raise ValueError("keep must be >= 1")
        doomed = (await self.list_steps())[:-keep]
        for step in doomed:
            await self.client.delete_file(
                ckptpaths.manifest_path(self.base, step))
            await self._delete_prefix(ckptpaths.step_prefix(self.base, step))
        return doomed

    async def gc_incomplete(self, *, max_age_ms: int = 3_600_000) -> list[str]:
        """Client-side twin of the master's run_ckpt_gc, for harnesses that
        want deterministic cleanup now rather than on the master's cadence.
        Removes staging files of unpublished steps that are superseded or
        older than ``max_age_ms``. Runs shielded from any ambient deadline
        for the same reason the master loop does: cleanup must not be
        starved by exactly the overload that produced the garbage — and as
        the ``system`` tenant, so QoS never rate-limits GC against the
        training job's quota. (Only complete-but-unpublished files are
        visible here; files torn mid-put are invisible to clients and only
        the master GC frees them.)"""
        deleted: list[str] = []
        with shielded_from_deadline(), as_system_tenant():
            published = set(await self.list_steps())
            latest = max(published, default=-1)
            now = int(time.time() * 1000)
            entries = await self.client.list_files_with_meta(
                ckptpaths.staging_root(self.base), meta=True)
            for path, meta in entries:
                parsed = ckptpaths.parse_step_path(path)
                if parsed is None or parsed[0] != self.base:
                    continue
                step = parsed[1]
                if step in published:
                    continue
                age = now - int((meta or {}).get("created_at_ms") or now)
                if latest > step or age >= max_age_ms:
                    await self.client.delete_file(path)
                    deleted.append(path)
                    self.stats["gc_deleted"] += 1
        return deleted

    async def _delete_prefix(self, prefix: str) -> None:
        entries = await self.client.list_files_with_meta(prefix, meta=False)
        await asyncio.gather(*(
            self.client.delete_file(path) for path, _ in entries
        ))
