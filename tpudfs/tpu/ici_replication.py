"""Pipeline replication as XLA collectives over ICI.

The reference replicates every block over a sequential gRPC chain
client → CS1 → CS2 → CS3 (chunkserver.rs:777-825,1039-1077) — three full
traversals of the NIC per block. When ChunkServers are colocated on the TPU
hosts of a pod (the BASELINE.json north star), the same 3× chain can ride the
ICI fabric instead: each host's pending chunk writes are batched into a
"collective write group" (SURVEY.md §7 hard parts), expressed as a sharded
jax.Array, and the chain hop becomes ``jax.lax.ppermute`` ring shifts under
``shard_map`` — after R-1 shifts device i holds the shards of hosts
i, i-1, ..., i-R+1, exactly the chain-replication layout, with the transfers
scheduled by XLA on ICI links rather than TCP.

Acks: the per-hop ``replicas_written`` aggregation becomes an on-device
``psum`` of per-device verify results; CRC verification of the received
replicas runs on-device via the Pallas CRC kernel (jnp fallback off-TPU).

Works identically on the virtual CPU mesh used in tests (the driver's
``dryrun_multichip`` path) and a real multi-chip mesh.

Multi-host pods: every collective here also runs on an N-D mesh (e.g.
``Mesh(devs.reshape(n_hosts, chips), ("dcn", "ici"))``) with the ring
``axis`` naming the LAST mesh axis — the chain/scatter then rides ICI
inside each host row while the leading axes carry independent
data-parallel write groups (the reference's NCCL/MPI multi-host scaling,
re-expressed as mesh axes; DCN never carries block bytes, matching the
reference's rack-aware "replicas stay in-rack" placement). Ack psums
reduce over the WHOLE mesh: one scalar says every group verified.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudfs.tpu.crc32c_pallas import WORDS_PER_CHUNK, crc32c_chunks_device


def make_mesh(devices=None, axis: str = "hosts") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis,))


def _ring_axis(mesh: Mesh, axis: str | None) -> str:
    """The axis the chain/scatter rings ride. On N-D meshes it must be
    the LAST (fastest-varying) axis: per-position state built host-side
    (EcShardGather's decode matrices) maps device order to ring position
    as ``flat_index % ring_size``, which only holds for the last axis."""
    axis = axis or mesh.axis_names[-1]
    if axis != mesh.axis_names[-1]:
        raise ValueError(
            f"ring axis {axis!r} must be the last mesh axis "
            f"{mesh.axis_names[-1]!r}")
    return axis


def _all_axes(mesh: Mesh) -> tuple:
    return tuple(mesh.axis_names)


class IciReplicator:
    """R-way chain replication of per-host chunk groups over the mesh."""

    def __init__(self, mesh: Mesh, replication: int = 3, axis: str | None = None):
        self.mesh = mesh
        self.axis = _ring_axis(mesh, axis)
        self.replication = replication
        n = mesh.shape[self.axis]
        # Single-chip exception: every hop is a self-ppermute, replicas
        # coincide — degenerate but still compiles and runs the full
        # collective graph, which is what the driver's entry() exercises
        # on the one real chip. Any MULTI-device mesh must hold R distinct
        # replicas along the ring (a size-1 ring axis on a larger mesh
        # would silently produce zero redundancy), so the exception keys
        # on the TOTAL device count, not the ring size.
        if mesh.devices.size > 1 and replication > n:
            raise ValueError(f"replication {replication} > ring axis size {n}")
        self._fn = self._build()

    def _build(self):
        axis = self.axis
        R = self.replication
        mesh = self.mesh
        n = mesh.shape[axis]

        # The trace's readers find the program as ``jit_step`` (the inner
        # function's name) and its insides by this scope.
        @jax.named_scope("tpudfs.ici_replicate")
        def step(local_words: jnp.ndarray, local_crcs: jnp.ndarray):
            # local_words: (C, 128) uint32 — this host's pending chunk batch.
            # local_crcs:  (C,) uint32 — expected per-chunk CRCs.
            perm = [(i, (i + 1) % n) for i in range(n)]
            replicas = [local_words]
            crcs = [local_crcs]
            cur_w, cur_c = local_words, local_crcs
            for _ in range(R - 1):
                # Chain hop over ICI: everyone forwards to its right neighbor.
                cur_w = jax.lax.ppermute(cur_w, axis, perm)
                cur_c = jax.lax.ppermute(cur_c, axis, perm)
                replicas.append(cur_w)
                crcs.append(cur_c)
            stacked = jnp.stack(replicas)  # (R, C, 128)
            expected = jnp.stack(crcs)  # (R, C)
            # On-device end-to-end verify of every replica we now hold.
            actual = jax.vmap(
                lambda w: crc32c_chunks_device(w, use_pallas=None)
            )(stacked)
            ok = jnp.all(actual == expected)
            # replicas_written analogue: how many hosts verified every
            # replica — psum over EVERY mesh axis so the scalar covers all
            # data-parallel groups of an N-D pod mesh, not just this ring.
            acks = jax.lax.psum(ok.astype(jnp.int32), _all_axes(mesh))
            # ok gets a singleton axis: rank-0 outputs can't vary over a mesh.
            return stacked, ok[None], acks

        spec_in = P(_all_axes(mesh))
        # check_vma=False: pallas_call outputs don't carry vma metadata yet
        # (JAX 0.9), so the varying-across-mesh check can't see through them.
        return jax.jit(shard_map(
            step,
            mesh=mesh,
            in_specs=(spec_in, spec_in),
            out_specs=(spec_in, spec_in, P()),
            check_vma=False,
        ))

    def replicate(self, words: jax.Array, crcs: jax.Array):
        """words: (N*C, 128) uint32 sharded over every mesh axis (N =
        total devices, C chunks per host); crcs: (N*C,) uint32. Returns
        (replicas, ok, acks): replicas (N*R, C, 128) — R replica groups
        per host, ok per-host verify bit, acks = number of hosts (across
        ALL data-parallel groups) whose replicas all verified."""
        return self._fn(words, crcs)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(_all_axes(self.mesh)))


@partial(jax.jit, static_argnames=("k", "m"))
def _parity_of_words(words: jnp.ndarray, k: int, m: int) -> jnp.ndarray:
    from tpudfs.tpu.rs_pallas import pad_shard_len, rs_encode_device

    C = words.shape[0]
    total = C * WORDS_PER_CHUNK * 4
    # Shards are zero-padded to equal 128-lane-aligned length, matching the
    # reference's padded-shard layout (dfs/common/src/erasure.rs:7-28) and
    # rs_encode_device's lane requirement.
    shard = pad_shard_len(-(-total // k))
    flat = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(-1)
    flat = jnp.pad(flat, (0, k * shard - total))
    return rs_encode_device(flat.reshape(k, shard), k, m)


class EcShardScatter:
    """RS(k,m) shard distribution over ICI — the device twin of the
    storage-tier CONVERT_TO_EC migration (tpudfs/master:
    _schedule_ec_migrations / chunkserver convert_block_to_ec, which move
    shards host-to-host over gRPC).

    Each host RS-encodes its local chunk batch into k+m shards on device
    (Pallas GF(2^8) kernel), then shard j rides a ``ppermute`` ring shift
    of offset j: device d ends up holding shard j of host (d - j) mod n —
    the positional round-robin layout the master's rack-aware placement
    produces, with every transfer scheduled by XLA on ICI links. Every
    received shard is CRC-verified on device against the sender's
    per-chunk CRCs (which travel on the same ring), and the ack count is
    a ``psum`` — one collective round converts a whole batch of blocks,
    versus (k+m) gRPC hops per block on the host path.
    """

    def __init__(self, mesh: Mesh, k: int, m: int, axis: str | None = None):
        self.axis = _ring_axis(mesh, axis)
        n = mesh.shape[self.axis]
        # Degenerate-layout exception only for a true single-chip mesh —
        # see IciReplicator.__init__ (a size-1 ring axis on a multi-device
        # mesh must stay an error, not silently co-locate every shard).
        if mesh.devices.size > 1 and k + m > n:
            raise ValueError(f"RS({k},{m}) scatter needs {k + m} ring "
                             f"devices, axis has {n}")
        self.mesh = mesh
        self.k, self.m = k, m
        self._fn = self._build()

    def _build(self):
        axis, k, m = self.axis, self.k, self.m
        mesh = self.mesh
        n = mesh.shape[axis]

        @jax.named_scope("tpudfs.ec_scatter")
        def step(local_words: jnp.ndarray):
            # local_words: (C, 128) uint32 — this host's block batch.
            C = local_words.shape[0]
            total = C * WORDS_PER_CHUNK * 4
            # Shard length padded to a 512-byte multiple so per-shard CRC
            # chunking stays lane-aligned (512 is a multiple of the RS
            # kernel's 128-byte lane requirement).
            per = -(-total // k)          # ceil bytes per data shard
            shard = -(-per // 512) * 512  # …rounded up to whole 512B chunks
            flat = jax.lax.bitcast_convert_type(
                local_words, jnp.uint8
            ).reshape(-1)
            flat = jnp.pad(flat, (0, k * shard - total))
            data = flat.reshape(k, shard)
            from tpudfs.tpu.rs_pallas import rs_encode_device

            parity = rs_encode_device(data, k, m)
            shards = jnp.concatenate([data, parity])  # (k+m, shard)
            # Per-chunk CRCs of every shard, computed on the SENDER.
            swords = jax.lax.bitcast_convert_type(
                shards.reshape(k + m, -1, 4), jnp.uint32
            ).reshape(k + m, -1, WORDS_PER_CHUNK)
            sent_crcs = jax.vmap(crc32c_chunks_device)(swords)  # (k+m, C')
            received = []
            recv_crcs = []
            for j in range(k + m):
                perm = [(i, (i + j) % n) for i in range(n)]
                received.append(jax.lax.ppermute(swords[j], axis, perm))
                recv_crcs.append(jax.lax.ppermute(sent_crcs[j], axis, perm))
            stacked = jnp.stack(received)        # (k+m, C', 128)
            expected = jnp.stack(recv_crcs)      # (k+m, C')
            actual = jax.vmap(crc32c_chunks_device)(stacked)
            ok = jnp.all(actual == expected)
            acks = jax.lax.psum(ok.astype(jnp.int32), _all_axes(mesh))
            return stacked, ok[None], acks

        spec = P(_all_axes(mesh))
        return jax.jit(shard_map(
            step, mesh=mesh, in_specs=(spec,),
            out_specs=(spec, spec, P()), check_vma=False,
        ))

    def scatter(self, words: jax.Array):
        """words: (N*C, 128) uint32 sharded over every mesh axis (N =
        total devices). Returns (shards, ok, acks): shards
        (N*(k+m), C', 128) — within each ring, device d's group holds
        shard j of host (d - j) mod ring_size at row j — per-host verify
        bit, and the mesh-wide psum'd ack count."""
        return self._fn(words)


class EcShardGather:
    """Pod-level degraded read — the inverse of EcShardScatter: each host
    ``ppermute``-gathers its codeword's k+m shards back over ICI and
    RS-decodes around a FAILED device entirely on the accelerators (the
    host path would fetch surviving shards over gRPC and decode on CPU,
    client.py _read_ec_block / reference mod.rs:1110-1165).

    Which shard index the failed device held differs PER HOST (device
    (i+j) mod n holds host i's shard j), so every host needs a different
    decode matrix — incompatible with compile-time constants inside one
    SPMD program. The matrices are therefore computed host-side per
    failure pattern and ride in as sharded (n, k, k+m)/(n, k, k) inputs,
    applied on device by the runtime bit-plane GF matmul
    (rs_pallas.gf_matmul_runtime): ONE compiled program serves every
    failure pattern, including none."""

    def __init__(self, mesh: Mesh, k: int, m: int, axis: str | None = None):
        self.axis = _ring_axis(mesh, axis)
        n = mesh.shape[self.axis]
        if mesh.devices.size > 1 and k + m > n:
            # Same guard as EcShardScatter: on a smaller ring a single
            # device holds MULTIPLE shards of one codeword, so one failure
            # exceeds what excluding one shard index can repair.
            raise ValueError(f"RS({k},{m}) gather needs {k + m} ring "
                             f"devices, axis has {n}")
        self.mesh = mesh
        self.k, self.m = k, m
        self._fn = self._build()
        #: failed-index -> sharded (n, k, k+m) matrix, cached on device so
        #: repeat degraded reads around the same failure are transfer-free.
        self._mats: dict[int | None, jax.Array] = {}

    def _matrices(self, failed: int | None) -> jax.Array:
        """Per-host (k, k+m) decode-and-select matrices, on device: the
        decode inverse composed with the one-hot survivor selection
        (column j gets dec's column for present-rank of j; excluded shard
        columns stay zero, so garbage from the failed device is ignored
        by the GF multiply itself)."""
        cached = self._mats.get(failed)
        if cached is not None:
            return cached
        from tpudfs.tpu.rs_pallas import decode_matrix

        n = self.mesh.shape[self.axis]  # ring size
        total = self.mesh.devices.size
        k, m = self.k, self.m
        # One matrix per device, by its RING position (flat_index % n —
        # valid because _ring_axis pins the ring to the last mesh axis);
        # ``failed`` names a ring position, i.e. that position in EVERY
        # data-parallel group loses its shards.
        mats = np.zeros((total, k, k + m), dtype=np.uint8)
        for idx in range(total):
            i = idx % n
            j0 = (failed - i) % n if failed is not None else None
            present = [j for j in range(k + m) if j != j0][:k]
            dec = decode_matrix(k, m, tuple(present))
            for rank, j in enumerate(present):
                mats[idx, :, j] = dec[:, rank]
        out = jax.device_put(
            jnp.asarray(mats),
            NamedSharding(self.mesh, P(_all_axes(self.mesh))),
        )
        self._mats[failed] = out
        return out

    def _build(self):
        from tpudfs.tpu.rs_pallas import gf_matmul_runtime

        axis, k, m = self.axis, self.k, self.m
        mesh = self.mesh
        n = mesh.shape[axis]

        @jax.named_scope("tpudfs.ec_gather")
        def step(local_shards, mats):
            # local_shards: (k+m, S, 128) — row j = shard j of host
            # (d - j) mod n. Send row j back to its owner: src -> src - j.
            received = []
            for j in range(k + m):
                perm = [(s, (s - j) % n) for s in range(n)]
                received.append(
                    jax.lax.ppermute(local_shards[j], axis, perm)
                )
            rows = jnp.stack(received)  # (k+m, S, 128): MY codeword
            S = rows.shape[1]
            data = gf_matmul_runtime(
                mats[0], rows.reshape(k + m, S * WORDS_PER_CHUNK)
            )
            return data.reshape(k, S, WORDS_PER_CHUNK)

        spec = P(_all_axes(mesh))
        return jax.jit(shard_map(
            step, mesh=mesh, in_specs=(spec, spec),
            out_specs=spec, check_vma=False,
        ))

    def gather(self, shards: jax.Array, failed: int | None = None) -> jax.Array:
        """``shards``: EcShardScatter's (N*(k+m), S, 128) layout (N =
        total devices). Returns (N*k, S, 128): each host's k
        reconstructed DATA shards, bit-exact with its original encoding
        even when ring position ``failed``'s rows are garbage in every
        data-parallel group (one loss per ring is within RS(k,m>=1)
        tolerance)."""
        if failed is not None and self.mesh.devices.size == 1:
            # A 1-device mesh holds EVERY shard of the codeword on the
            # "failed" device — excluding one shard index there decodes
            # from rows the caller just declared garbage. n=1 is the
            # replication-degenerate layout; only failed=None is sound.
            raise ValueError(
                "failed=<index> is meaningless on a 1-device mesh: the "
                "single device holds every shard of the codeword"
            )
        return self._fn(shards, self._matrices(failed))


def replicated_write_step(mesh: Mesh, replication: int = 3,
                          ec: tuple[int, int] | None = None):
    """The full distributed data-plane step used by ``dryrun_multichip``:
    chain-replicate each host's chunk batch over ICI, verify every received
    replica on-device, optionally RS-encode local parity shards, and psum the
    ack count — the TPU-native equivalent of one pipeline-replicated
    WriteBlock round."""
    replicator = IciReplicator(mesh, replication)
    parity_fn = None
    if ec is not None:
        k, m = ec
        # Built (and jitted) once — rebuilding inside step() would miss the
        # jit cache and recompile the RS-parity shard_map on every call.
        parity_fn = jax.jit(
            shard_map(
                lambda w: _parity_of_words(w, k, m),
                mesh=mesh,
                in_specs=P(tuple(mesh.axis_names)),
                out_specs=P(tuple(mesh.axis_names)),
                check_vma=False,
            )
        )

    def step(words: jax.Array, crcs: jax.Array):
        replicas, ok, acks = replicator.replicate(words, crcs)
        out = {"replicas": replicas, "ok": ok, "acks": acks}
        if parity_fn is not None:
            out["parity"] = parity_fn(words)
        return out

    return step
