"""WebDataset-on-DFS training loop (BASELINE config 5, the WDS half).

DFS tar shards -> DfsWdsSource (tar-header index, per-member range reads)
-> ``grain_infeed.make_dataset`` (shuffle, decode map, prefetch, batch) ->
sharded device batches -> pjit'd SGD on a small MLP classifier. Asserts the model actually LEARNS
(train accuracy) — the bytes reaching the accelerators are the right
samples with the right labels, through tar framing, DFS striping, and
3x replication.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from tests.test_master_service import MiniCluster
from tpudfs.client.client import Client

FEATURES = 32
CLASSES = 4
SAMPLES = 512
BATCH = 64


def _make_samples(rng, centers):
    for i in range(SAMPLES):
        cls = int(rng.integers(0, CLASSES))
        x = (centers[cls] + 0.3 * rng.normal(size=FEATURES)).astype(
            np.float32
        )
        yield {"__key__": f"{i:06d}", "img": x.tobytes(),
               "cls": str(cls).encode()}


async def test_wds_training_loop_learns(tmp_path):
    pytest.importorskip("grain")
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudfs.tpu import grain_infeed as gi
    from tpudfs.tpu.wds import DfsWdsSource, decode_sample, write_wds_shards

    rng = np.random.default_rng(42)
    centers = rng.normal(size=(CLASSES, FEATURES)).astype(np.float32) * 2.0

    c = MiniCluster(tmp_path, n_masters=1, n_cs=3)
    await c.start()
    try:
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        client = Client(list(c.masters), rpc_client=c.client,
                        block_size=64 * 1024)
        shards = await write_wds_shards(
            client, "/wds/train", _make_samples(rng, centers),
            shard_size_bytes=96 * 1024,  # several shards, several blocks
        )
        assert len(shards) >= 2, "want a multi-shard dataset"

        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        xsh = NamedSharding(mesh, P("data"))
        repl = NamedSharding(mesh, P())

        @jax.jit
        def step(params, x, y):
            def loss_fn(p):
                h = jax.nn.relu(x @ p["w1"])
                logits = h @ p["w2"]
                onehot = jax.nn.one_hot(y, CLASSES)
                return -jnp.mean(
                    jnp.sum(jax.nn.log_softmax(logits) * onehot, -1)
                )

            loss, g = jax.value_and_grad(loss_fn)(params)
            return (
                jax.tree.map(lambda p, gg: p - 0.3 * gg, params, g),
                loss,
            )

        def run_training():
            # Built and driven in a worker thread: the in-process cluster
            # serves on the MAIN event loop, which must stay unblocked.
            source = DfsWdsSource(list(c.masters), shards)
            try:
                assert len(source) == SAMPLES
                # Spot-check tar framing end-to-end.
                s0 = source[0]
                assert s0["__key__"] == "000000"
                x0, y0 = decode_sample(s0, image_shape=(FEATURES,))
                assert x0.shape == (FEATURES,) and 0 <= int(y0) < CLASSES

                ds = gi.make_dataset(
                    source, batch_size=BATCH, shuffle_seed=7,
                    decode=lambda s: decode_sample(
                        s, image_shape=(FEATURES,)))

                k1, k2 = jax.random.split(jax.random.PRNGKey(0))
                params = {
                    "w1": jax.device_put(
                        jax.random.normal(k1, (FEATURES, 64)) * 0.1, repl),
                    "w2": jax.device_put(
                        jax.random.normal(k2, (64, CLASSES)) * 0.1, repl),
                }
                first = last = None
                for _epoch in range(6):
                    for xb, yb in ds:
                        x = jax.device_put(jnp.asarray(xb), xsh)
                        y = jax.device_put(jnp.asarray(yb), xsh)
                        params, loss = step(params, x, y)
                        if first is None:
                            first = float(loss)
                        last = float(loss)

                # Accuracy on a fresh pass: labels rode the tar members.
                correct = total = 0
                for xb, yb in ds:
                    h = jax.nn.relu(jnp.asarray(xb) @ params["w1"])
                    pred = jnp.argmax(h @ params["w2"], axis=-1)
                    correct += int(jnp.sum(pred == jnp.asarray(yb)))
                    total += len(yb)
                return first, last, correct, total
            finally:
                source.close()

        first, last, correct, total = await asyncio.to_thread(run_training)
        assert first is not None and last < first / 3, (first, last)
        assert correct / total > 0.9, f"accuracy {correct}/{total}"
    finally:
        await c.stop()


async def test_wds_writer_validation_and_multipart_ext(tmp_path):
    """USTAR discipline is enforced at write time (dotted keys, >100-char
    names rejected); multi-part extensions round-trip whole."""
    from tpudfs.tpu.wds import DfsWdsSource, write_wds_shards

    c = MiniCluster(tmp_path, n_masters=1, n_cs=3)
    await c.start()
    try:
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        client = Client(list(c.masters), rpc_client=c.client,
                        block_size=64 * 1024)
        with pytest.raises(ValueError, match="must not contain"):
            await write_wds_shards(client, "/wds/bad",
                                   [{"__key__": "a.b", "img": b"x"}])
        with pytest.raises(ValueError, match="USTAR"):
            await write_wds_shards(client, "/wds/bad2",
                                   [{"__key__": "k" * 101, "img": b"x"}])
        shards = await write_wds_shards(client, "/wds/mp", [
            {"__key__": "000", "img": b"A" * 100, "seg.png": b"B" * 50},
            {"__key__": "001", "img": b"C" * 100, "seg.png": b"D" * 50},
        ])

        def check():
            source = DfsWdsSource(list(c.masters), shards)
            try:
                assert len(source) == 2
                s0, s1 = source[0], source[1]
                assert s0["__key__"] == "000" and s0["seg.png"] == b"B" * 50
                assert s1["__key__"] == "001" and s1["img"] == b"C" * 100
            finally:
                source.close()

        await asyncio.to_thread(check)
    finally:
        await c.stop()


async def test_wds_shards_on_ec_files(tmp_path):
    """WDS shards stored ERASURE-CODED (RS(2,1)) read back sample-exact —
    the tar indexer and per-sample range reads ride the EC read path."""
    from tpudfs.tpu.wds import DfsWdsSource, write_wds_shards

    c = MiniCluster(tmp_path, n_masters=1, n_cs=3)
    await c.start()
    try:
        leader = await c.leader()
        await c.wait_out_of_safe_mode(leader)
        client = Client(list(c.masters), rpc_client=c.client,
                        block_size=64 * 1024)
        rng = np.random.default_rng(5)
        payloads = [rng.integers(0, 256, 3000, dtype=np.uint8).tobytes()
                    for _ in range(40)]
        shards = await write_wds_shards(
            client, "/wds/ec",
            ({"__key__": f"{i:06d}", "img": p, "cls": b"1"}
             for i, p in enumerate(payloads)),
            shard_size_bytes=48 * 1024, ec=(2, 1),
        )
        meta = await client.get_file_info(shards[0])
        assert meta["blocks"][0].get("ec_data_shards") == 2  # really EC

        def check():
            source = DfsWdsSource(list(c.masters), shards)
            try:
                assert len(source) == len(payloads)
                for i in (0, 7, len(payloads) - 1):
                    s = source[i]
                    assert s["__key__"] == f"{i:06d}"
                    assert s["img"] == payloads[i]
            finally:
                source.close()

        await asyncio.to_thread(check)
    finally:
        await c.stop()
