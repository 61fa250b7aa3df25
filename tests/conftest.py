"""Test harness config.

- Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding
  (mesh/pjit/shard_map) is exercised without TPU hardware, per the reference
  test strategy of model-level multi-node simulation (SURVEY.md §4 tier 2).
- Runs ``async def`` tests via asyncio.run (no pytest-asyncio in this image).
"""

import asyncio
import inspect
import os

# Tests never take an accelerator: the CPU is forced both in the
# environment (for children) and in jax.config (for this process, in case
# jax was imported before this file). Backends initialize lazily, so both
# land before the first jax.devices() call; XLA_FLAGS is read then too.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORM_NAME"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Short-circuit local reads default OFF in tests: every MiniCluster
# chunkserver shares the test host's filesystem, so the fast path would
# silently reroute reads off disk and bypass the RPC machinery that
# chaos/failover/cache tests exist to exercise. Short-circuit tests opt in
# with Client(..., local_reads=True).
os.environ.setdefault("TPUDFS_LOCAL_READS", "0")

# Build (no-op when fresh) and load the native library once, up front.
# get_lib() itself never runs make — it must stay safe to call from event
# loops — so the test session is the synchronous context that guarantees an
# edited native/*.cc is recompiled before anything dlopens a stale .so.
from tpudfs.common import native  # noqa: E402

native.build_and_load()


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=120))
        return True
    return None
