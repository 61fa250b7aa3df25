"""Subprocess helpers for multi-process cluster harnesses.

Shared by scripts/start_cluster.py and chip_smoke.py (the reference drives
the same need with start_cluster.sh + docker-compose): spawn service entry
points as real OS processes, redirect their output to per-process logs, and
poll for the ``READY <addr>`` line each tpudfs ``__main__`` prints once its
sockets are bound.
"""

from __future__ import annotations

import os
import pathlib
import socket
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent.parent


#: Servers put their ops HTTP listener at rpc port + this by default
#: (``--http-port -1``, common/ops_http.py maybe_start_ops).
OPS_PORT_OFFSET = 1000


def ops_twin_free(port: int) -> bool:
    """Whether ``port``'s ops-HTTP twin is a valid port nobody holds."""
    twin = port + OPS_PORT_OFFSET
    if twin > 65535:
        return False
    with socket.socket() as s:
        try:
            s.bind(("127.0.0.1", twin))
        except OSError:
            return False
    return True


def free_port() -> int:
    """A free port for a server's rpc listener whose ops twin can be
    bound too. Where the kernel hands out ephemeral ports up to 65535
    (``ip_local_port_range``), a bare bind-to-0 pick lands above 64535
    about once in thirty, and that server dies at start-up in
    ``bind(): port must be 0-65535``. Rejected picks stay bound until
    the search ends so the kernel cannot hand them out again."""
    rejected: list[socket.socket] = []
    try:
        while True:
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            if ops_twin_free(port):
                s.close()
                return port
            rejected.append(s)
    finally:
        for s in rejected:
            s.close()


# Bound at import: preexec_fn runs between fork and exec, where imports or
# dlopen in a multithreaded parent (JAX starts threads) can deadlock the
# child — the post-fork hook must only CALL the pre-resolved symbol.
try:
    import ctypes as _ctypes

    _PRCTL = _ctypes.CDLL(None).prctl
except (OSError, AttributeError):  # non-Linux / no libc — best-effort only
    _PRCTL = None


def _die_with_parent() -> None:
    """PR_SET_PDEATHSIG: the kernel SIGTERMs the child when its parent dies.
    A SIGKILLed harness (driver timeout) never runs atexit/terminate_all —
    without this, orphaned master/chunkserver processes keep time-sharing
    the single bench core for hours and silently poison later benchmarks."""
    if _PRCTL is not None:
        _PRCTL(1, 15)  # PR_SET_PDEATHSIG=1, SIGTERM=15


def spawn(procs: list[subprocess.Popen], name: str, logdir: pathlib.Path,
          mod: str, *args: str, env: dict | None = None) -> subprocess.Popen:
    """Start ``python -m mod`` appended to ``procs``, stdout+stderr to
    ``logdir/name.log``. The child dies with this process (PDEATHSIG)."""
    with open(logdir / f"{name}.log", "w") as log:
        p = subprocess.Popen(
            [sys.executable, "-m", mod, *args],
            env={**os.environ, "PYTHONPATH": str(REPO), **(env or {})},
            stdout=log, stderr=subprocess.STDOUT,
            preexec_fn=_die_with_parent,
        )
    procs.append(p)
    return p


def wait_ready(logdir: pathlib.Path, name: str, timeout: float = 60.0) -> None:
    deadline = time.time() + timeout
    path = logdir / f"{name}.log"
    while time.time() < deadline:
        if path.exists() and "READY" in path.read_text():
            return
        time.sleep(0.2)
    raise RuntimeError(f"{name} failed to start; see {path}")


def terminate_all(procs: list[subprocess.Popen], grace: float = 5.0) -> None:
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + grace
    for p in procs:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if p.poll() is None:
            p.kill()
