"""ctypes loader for the native C++ hot-path library (native/libtpudfs_native.so).

The native library carries the byte-crunching inner loops the reference
implements in Rust (crc32fast checksums, reed-solomon-erasure GF(2^8) math —
see SURVEY.md §2.4). Pure-numpy fallbacks live next to each call site so the
framework still runs where the shared library can't be built.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from pathlib import Path

logger = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_NAME = "libtpudfs_native.so"

#: Guards _lib/_load_attempted/_build_attempted. get_lib runs on the event
#: loop while build_and_load runs on a to_thread worker, so this must be a
#: threading.Lock — and it is never held across the compiler (make runs
#: outside it), only across flag flips and the cheap dlopen.
_state_lock = threading.Lock()

_lib: ctypes.CDLL | None = None
_load_attempted = False
_build_attempted = False


def _try_build() -> bool:
    makefile = _NATIVE_DIR / "Makefile"
    if not makefile.exists():
        return False
    try:
        subprocess.run(
            ["make", "-C", str(_NATIVE_DIR)],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return True
    except (subprocess.SubprocessError, OSError) as e:
        logger.warning("native build failed: %s", e)
        return False


def build_and_load() -> ctypes.CDLL | None:
    """Invoke make (a no-op when the .so is newer than its sources, so an
    edited .cc is never shadowed by a stale binary), then load.

    This is the ONLY entry point that runs the compiler, and it blocks for
    up to two minutes on a cold build: call it from synchronous entry
    points (benchmarks, the test session fixture) or from async code via
    ``await asyncio.to_thread(native.build_and_load)``. Everything on the
    event loop goes through :func:`get_lib`, which only ever mmaps an
    already-built library.
    """
    global _load_attempted, _build_attempted
    with _state_lock:
        need_build = _lib is None and not _build_attempted
        if need_build:
            _build_attempted = True
    if need_build and "TPUDFS_NATIVE_LIB" not in os.environ:
        if _try_build():
            with _state_lock:
                # A failed earlier load may now succeed against the fresh .so.
                _load_attempted = False
    return get_lib()


def get_lib() -> ctypes.CDLL | None:
    """Load the already-built native library, or None.

    Never builds — loading an existing .so is fast enough for the event
    loop, running make is not. Processes that want a guaranteed-fresh
    build warm up through :func:`build_and_load` first.
    """
    with _state_lock:
        return _locked_load()


def _locked_load() -> ctypes.CDLL | None:
    """Load + bind symbols. Callers hold ``_state_lock``."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    path = os.environ.get("TPUDFS_NATIVE_LIB", str(_NATIVE_DIR / _LIB_NAME))
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        logger.warning("native library unavailable (%s); using numpy fallbacks", e)
        return None

    lib.tpudfs_crc32c.restype = ctypes.c_uint32
    lib.tpudfs_crc32c.argtypes = [
        ctypes.c_uint32,
        ctypes.c_void_p,
        ctypes.c_size_t,
    ]
    lib.tpudfs_crc32c_chunks.restype = None
    lib.tpudfs_crc32c_chunks.argtypes = [
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_size_t,
        ctypes.c_void_p,
    ]
    lib.tpudfs_crc32c_contrib_table.restype = None
    lib.tpudfs_crc32c_contrib_table.argtypes = [
        ctypes.c_void_p,
        ctypes.c_size_t,
    ]
    try:
        lib.tpudfs_crc64nvme.restype = ctypes.c_uint64
        lib.tpudfs_crc64nvme.argtypes = [
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_size_t,
        ]
    except AttributeError:
        # Prebuilt library predating the CRC-64/NVME trailer support.
        pass
    try:
        lib.tpudfs_block_write.restype = ctypes.c_int64
        lib.tpudfs_block_write.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.c_void_p,
        ]
        lib.tpudfs_block_read_verify.restype = ctypes.c_int64
        lib.tpudfs_block_read_verify.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_int,
            ctypes.c_uint32,
        ]
    except AttributeError:
        # Prebuilt library (TPUDFS_NATIVE_LIB) predating the block I/O
        # engine: checksum/GF math still work, block ops use the fallback.
        logger.warning("native library has no block I/O engine; "
                       "using Python block path")
    try:
        lib.tpudfs_blocks_read.restype = ctypes.c_int64
        lib.tpudfs_blocks_read.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.tpudfs_blocks_read_crc.restype = ctypes.c_int64
        lib.tpudfs_blocks_read_crc.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
    except AttributeError:
        # Prebuilt library predating the batched read engine.
        pass
    try:
        lib.tpudfs_sweep_start.restype = ctypes.c_int64
        lib.tpudfs_sweep_start.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),  # paths
            ctypes.c_uint64,                  # n
            ctypes.c_uint64,                  # stride
            ctypes.c_uint64,                  # round_blocks
            ctypes.POINTER(ctypes.c_void_p),  # ring buffers
            ctypes.c_uint64,                  # nbufs
            ctypes.c_void_p,                  # sizes (int64*)
            ctypes.c_void_p,                  # crcs (uint32*)
        ]
        lib.tpudfs_sweep_wait.restype = ctypes.c_int64
        lib.tpudfs_sweep_wait.argtypes = [ctypes.c_int64, ctypes.c_int64]
        lib.tpudfs_sweep_release.restype = None
        lib.tpudfs_sweep_release.argtypes = [ctypes.c_int64, ctypes.c_int64]
        lib.tpudfs_sweep_stop.restype = None
        lib.tpudfs_sweep_stop.argtypes = [ctypes.c_int64]
        lib.tpudfs_sweep_info.restype = None
        lib.tpudfs_sweep_info.argtypes = [
            ctypes.c_int64,
            ctypes.c_void_p,                  # out (int64[2])
        ]
    except AttributeError:
        # Prebuilt library predating the sweep pump (or its producer team:
        # the sweep asks for tpudfs_sweep_info and falls back without it).
        pass
    try:
        lib.tpudfs_dataplane_stage_stats.restype = None
        lib.tpudfs_dataplane_stage_stats.argtypes = [
            ctypes.c_int64, ctypes.c_void_p,
        ]
    except AttributeError:
        # Prebuilt library predating write-stage budgets.
        pass
    try:
        lib.tpudfs_dataplane_stream_stats.restype = None
        lib.tpudfs_dataplane_stream_stats.argtypes = [
            ctypes.c_int64, ctypes.c_void_p,
        ]
    except AttributeError:
        # Prebuilt library predating the streaming write engine.
        pass
    try:
        lib.tpudfs_block_write_staged.restype = ctypes.c_int64
        lib.tpudfs_block_write_staged.argtypes = \
            list(lib.tpudfs_block_write.argtypes)
        lib.tpudfs_syncfs.restype = ctypes.c_int64
        lib.tpudfs_syncfs.argtypes = [ctypes.c_char_p]
    except AttributeError:
        # Prebuilt library predating group-commit staging; per-block
        # durable writes still work.
        pass
    try:
        # The dataplane ABI has changed arity across versions; a prebuilt
        # library (TPUDFS_NATIVE_LIB) that predates the current revision
        # must be rejected outright — hasattr alone would bind the old
        # symbols and call them with mismatched arguments.
        lib.tpudfs_dataplane_abi.restype = ctypes.c_int64
        lib.tpudfs_dataplane_abi.argtypes = []
        if lib.tpudfs_dataplane_abi() != 8:
            raise AttributeError("dataplane ABI mismatch")
        lib.tpudfs_dataplane_start.restype = ctypes.c_int64
        lib.tpudfs_dataplane_start.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint32, ctypes.c_uint16, ctypes.c_uint64,
            # TLS material: server cert/key, client-CA (mTLS), and the
            # outbound chain-forward CA + cert/key. Empty = plaintext.
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.tpudfs_dataplane_port.restype = ctypes.c_int32
        lib.tpudfs_dataplane_port.argtypes = [ctypes.c_int64]
        lib.tpudfs_dataplane_set_term.restype = None
        lib.tpudfs_dataplane_set_term.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.tpudfs_dataplane_term.restype = ctypes.c_uint64
        lib.tpudfs_dataplane_term.argtypes = [ctypes.c_int64,
                                              ctypes.c_char_p]
        lib.tpudfs_dataplane_take_bad.restype = ctypes.c_int64
        lib.tpudfs_dataplane_take_bad.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.tpudfs_dataplane_take_terms.restype = ctypes.c_int64
        lib.tpudfs_dataplane_take_terms.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.tpudfs_dataplane_invalidate.restype = None
        lib.tpudfs_dataplane_invalidate.argtypes = [
            ctypes.c_int64, ctypes.c_char_p,
        ]
        lib.tpudfs_dataplane_stats.restype = None
        lib.tpudfs_dataplane_stats.argtypes = [ctypes.c_int64,
                                               ctypes.c_void_p]
        # ABI 6: QoS admission plane — config push (msgpack flat map
        # from resilience.qos_wire_config), aggregate counters, and the
        # per-tenant take-style drain.
        lib.tpudfs_dataplane_set_qos.restype = None
        lib.tpudfs_dataplane_set_qos.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.tpudfs_dataplane_qos_stats.restype = None
        lib.tpudfs_dataplane_qos_stats.argtypes = [ctypes.c_int64,
                                                   ctypes.c_void_p]
        lib.tpudfs_dataplane_take_qos.restype = ctypes.c_int64
        lib.tpudfs_dataplane_take_qos.argtypes = [
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_uint64,
        ]
        # ABI 7: the read path's stage clocks (ChunkServer.read_stage_stats);
        # ABI 8 appends rbs_torn to them.
        lib.tpudfs_dataplane_read_stats.restype = None
        lib.tpudfs_dataplane_read_stats.argtypes = [ctypes.c_int64,
                                                    ctypes.c_void_p]
        lib.tpudfs_dataplane_stop.restype = ctypes.c_int64
        lib.tpudfs_dataplane_stop.argtypes = [ctypes.c_int64]
        _dataplane_ok = True
    except AttributeError:
        # Prebuilt library predating (or ABI-mismatching) the native
        # data-plane engine.
        _dataplane_ok = False
    lib.tpudfs_has_dataplane = _dataplane_ok
    lib.tpudfs_gf256_mul.restype = ctypes.c_uint8
    lib.tpudfs_gf256_mul.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
    lib.tpudfs_gf256_mul_slice.restype = None
    lib.tpudfs_gf256_mul_slice.argtypes = [
        ctypes.c_uint8,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_void_p,
    ]
    lib.tpudfs_gf256_matmul.restype = None
    lib.tpudfs_gf256_matmul.argtypes = [
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    _lib = lib
    return _lib


def have_native() -> bool:
    return get_lib() is not None


def has_dataplane() -> bool:
    """True when the loaded library carries the CURRENT data-plane ABI."""
    lib = get_lib()
    return lib is not None and getattr(lib, "tpudfs_has_dataplane", False)


def has_blockio() -> bool:
    """True when the loaded library carries the block I/O engine (an older
    prebuilt .so named via TPUDFS_NATIVE_LIB may predate it)."""
    lib = get_lib()
    return lib is not None and hasattr(lib, "tpudfs_block_write")
