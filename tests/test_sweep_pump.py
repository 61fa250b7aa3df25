"""The native sweep pump's producer team (native/blockio.cc, PR 30), driven
through its five C calls on temp files: several threads fill a round's
blocks, and the consumer's contract is the single producer's. Every call
that can block runs under ``bounded``: a lost wake-up fails its case
instead of hanging the run."""

import ctypes
import errno
import os
import threading
import time

import numpy as np
import pytest

from tpudfs.common import native

BLOCK = 64 * 1024
POISON = 0xEE
MAX_PRODUCERS = 8  # kSweepProducers


@pytest.fixture(scope="module")
def lib():
    lib = native.get_lib()
    if lib is None or not hasattr(lib, "tpudfs_sweep_info"):
        pytest.skip("native library without the sweep pump")
    return lib


def bounded(fn, *args, timeout=20.0):
    """``fn(*args)`` on a daemon thread: its result, or a failure when it
    has not returned in ``timeout`` seconds."""
    box = []
    t = threading.Thread(target=lambda: box.append(fn(*args)), daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), f"{fn.__name__}{args[1:]} still blocked " \
        f"after {timeout} s"
    return box[0]


def write_files(tmp_path, n, size=BLOCK, seed=0):
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n):
        p = tmp_path / f"blk{i:05d}"
        p.write_bytes(rng.bytes(size))
        paths.append(str(p).encode())
    return paths


def reference(lib, paths, stride):
    """tpudfs_blocks_read_crc over the same paths: bytes, sizes, crcs."""
    n = len(paths)
    out = np.zeros(n * stride, dtype=np.uint8)
    sizes = np.zeros(n, dtype=np.int64)
    crcs = np.zeros(n, dtype=np.uint32)
    lib.tpudfs_blocks_read_crc((ctypes.c_char_p * n)(*paths), n, stride,
                               out.ctypes.data, sizes.ctypes.data,
                               crcs.ctypes.data)
    return out.reshape(n, stride), sizes, crcs


class Sweep:
    """One pump over ``paths``; owns what the C side borrows until stop."""

    def __init__(self, lib, paths, *, stride=BLOCK, round_blocks, nbufs):
        self.lib, self.n, self.stride = lib, len(paths), stride
        self.round_blocks = round_blocks
        self.nrounds = -(-self.n // round_blocks)
        self.bufs = [np.full(round_blocks * stride, POISON, dtype=np.uint8)
                     for _ in range(nbufs)]
        self.sizes = np.full(self.n, -1, dtype=np.int64)
        self.crcs = np.zeros(self.n, dtype=np.uint32)
        self._paths = (ctypes.c_char_p * self.n)(*paths)
        self._bufs = (ctypes.c_void_p * nbufs)(
            *(b.ctypes.data for b in self.bufs))
        self.handle = lib.tpudfs_sweep_start(
            self._paths, self.n, stride, round_blocks, self._bufs, nbufs,
            self.sizes.ctypes.data, self.crcs.ctypes.data)
        assert self.handle

    def wait(self, r):
        return bounded(self.lib.tpudfs_sweep_wait, self.handle, r)

    def round(self, r, nblk):
        """A copy of round r's slots, as the consumer sees them now."""
        buf = self.bufs[r % len(self.bufs)]
        return buf[:nblk * self.stride].reshape(nblk, self.stride).copy()

    def release(self, r):
        # What the next round does not write must not look like data.
        self.bufs[r % len(self.bufs)][:] = POISON
        self.lib.tpudfs_sweep_release(self.handle, r)

    def info(self):
        out = np.zeros(2, dtype=np.int64)
        self.lib.tpudfs_sweep_info(self.handle, out.ctypes.data)
        return int(out[0]), int(out[1])

    def stop(self):
        if self.handle:
            bounded(self.lib.tpudfs_sweep_stop, self.handle)
            self.handle = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


def consume_in_order(sweep, want):
    """wait -> compare with the reference at once -> release, round by
    round: what ``tpudfs_sweep_wait`` returned for must already be final."""
    ref_bytes, ref_sizes, ref_crcs = want
    rb = sweep.round_blocks
    for r in range(sweep.nrounds):
        lo = r * rb
        nblk = sweep.wait(r)
        assert nblk == min(rb, sweep.n - lo)
        got = sweep.round(r, nblk)
        assert (sweep.sizes[lo:lo + nblk] == ref_sizes[lo:lo + nblk]).all()
        assert (sweep.crcs[lo:lo + nblk] == ref_crcs[lo:lo + nblk]).all()
        for j in range(nblk):
            size = max(int(ref_sizes[lo + j]), 0)
            assert (got[j, :size] == ref_bytes[lo + j, :size]).all(), \
                f"round {r} slot {j} not final when the wait returned"
        sweep.release(r)


@pytest.mark.parametrize("n,round_blocks,size", [
    (32, 8, BLOCK),      # a multiple of round_blocks
    (37, 8, BLOCK),      # a short last round
    (3, 8, BLOCK),       # fewer blocks than producers
    (1, 8, BLOCK),
    (5, 1, BLOCK),       # one-slot rounds: one producer
    (1500, 16, 4096),    # many small blocks: the cursor and the counts
])
def test_team_matches_blocks_read_crc(lib, tmp_path, n, round_blocks, size):
    paths = write_files(tmp_path, n, size=size, seed=n)
    want = reference(lib, paths, size)
    assert (want[1] == size).all()
    with Sweep(lib, paths, stride=size, round_blocks=round_blocks,
               nbufs=3) as sweep:
        producers, _ = sweep.info()
        assert 1 <= producers <= min(MAX_PRODUCERS, round_blocks)
        consume_in_order(sweep, want)


@pytest.mark.parametrize("nbufs", [1, 2, 3])
def test_round_is_final_when_wait_returns(lib, tmp_path, nbufs):
    paths = write_files(tmp_path, 37, seed=nbufs)
    want = reference(lib, paths, BLOCK)
    with Sweep(lib, paths, round_blocks=4, nbufs=nbufs) as sweep:
        consume_in_order(sweep, want)


def test_missing_and_short_file_fail_their_slot_only(lib, tmp_path):
    paths = write_files(tmp_path, 20, seed=7)
    os.unlink(paths[5])
    with open(paths[11], "r+b") as f:
        f.truncate(BLOCK // 2 + 13)
    want = reference(lib, paths, BLOCK)
    with Sweep(lib, paths, round_blocks=8, nbufs=2) as sweep:
        consume_in_order(sweep, want)
        assert sweep.sizes[5] == -errno.ENOENT
        assert sweep.sizes[11] == BLOCK // 2 + 13
        ok = np.ones(20, dtype=bool)
        ok[[5, 11]] = False
        assert (sweep.sizes[ok] == BLOCK).all()


@pytest.mark.parametrize("producers_are", ["parked", "running"])
def test_stop_mid_sweep_returns_and_nothing_writes_after(lib, tmp_path,
                                                         producers_are):
    paths = write_files(tmp_path, 64, seed=9)
    sweep = Sweep(lib, paths, round_blocks=4, nbufs=2)
    try:
        if producers_are == "parked":
            # Both ring buffers full and none released: every producer
            # waits on the gate.
            assert sweep.wait(1) == 4
            time.sleep(0.05)
    finally:
        sweep.stop()
    for buf in sweep.bufs:
        buf[:] = POISON
    filled = sweep.sizes.copy()
    time.sleep(0.1)
    assert all((buf == POISON).all() for buf in sweep.bufs)
    assert (sweep.sizes == filled).all()
    assert (sweep.sizes[8:] == -1).all(), "a block past the ring was read"


def test_out_of_order_release(lib, tmp_path):
    paths = write_files(tmp_path, 36, seed=11)
    want = reference(lib, paths, BLOCK)
    with Sweep(lib, paths, round_blocks=4, nbufs=3) as sweep:
        for r in range(3):
            assert sweep.wait(r) == 4
            assert (sweep.round(r, 4) == want[0][4 * r:4 * r + 4]).all()
        # Rounds 1 and 2 go back first: round 3 wants round 0's buffer,
        # so the gate stays shut and nothing is written.
        sweep.release(2)
        sweep.release(1)
        sweep.bufs[0][:] = POISON
        time.sleep(0.1)
        assert (sweep.bufs[0] == POISON).all()
        assert (sweep.sizes[12:] == -1).all()
        sweep.release(0)
        for r in range(3, 6):
            assert sweep.wait(r) == 4
            assert (sweep.round(r, 4) == want[0][4 * r:4 * r + 4]).all()
        for r in (5, 3, 4):
            sweep.release(r)
        for r in range(6, 9):
            assert sweep.wait(r) == 4
            assert (sweep.round(r, 4) == want[0][4 * r:4 * r + 4]).all()
        assert (sweep.sizes == want[1]).all()
        assert (sweep.crcs == want[2]).all()


def test_info_counts_waits_that_found_their_round_produced(lib, tmp_path):
    paths = write_files(tmp_path, 12, seed=13)
    with Sweep(lib, paths, round_blocks=4, nbufs=3) as sweep:
        assert sweep.info()[1] == 0
        sweep.wait(2)  # may block; the whole ring is produced after it
        for r in (0, 1, 2):
            sweep.wait(r)
        producers, ready = sweep.info()
        cores = os.cpu_count() or 1
        if cores == len(os.sched_getaffinity(0)):  # no doubt what C++ sees
            assert producers == min(MAX_PRODUCERS, 4, max(1, cores - 1))
        assert 1 <= producers <= 4
        assert ready in (3, 4)
