"""Pin for the bench's ICI microbench entry points.

BENCH_r05 and BENCH_r06 recorded a 2x swing in ici_write /
ici_ec_scatter on byte-identical kernels: both ran on the CPU backend,
where these microbenches measure one core's emulated collectives and
move with machine state — not a device number and not a code change.

This test pins what CAN regress in code: the exact bench entry points
must keep producing verified replicas/acks and per-window samples, so a
future real kernel break (or a bytes-accounting drift that would skew
cross-round GB/s comparisons) fails loudly instead of hiding inside
host noise.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import bench


def test_ici_bench_steps_stay_verified(monkeypatch):
    # Shrink the payload/rep counts: this pins semantics, not speed.
    monkeypatch.setattr(bench, "ICI_STEP_MB", 1)
    monkeypatch.setattr(bench, "ICI_REPS", 2)
    monkeypatch.setattr(bench, "REPS", 2)
    device = jax.devices()[0]

    samples, oks = bench._bench_ici_write_step(device)
    assert len(samples) == bench.REPS
    assert all(s > 0 for s in samples)
    # Same assertion the bench run makes after its verdict fetch: every
    # round's on-device CRC verify of all 3 replicas must pass.
    assert np.asarray(oks).all()
    assert np.asarray(oks).size == bench.REPS * bench.ICI_REPS

    ec_samples, ec_acks = bench._bench_ec_scatter_step(device)
    assert len(ec_samples) == bench.REPS
    assert all(s > 0 for s in ec_samples)
    assert (np.asarray(ec_acks) == 1).all()
