"""Share of the chunkservers' streamed-write stage time that is disk:
``Stats`` -> ``stream_stages`` disk ns over (net + crc + disk + fanout) ns,
delta over the window, summed over chunkservers."""

STAGES = ("net_ns", "crc_ns", "disk_ns", "fanout_ns")


def read(win):
    deltas = [win.delta(f"cs.stream_stages.{k}") for k in STAGES]
    if any(d is None for d in deltas) or sum(deltas) <= 0:
        return None
    return 100.0 * deltas[2] / sum(deltas)
