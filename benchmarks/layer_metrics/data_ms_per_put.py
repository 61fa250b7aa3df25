"""Milliseconds a put spends outside its master calls — the block write
through the chunkserver chain or the write group — as put latency less its
``master_rpc`` spans, mean over the window."""

from benchmarks.layer_metrics.master_ms_per_put import per_put_master_ms


def read(win):
    by_op = per_put_master_ms(win)
    puts = [o for o in win.ops if o.ok]
    if not puts:
        return None
    return sum(o.ms - by_op.get(o.what[0], 0.0) for o in puts) / len(puts)
