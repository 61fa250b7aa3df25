"""Milliseconds a put spends in calls to the master service (create,
allocate, complete: each a Raft round), from the ``master_rpc`` spans of
the benchmark's own RpcClient, summed per put, mean over the window."""


def per_put_master_ms(win) -> dict[int, float]:
    by_op: dict[int, float] = {}
    for name, op, start, end in win.ctx.spans.rows:
        if name == "master_rpc" and op is not None:
            by_op[op] = by_op.get(op, 0.0) + (end - start) / 1e6
    return by_op


def read(win):
    by_op = per_put_master_ms(win)
    puts = [o for o in win.ops if o.ok]
    if not puts:
        return None
    return sum(by_op.get(o.what[0], 0.0) for o in puts) / len(puts)
