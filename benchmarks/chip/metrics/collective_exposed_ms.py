"""collective_exposed_ms: per traced round, the device time of
collective operations (all-gather, all-reduce, reduce-scatter,
collective-permute, all-to-all, send/recv) during which that device runs
no other operation; the most of any device of the cell."""


def read(ctx):
    if not ctx.trace.devices or not ctx.sels or ctx.chips < 2:
        return None
    return 1e3 * max(ctx.trace.exposed_collective_s()) / len(ctx.sels)
