"""Device time in all-gather / reduce-scatter / all-reduce (and other
collective) operations over the device's busy time in the window."""

from perfbench import xplane


def read(ctx, exposed=False):
    lo, hi = ctx.trace.window
    busy = xplane.busy_seconds(ctx.trace, lo, hi)
    total, alone = xplane.collective_seconds(ctx.trace, lo, hi)
    return 100.0 * (alone if exposed else total) / busy if busy else None
