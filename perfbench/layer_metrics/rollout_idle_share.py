"""Device idle time inside the ``get_action`` spans: the host between
decode chunks, admission, the sampler's bookkeeping."""

from perfbench import xplane


def read(ctx):
    spans = [(s.start, s.end) for s in ctx.trace.host if s.name == "get_action"]
    if not spans:
        return None
    busy, total = xplane.busy_seconds_within(ctx.trace, spans)
    return 100.0 * (1.0 - busy / total)
