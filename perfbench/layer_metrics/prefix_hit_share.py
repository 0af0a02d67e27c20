"""Admissions served from the prefix cache over all admissions: the
program's ``serving/prefix_cache_hits_total`` and ``..._misses_total``."""


def read(ctx):
    hits = ctx.counters.get("serving/prefix_cache_hits_total", 0.0)
    misses = ctx.counters.get("serving/prefix_cache_misses_total", 0.0)
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
