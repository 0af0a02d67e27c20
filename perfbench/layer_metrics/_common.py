"""Helpers several readers share. A reader is ``read(ctx) -> float | None``
where ``ctx`` is ``harness.LayerContext``; ``None`` leaves the metric out."""

from __future__ import annotations

import re

from perfbench import xplane

LEARN_PROGRAMS = re.compile(r"jit_(logprobs|update)$")
NOGRAD_PROGRAMS = re.compile(r"jit_logprobs$")
DECODE_PROGRAM = re.compile(r"decode_chunk")
GENERATION_PROGRAM = re.compile(r"jit_generation$")


def program_total(ctx, pattern):
    """(device seconds, calls) of the programs whose name matches."""
    lo, hi = ctx.trace.window
    found = [v for k, v in xplane.program_seconds(ctx.trace, lo, hi).items()
             if pattern.search(k)]
    return sum(s for s, _ in found), sum(c for _, c in found)


def idle_share(ctx):
    lo, hi = ctx.trace.window
    return 100.0 * (1.0 - xplane.busy_seconds(ctx.trace, lo, hi) * 1e9 / (hi - lo))


def peak_gib(ctx):
    return ctx.peak_bytes / 2 ** 30
