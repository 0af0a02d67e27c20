"""Device time of the decode-chunk program over the decode steps it ran
(calls x the chunk's length, which the configuration file states)."""

from perfbench.layer_metrics import _common


def read(ctx):
    seconds, calls = _common.program_total(ctx, _common.DECODE_PROGRAM)
    chunk = ctx.cell.config.get("serving", {}).get("decode_chunk")
    if not calls or not chunk:
        return None
    return 1e3 * seconds / (calls * int(chunk))
