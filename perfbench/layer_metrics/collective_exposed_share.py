"""The part of ``collective_share`` during which no other operation ran on
that device."""

from perfbench.layer_metrics import collective_share


def read(ctx):
    return collective_share.read(ctx, exposed=True)
