"""The experts' grouped matmuls of a SmallThinker-class stack against their
roofline: the FLOPs the learn programs execute in them
(``counts_smallthinker.learn_grouped_matmul_flops``: the reference pass's
forward, the update's forward, remat's second forward and the backward with
respect to the rows — 4 forwards' worth since a call of one step dropped the
second no-grad pass, PR 34) over the device time under the scope
``moe/experts`` in the learn programs (``jit_logprobs``, ``jit_update``),
over the chips' bf16 peak. XLA:TPU's expansion of a grouped matmul drops the
scope from the operation's ``tf_op`` (it reads ``ragged-dot``), so the
operations are found by that name as well as by the scope, which still holds
the ReGLU's elementwise product between the matmuls; its time counts against
the share. Compute bounds it at these sizes (16384 x 6 rows over 64 experts
of 2560 x 768: 1536 rows an expert read its 11.8 MB once). Read from the
cell's own ``.xplane.pb`` (``_scopes``)."""

from perfbench import counts_smallthinker
from perfbench.layer_metrics import _common, _scopes


def read(ctx):
    path = _scopes.cell_trace(ctx)
    steps = [r for r in ctx.records if "learn_tokens" in r]
    if path is None or not steps:
        return None
    found = _scopes.seconds(ctx.trace, _scopes.operation_scopes(str(path)),
                            ("moe/experts", "ragged-dot"),
                            _common.LEARN_PROGRAMS)
    if not found or not found[0]:
        return None
    flops = sum(counts_smallthinker.learn_grouped_matmul_flops(
        ctx.cell.config, r["learn_tokens"],
        remat=bool(ctx.cell.config.get("gpt_config", {}).get("remat")))
        for r in steps)
    peak = ctx.peaks["bf16_flops_per_s"]  # seconds are averaged over chips
    return 100.0 * flops / ctx.cell.chips / found[0] / peak
