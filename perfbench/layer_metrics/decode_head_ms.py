"""Device milliseconds a decode step spends under ``decode/head``
(``generate.paged_decode_step``: the head's matmul, the EOS floor, the
sampler and, where log-probabilities are captured, the ``log_softmax``
pick): the time under the scope inside the decode-chunk program over calls
x the chunk's length, as ``decode_ms_per_step`` divides. With
``decode_ffn_ms``, ``decode_proj_ms`` and ``paged_attend_share`` x
``decode_ms_per_step`` it adds up to less than ``decode_ms_per_step``; the
rest is what no scope can hold (the casts and copies XLA makes). ``None`` on
a program without the scope (before PR 36)."""

from perfbench.layer_metrics import _kernels


def read(ctx):
    return _kernels.decode_scope_ms(ctx, "decode/head")
