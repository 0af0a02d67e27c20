"""The selective scan's two Pallas kernels (``ssm_scan_fwd``,
``ssm_scan_bwd``, ``agilerl_tpu/ops/selective_scan.py``) against the HBM
roofline: the least bytes their executions inside the learn programs
(``jit_logprobs``, ``jit_update``) must move, over those executions' device
seconds, over the chip's HBM bandwidth. Read from the cell's own
``.xplane.pb`` (``_kernels``).

Bytes are counted **per execution, from its own first result** — ``y`` (the
forward) or ``dx`` (the backward), ``[B, T, D]`` in ``x``'s dtype of ``a``
bytes, ``D`` the ``d_inner`` lanes that execution covered — with ``N`` the
configuration's ``mamba_d_state``, each input and output once:

- forward: ``B T (D (2 a + 4) + 2 N 4)``: ``x`` in and ``y`` out, ``dt``,
  ``B`` and ``C`` in float32 (``counts_hybrid.selective_scan_bytes``);
- backward: ``B T (D (3 a + 8) + 4 N 4)``: ``x``, ``dy`` and ``dx``; ``dt``
  and ``ddt`` in float32; ``B``, ``C``, ``dB`` and ``dC`` in float32.

The state a block starts from, the start and last states and ``A`` are left
out: a floor, so the share cannot pass 100 %. The kernels are bound by the
vector units, not by HBM: the share says how far they sit above the bytes
they cannot avoid. None where no execution ran: a program from before the
kernels, or one that the gate sent to the XLA form."""

import re

from perfbench.layer_metrics import _common, _kernels, _scopes

STEMS = ("ssm_scan_fwd", "ssm_scan_bwd")
#: the first array of a result type: its element's bits and its dims
FIRST = re.compile(r"[a-z]+([0-9]+)\[([0-9,]*)\]")


def execution_bytes(stem: str, result: str, n: int) -> float:
    """The least HBM bytes of one execution, from its result type."""
    bits, dims = FIRST.search(result).groups()
    b, t, d = (int(x) for x in dims.split(","))
    a = int(bits) // 8
    if stem == "ssm_scan_fwd":
        return float(b * t * (d * (2 * a + 4) + 2 * n * 4))
    return float(b * t * (d * (3 * a + 8) + 4 * n * 4))


def read(ctx):
    path = _scopes.cell_trace(ctx)
    if path is None:
        return None
    found = _kernels.executions(ctx.trace, _kernels.operations(str(path)),
                                STEMS, _common.LEARN_PROGRAMS)
    seconds = sum(s for _, _, s in found)
    if not seconds:
        return None
    n = int(ctx.cell.config["mamba_d_state"])
    moved = sum(execution_bytes(stem, result, n) for stem, result, _ in found)
    return 100.0 * moved / seconds / ctx.peaks["hbm_bytes_per_s"]
