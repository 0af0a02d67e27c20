"""Target-aware interpret-mode resolution for the Pallas kernels.

The kernels in this package take ``interpret: Optional[bool]``. Explicit
True/False always wins; ``None`` historically meant "interpret unless the
*default* backend is TPU". That heuristic is wrong for ahead-of-time
compilation: when lowering for a TPU *topology* (compile-only PJRT devices
from libtpu — no chip attached, ``jax.default_backend()`` is still ``cpu``),
the kernels must lower natively through Mosaic, not as interpret-mode HLO.

``native_kernels()`` is the override used by the AOT harness
(benchmarking/tpu_aot_compile.py) and any caller staging programs for a
device set that differs from the default backend:

    with native_kernels():
        compiled = jax.jit(step).lower(*abstract_args).compile()  # TPU topo

Sharp edge (documented, deliberate): the override is consulted at TRACE
time. A function traced under the context bakes the mode into that trace;
jit caches are keyed by the ``interpret`` argument the caller passed (often
``None``), not by the override. Mixing modes for the same static signature
in one process therefore requires fresh functions (what the AOT harness
does) or ``jax.clear_caches()``. Public entry points that jit internally
resolve the mode BEFORE entering jit, so their caches stay honest.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

# None = auto (default-backend heuristic); True = force native Mosaic
# lowering; False = force interpret mode.
_FORCE_NATIVE: Optional[bool] = None


def resolve_interpret(explicit: Optional[bool]) -> bool:
    """Resolve an ``interpret=`` argument to a concrete bool."""
    if explicit is not None:
        return bool(explicit)
    if _FORCE_NATIVE is not None:
        return not _FORCE_NATIVE
    return jax.default_backend() != "tpu"


# The one environment variable that changes what a program compiles to
# (ops.pallas_enabled: kernels off = the XLA paths every non-TPU backend
# runs). perfbench/harness.py refuses to measure with it set.
KILL_SWITCH_ENV_VARS = ("AGILERL_TPU_DISABLE_PALLAS",)


def active_kill_switches():
    """Names of the compile-path kill switches set in this process."""
    import os

    return [k for k in KILL_SWITCH_ENV_VARS if os.environ.get(k)]


@contextlib.contextmanager
def native_kernels(enable: bool = True):
    """Force native (Mosaic) Pallas lowering while tracing/lowering inside
    the context — regardless of the default backend. ``enable=False`` forces
    interpret mode instead."""
    global _FORCE_NATIVE
    prev = _FORCE_NATIVE
    _FORCE_NATIVE = bool(enable)
    try:
        yield
    finally:
        _FORCE_NATIVE = prev
