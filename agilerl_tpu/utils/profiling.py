"""Tracing / profiling / MFU accounting — first-class on TPU
(parity+: the reference has NO in-library tracer, SURVEY.md §5.1 — profiling is
demonstrated via external cProfile/torch.profiler scripts and the only MFU
accounting is EvolvableGPT.estimate_mfu, agilerl/modules/gpt.py:516. Here
jax.profiler traces and per-step MFU/step-time metrics are built in.)
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, Optional

import jax


@contextlib.contextmanager
def profile_trace(logdir: str = "/tmp/agilerl_tpu_trace") -> Iterator[None]:
    """Capture a jax.profiler trace viewable in TensorBoard/Perfetto."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named trace span for host-side phases."""
    return jax.profiler.TraceAnnotation(name)


def transformer_flops_per_token(config) -> float:
    """Approximate fwd+bwd FLOPs per token for the GPT config (6N + attention),
    PaLM-style accounting."""
    d, L = config.d_model, config.n_layer
    ff = config.ff_dim
    # parameter count (mirrors llm/model.init_params)
    attn = d * config.n_head * config.head_dim * 2 + d * config.kv_heads * config.head_dim * 2
    mlp = 3 * d * ff
    n_params = config.vocab_size * d + L * (attn + mlp)
    return 6.0 * n_params + 12.0 * L * config.max_seq_len * d


#: peak bf16 FLOPs/s per chip by generation — the ONE table (hbm_budget and
#: the 7B plan read it too)
PEAK_BF16_FLOPS = {
    "tpu v4": 275e12, "tpu v5": 197e12, "tpu v5 lite": 197e12,
    "tpu v5p": 459e12, "tpu v6e": 918e12, "tpu v6 lite": 918e12,
}


#: the v5 peak ``estimate_mfu`` falls back to on a backend with NO defined
#: peak (CPU/GPU), announced by a warning; never used for a TPU
_FALLBACK_TPU_PEAK = 197e12


def peak_flops_per_device(device=None) -> Optional[float]:
    """Peak bf16 FLOPs/s for the device's chip generation; None when the
    backend has no well-defined peak (CPU/GPU) — no fabricated MFU.

    A TPU whose ``device_kind`` is missing from PEAK_BF16_FLOPS is an error,
    not a default: a peak assumed for an unknown chip makes every MFU reading
    wrong by the ratio of the two peaks, and nobody notices."""
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"unknown TPU device_kind {kind!r}: no entry in PEAK_BF16_FLOPS "
            f"({sorted(PEAK_BF16_FLOPS)}); add its published bf16 peak "
            "before reporting a utilisation on it")
    return PEAK_BF16_FLOPS[kind]


def estimate_mfu(
    config,
    tokens_per_step: int,
    step_time_s: float,
    peak_flops: Optional[float] = None,
) -> float:
    """Model FLOPs utilisation (parity: modules/gpt.py:516, generalised).

    peak_flops defaults per detected TPU generation (bf16). On a backend with
    no defined peak (CPU/GPU) the historical v5 fallback is kept for
    backward compatibility but announced via a one-time warning event — the
    returned figure is an estimate, not a real MFU."""
    if peak_flops is None:
        peak_flops = peak_flops_per_device()
        if peak_flops is None:
            from agilerl_tpu.observability import warn_once

            warn_once(
                "estimate_mfu:no-peak",
                "estimate_mfu called on a backend with no defined bf16 peak "
                f"(CPU/GPU): using the TPU v5 fallback {_FALLBACK_TPU_PEAK:.0f} "
                "FLOPs/s — treat the result as an estimate",
            )
            peak_flops = _FALLBACK_TPU_PEAK
    flops = transformer_flops_per_token(config) * tokens_per_step
    return flops / (step_time_s * peak_flops)


def achieved_flops_metrics(
    lowered, calls: int, elapsed_s: float
) -> Dict[str, Any]:
    """Achieved FLOPs/s (and MFU where the chip has a defined peak) for a
    lowered jitted program, using XLA's own cost analysis — no hand model.
    Returns {} when the analysis is unavailable."""
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
    except Exception:
        return {}
    if flops <= 0 or elapsed_s <= 0:
        return {}
    achieved = flops * calls / elapsed_s
    out: Dict[str, Any] = {"achieved_tflops_per_sec": round(achieved / 1e12, 4)}
    peak = peak_flops_per_device()
    out["mfu"] = round(achieved / peak, 4) if peak else None
    return out


class StepTimer:
    """Rolling fps / step-time / MFU tracker for training loops
    (parity: fps tracking in training/train_off_policy.py:439)."""

    def __init__(self, window: int = 20):
        self.window = window
        self._times = []
        self._last = None

    def tick(self) -> Optional[float]:
        now = time.perf_counter()
        dt = None
        if self._last is not None:
            dt = now - self._last
            self._times.append(dt)
            if len(self._times) > self.window:
                self._times.pop(0)
        self._last = now
        return dt

    @property
    def mean_step_time(self) -> float:
        return sum(self._times) / len(self._times) if self._times else float("nan")

    def throughput(self, units_per_step: float) -> float:
        st = self.mean_step_time
        return units_per_step / st if st == st and st > 0 else float("nan")
