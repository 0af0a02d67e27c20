"""Evolutionary layout search: sweep candidate sharding plans on measured
step time, paying compile once per layout EVER (the PR 6 follow-up the
persistent executable store unblocks).

The ``sharding=`` mutation (``hpo/mutation.py``) already swaps a member's
layout among the registered plans and lets tournament pressure feel the
difference through :class:`~agilerl_tpu.observability.timeline.StepTimeline`
step-time telemetry — but every candidate layout used to pay a full XLA
compile, which made a sweep over even a handful of layouts spend most of
its time in the compiler. With the
:mod:`~agilerl_tpu.parallel.compile_cache` store wired through
:func:`~agilerl_tpu.parallel.plan.compile_step_with_plan`, each (plan,
signature, topology, toolchain) executable is compiled at most once per
store lifetime: the first sweep warms the store, every later sweep — and
every ``sharding=`` mutation that lands on a swept layout — loads.

:func:`search_layouts` is the driver: candidates default to the registry's
plans for the live device count (exactly the mutation's swap set), fitness
is mean measured step time over ``steps`` timed calls (after ``warmup``
un-timed calls that also absorb the load-or-compile), and the result ranks
candidates fastest-first with per-candidate cache provenance so warm-vs-
cold is visible in the report and the telemetry plane.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from agilerl_tpu.parallel import plan as PL
from agilerl_tpu.parallel.compile_cache import resolve_cache


@dataclass
class LayoutCandidate:
    """One evaluated layout: the plan, its measured step times, and the
    compile-cache provenance of its executable."""

    plan: Any
    step_times_s: List[float] = field(default_factory=list)
    step_time_s: Optional[float] = None  # mean over the timed calls
    cache_hit: Optional[bool] = None
    load_s: Optional[float] = None
    compile_s: Optional[float] = None
    fingerprint: Optional[str] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.step_time_s is not None

    def to_dict(self) -> Dict[str, Any]:
        return {
            "plan": self.plan.name,
            "mesh": dict(self.plan.ordered_axes()),
            "step_time_s": self.step_time_s,
            "step_times_s": list(self.step_times_s),
            "cache_hit": self.cache_hit,
            "load_s": self.load_s,
            "compile_s": self.compile_s,
            "fingerprint": self.fingerprint,
            "error": self.error,
        }


@dataclass
class LayoutSearchResult:
    candidates: List[LayoutCandidate]

    @property
    def ranked(self) -> List[LayoutCandidate]:
        """Successful candidates, fastest mean step time first."""
        return sorted((c for c in self.candidates if c.ok),
                      key=lambda c: c.step_time_s)

    @property
    def best(self) -> Optional[LayoutCandidate]:
        ranked = self.ranked
        return ranked[0] if ranked else None

    def to_dict(self) -> Dict[str, Any]:
        best = self.best
        return {
            "best_plan": best.plan.name if best is not None else None,
            "candidates": [c.to_dict() for c in self.ranked]
            + [c.to_dict() for c in self.candidates if not c.ok],
        }


def search_layouts(
    step_fn: Callable,
    in_groups: Sequence[Optional[str]],
    args_for: Any,
    *,
    plans: Optional[Sequence[Any]] = None,
    devices: Optional[Sequence[Any]] = None,
    cache: Any = None,
    steps: int = 3,
    warmup: int = 1,
    donate: bool = False,
    registry=None,
    name: str = "layout_search",
) -> LayoutSearchResult:
    """Evaluate ``step_fn`` under each candidate plan and rank by measured
    step time.

    - ``args_for``: either a tuple of concrete arg trees (placed per plan
      through ``step.place_args`` for every candidate) or a callable
      ``args_for(plan, mesh) -> args tuple`` for layouts that need
      per-plan inputs (e.g. per-layout batch shapes).
    - ``plans``: candidate :class:`~agilerl_tpu.parallel.plan.ShardingPlan`
      objects or registered names; default = the registry's plans for the
      live device count — the same swap set the ``sharding=`` mutation
      draws from (seeded with the default GRPO layouts when empty).
    - ``cache``: the persistent executable store (store / path / env
      opt-in via :func:`~agilerl_tpu.parallel.compile_cache.resolve_cache`)
      — each candidate's executable is loaded when already swept, so a
      warm store turns the sweep from compile-bound into measure-bound.
    - ``donate``: step donates its first arg (training-step convention);
      args are rebuilt from the template before EVERY call, outside the
      timed region, so donation cannot consume the measurement inputs.

    A candidate whose compile/evaluation raises is recorded with its error
    and excluded from the ranking — one invalid layout must not kill the
    sweep. Per-candidate step times feed a
    :class:`~agilerl_tpu.observability.timeline.StepTimeline`
    (``<name>/<plan>/step_time_s``) plus one ``layout_search`` event per
    candidate, so the sweep is visible in the PR 11 telemetry plane.
    """
    from agilerl_tpu import observability
    from agilerl_tpu.observability.timeline import StepTimeline

    reg = registry if registry is not None else observability.get_registry()
    store = resolve_cache(cache, metrics=reg)
    if plans is None:
        n = len(devices) if devices is not None else len(jax.devices())
        PL.register_default_plans(n)
        plans = PL.plans_for_device_count(n)
    plans = [PL.get_plan(p) if isinstance(p, str) else p for p in plans]
    if not plans:
        raise ValueError(
            "layout search needs at least one candidate plan (register "
            "plans for this device count, or pass plans=)")

    candidates: List[LayoutCandidate] = []
    n_warm, n_timed = int(warmup), int(steps)
    for plan in plans:
        cand = LayoutCandidate(plan=plan)
        candidates.append(cand)
        try:
            cand_devices = (list(devices)[: plan.device_count]
                            if devices is not None else None)
            step = PL.compile_step_with_plan(
                step_fn, plan, in_groups, devices=cand_devices,
                donate_argnums=(0,) if donate else (),
                cache=store if store is not None else False,
                name=f"{name}/{plan.name}",
            )

            def build_args() -> Tuple[Any, ...]:
                raw = (args_for(plan, step.mesh) if callable(args_for)
                       else args_for)
                return step.place_args(*raw)

            timeline = StepTimeline(reg, name=f"{name}/{plan.name}",
                                    step_event_every=0)
            timeline.step()  # arm the interval timer
            args = None
            for i in range(n_warm + n_timed):
                if donate or args is None:
                    args = build_args()
                t0 = time.perf_counter()
                out = step(*args)
                jax.block_until_ready(out)
                dt = time.perf_counter() - t0
                if i >= n_warm:
                    cand.step_times_s.append(dt)
                    timeline.step()
            cand.step_time_s = (sum(cand.step_times_s)
                                / max(len(cand.step_times_s), 1))
            info = step.cache_info
            if info is not None:
                cand.cache_hit = info.get("hit") is True
                cand.load_s = info.get("load_s")
                cand.compile_s = info.get("compile_s")
                cand.fingerprint = info.get("fingerprint")
        except Exception as e:  # noqa: BLE001 — one bad layout != dead sweep
            cand.error = f"{type(e).__name__}: {e}"
            reg.warn_once(
                f"layout-search-{plan.name}",
                f"layout search candidate {plan.name!r} failed: {cand.error}")
        reg.emit(name, **cand.to_dict())

    result = LayoutSearchResult(candidates)
    best = result.best
    if best is not None:
        reg.gauge(f"{name}/best_step_time_s").set(best.step_time_s)
        reg.emit(f"{name}_summary", **result.to_dict())
    return result
