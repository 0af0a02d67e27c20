"""Workload/benchmark harnesses that drive the stack the way production
traffic would (docs/serving.md — the traffic-harness workflow).

The package half of the repo's benchmarking surface: ``benchmarking/`` at
the repo root holds standalone scripts (training harnesses, AOT sweeps);
importable harness *libraries* live here so they are graftcheck-scanned,
unit-tested, and reusable from tests and the
PBT-over-serving-policies work (ROADMAP item 4)."""

from agilerl_tpu.benchmarking.traffic import (
    ScenarioSpec,
    TrafficDriver,
    TrafficRequest,
    TrafficRunResult,
    generate_trace,
    load_trace,
    save_trace,
    scenario_suite,
)

__all__ = [
    "ScenarioSpec", "TrafficRequest", "TrafficDriver", "TrafficRunResult",
    "generate_trace", "load_trace", "save_trace", "scenario_suite",
]
