"""Declarative SLOs + multi-window burn-rate alerting + scenario grading.

The telemetry plane already carries everything an operator needs to judge
the serving fleet — TTFT and per-token-decode histograms, shed/request
counters, ``fleet/scale_up_latency_s`` — but judging was manual: stare at
``latency_summary()`` and decide. This module makes the judgment a
DECLARED artifact:

- :class:`SLOSpec` / :class:`Objective` — objectives over a merged metric
  dump (:meth:`TelemetryAggregator.merged_dump` or a single registry's
  ``dump()``), YAML-loadable (``configs/slo/*.yaml``) so the SLO a fleet
  is graded against ships as reviewable config, not code.
- :class:`SLOEvaluator` — continuous evaluation with **multi-window
  burn-rate alerting** (the Google SRE workbook shape): an alert fires
  only when BOTH a fast and a slow window burn error budget faster than
  ``burn_threshold``, and clears when the fast window recovers — the fast
  window gives detection latency, the slow window kills flappy one-tick
  blips. Transitions (not states) are emitted: a forced — always-sampled,
  the tracer's anomaly contract — ``slo.alert`` span plus a structured
  ``slo_alert`` JSONL event per fire/clear.
- :meth:`SLOEvaluator.grade` — one scored report per scenario run:
  per-objective attainment over the whole window, pass/fail, a 0-100
  score, and the alert history: one per scenario a
  ``benchmarking.traffic.TrafficDriver`` runs.

Exactness contract: error fractions come from histogram BUCKET-COUNT
deltas, which are exact if and only if the objective threshold sits on a
bucket edge. That is why :meth:`MetricsRegistry.configure_buckets`
exists — fleets align bucket bounds with their SLO thresholds (and the
aggregator's :class:`TelemetrySchemaError` guarantees every pod agrees).
An off-edge threshold still works — linear interpolation inside the
containing bucket, same convention as ``Histogram.percentile`` — but the
evaluator says so once (``warn_once``) rather than silently degrading.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import time
from collections import deque
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from agilerl_tpu import observability

#: spec schema version (bump on layout changes)
SLO_SCHEMA = 1

_KINDS = ("latency", "ratio", "counter_ceiling")


@dataclasses.dataclass
class Objective:
    """One service-level objective over the merged metric dump.

    - ``kind="latency"`` — at least ``target`` of the observations in
      ``histogram`` must be ≤ ``threshold`` (error budget = 1 - target).
      The canonical fleet objectives: p95 TTFT, per-token decode time,
      scale-up latency.
    - ``kind="ratio"`` — ``numerator`` counter over ``denominator``
      counter must stay ≤ ``budget`` (e.g. shed rate:
      ``serving/shed_requests_total`` / ``serving/requests_total``).
    - ``kind="counter_ceiling"`` — ``counter``'s growth over the graded
      window must stay ≤ ``ceiling`` (e.g. rebalanced requests). Graded,
      never burn-rate alerted: a ceiling has no event-rate denominator to
      burn against.
    """

    name: str
    kind: str = "latency"
    # latency
    histogram: Optional[str] = None
    threshold: Optional[float] = None
    target: float = 0.95
    # ratio
    numerator: Optional[str] = None
    denominator: Optional[str] = None
    budget: Optional[float] = None
    # counter_ceiling
    counter: Optional[str] = None
    ceiling: Optional[float] = None
    #: burn-rate alerting on/off for this objective (latency/ratio only)
    alert: bool = True

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(
                f"objective {self.name!r}: unknown kind {self.kind!r} "
                f"(one of {_KINDS})")
        if self.kind == "latency":
            if self.histogram is None or self.threshold is None:
                raise ValueError(
                    f"latency objective {self.name!r} needs histogram + "
                    "threshold")
            if not 0.0 < self.target < 1.0:
                raise ValueError(
                    f"objective {self.name!r}: target must be in (0, 1)")
        elif self.kind == "ratio":
            if self.numerator is None or self.denominator is None \
                    or self.budget is None:
                raise ValueError(
                    f"ratio objective {self.name!r} needs numerator + "
                    "denominator + budget")
            if not 0.0 < float(self.budget) < 1.0:
                raise ValueError(
                    f"objective {self.name!r}: budget must be in (0, 1)")
        else:
            if self.counter is None or self.ceiling is None:
                raise ValueError(
                    f"counter_ceiling objective {self.name!r} needs "
                    "counter + ceiling")

    @property
    def error_budget(self) -> float:
        """Allowed error fraction (the burn-rate denominator)."""
        if self.kind == "latency":
            return 1.0 - float(self.target)
        if self.kind == "ratio":
            return float(self.budget)
        raise ValueError(f"{self.kind} objectives have no error budget")

    def to_dict(self) -> Dict[str, Any]:
        return {k: v for k, v in dataclasses.asdict(self).items()
                if v is not None}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Objective":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"objective {d.get('name', '<unnamed>')!r}: unknown "
                f"fields {sorted(unknown)}")
        return cls(**d)


@dataclasses.dataclass
class AlertPolicy:
    """Multi-window burn-rate alert shape, shared by every alerting
    objective in a spec. ``burn_threshold`` is the budget-consumption
    multiplier that pages: 1.0 means "exactly on budget"; production specs
    run 2-14x depending on window length (SRE workbook table)."""

    fast_window_s: float = 60.0
    slow_window_s: float = 300.0
    burn_threshold: float = 2.0
    #: fewer total events than this in the fast window ⇒ no verdict (a
    #: 1-request window is noise, not a page)
    min_events: int = 5

    def __post_init__(self):
        if self.fast_window_s <= 0 or self.slow_window_s < self.fast_window_s:
            raise ValueError(
                "need 0 < fast_window_s <= slow_window_s "
                f"(got {self.fast_window_s}, {self.slow_window_s})")
        if self.burn_threshold <= 0:
            raise ValueError("burn_threshold must be positive")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "AlertPolicy":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"alerting: unknown fields {sorted(unknown)}")
        return cls(**d)


@dataclasses.dataclass
class SLOSpec:
    """A named set of objectives + one alert policy — the unit a YAML file
    declares and a scenario is graded against."""

    name: str
    objectives: List[Objective]
    alerting: AlertPolicy = dataclasses.field(default_factory=AlertPolicy)

    def __post_init__(self):
        names = [o.name for o in self.objectives]
        if len(names) != len(set(names)):
            raise ValueError(f"duplicate objective names in spec "
                             f"{self.name!r}: {names}")

    def to_dict(self) -> Dict[str, Any]:
        return {"schema": SLO_SCHEMA, "name": self.name,
                "objectives": [o.to_dict() for o in self.objectives],
                "alerting": self.alerting.to_dict()}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SLOSpec":
        schema = d.get("schema", SLO_SCHEMA)
        if schema != SLO_SCHEMA:
            raise ValueError(f"SLO spec schema {schema} != {SLO_SCHEMA}")
        objs = [Objective.from_dict(o) if not isinstance(o, Objective)
                else o for o in d.get("objectives") or []]
        if not objs:
            raise ValueError(f"SLO spec {d.get('name')!r} has no objectives")
        alerting = d.get("alerting")
        if alerting is None:
            alerting = AlertPolicy()
        elif not isinstance(alerting, AlertPolicy):
            alerting = AlertPolicy.from_dict(alerting)
        return cls(name=str(d.get("name", "slo")), objectives=objs,
                   alerting=alerting)

    def bucket_overrides(self) -> Dict[str, List[float]]:
        """Histogram-name → threshold edges this spec needs for EXACT
        grading — feed into ``ServingFleet(bucket_overrides=...)`` /
        :meth:`MetricsRegistry.configure_buckets` merged with the default
        bounds, so SLO thresholds always sit on bucket edges."""
        out: Dict[str, List[float]] = {}
        for o in self.objectives:
            if o.kind == "latency":
                out.setdefault(o.histogram, []).append(float(o.threshold))
        return {k: sorted(set(v)) for k, v in out.items()}

    def metric_names(self):
        """``(counter_names, histogram_names)`` this spec reads — the
        filters to hand a selective source (``registry_source``,
        ``ServingFleet.merged_dump``) so per-tick evaluation never pays
        for instruments it does not grade."""
        counter_names: List[str] = []
        hist_names: List[str] = []
        for o in self.objectives:
            if o.kind == "latency":
                hist_names.append(o.histogram)
            elif o.kind == "ratio":
                counter_names += [o.numerator, o.denominator]
            else:
                counter_names.append(o.counter)
        return sorted(set(counter_names)), sorted(set(hist_names))

    def apply_buckets(self, registry,
                      base: Optional[Dict[str, Sequence[float]]] = None
                      ) -> Dict[str, List[float]]:
        """Configure ``registry`` so every latency threshold in this spec
        is a bucket edge: per histogram, the union of its existing bounds
        (or ``base[name]`` when the instrument does not exist yet) with the
        spec's thresholds, via :meth:`MetricsRegistry.configure_buckets`.
        Call BEFORE traffic; returns the bounds applied (hand the same
        mapping to ``ServingFleet(bucket_overrides=...)`` so member
        registries agree — the aggregator's exact merge requires it)."""
        applied: Dict[str, List[float]] = {}
        for name, edges in self.bucket_overrides().items():
            cur = (base or {}).get(name) or registry.bucket_bounds(name) or ()
            bounds = aligned_buckets(cur, edges)
            registry.configure_buckets(name, bounds)
            applied[name] = bounds
        return applied


def load_slo_spec(path: Union[str, Path]) -> SLOSpec:
    """Load an :class:`SLOSpec` from YAML (``configs/slo/*.yaml``)."""
    import yaml

    with open(path, encoding="utf-8") as fh:
        d = yaml.safe_load(fh)
    if not isinstance(d, dict):
        raise ValueError(f"{path}: SLO spec must be a mapping")
    return SLOSpec.from_dict(d)


def save_slo_spec(spec: SLOSpec, path: Union[str, Path]) -> Path:
    """Write a spec back to YAML (round-trips with :func:`load_slo_spec`)."""
    import yaml

    from agilerl_tpu.resilience.atomic import atomic_write_bytes

    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    atomic_write_bytes(
        path, yaml.safe_dump(spec.to_dict(), sort_keys=False).encode())
    return path


def aligned_buckets(base: Sequence[float],
                    thresholds: Sequence[float]) -> List[float]:
    """Union of default bucket bounds and SLO thresholds — the bounds a
    fleet should configure so grading is exact AND percentiles keep their
    usual resolution."""
    return sorted({float(b) for b in base} | {float(t) for t in thresholds})


def registry_source(registry, spec: SLOSpec) -> Callable[[], Dict[str, Any]]:
    """A per-tick source that reads ONLY the instruments ``spec`` grades —
    the hot-path alternative to ``registry.dump`` for in-process continuous
    evaluation. A fleet registry carries dozens of instruments; dumping all
    of them every scheduler step is where an evaluator's overhead budget
    actually goes. Reads live instrument state directly (same
    package-internal access the telemetry aggregator's materializer
    uses)."""
    from agilerl_tpu.observability.registry import Counter, Histogram

    counter_names, hist_names = spec.metric_names()

    def read() -> Dict[str, Any]:
        counters: Dict[str, float] = {}
        histograms: Dict[str, Any] = {}
        for n in counter_names:
            m = registry._metrics.get(n)
            if isinstance(m, Counter):
                counters[n] = m.value
        for n in hist_names:
            m = registry._metrics.get(n)
            if isinstance(m, Histogram):
                with m._lock:
                    histograms[n] = {"bounds": m.bounds,
                                     "counts": list(m._counts),
                                     "sum": m._sum, "count": m._count}
        return {"counters": counters, "gauges": {},
                "histograms": histograms}

    return read


# --------------------------------------------------------------------------- #
# evaluation
# --------------------------------------------------------------------------- #

def _hist_errors(h: Dict[str, Any], threshold: float):
    """(errors_above_threshold, total, exact) from one histogram dump.

    Exact when ``threshold`` is a bucket edge (counts[i] holds
    observations in (bounds[i-1], bounds[i]] — everything after the edge's
    bucket is strictly above it); otherwise linearly interpolated inside
    the containing bucket, flagged ``exact=False``."""
    bounds = [float(b) for b in h["bounds"]]
    counts = [int(c) for c in h["counts"]]
    total = int(h["count"])
    i = bisect.bisect_left(bounds, float(threshold))
    if i < len(bounds) and bounds[i] == float(threshold):
        return sum(counts[i + 1:]), total, True
    if i >= len(bounds):  # above the largest finite bound: only overflow
        return counts[-1], total, True
    lo = 0.0 if i == 0 else bounds[i - 1]
    hi = bounds[i]
    frac_above = (hi - float(threshold)) / (hi - lo) if hi > lo else 0.0
    errors = counts[i] * frac_above + sum(counts[i + 1:])
    return errors, total, False


class SLOEvaluator:
    """Continuous SLO evaluation over a metric-dump source.

    ``source`` is any zero-arg callable returning a ``registry.dump()``-
    shaped mapping — typically ``lambda: (agg.poll(), agg.merged_dump())[1]``
    for the cross-process plane, or ``fleet.metrics.dump`` in-process.
    ``clock`` is injectable (tests drive a fake clock; the traffic driver
    drives VIRTUAL time so burn windows are deterministic).

    :meth:`evaluate` is the tick: pull a snapshot, update every alerting
    objective's fast/slow-window burn rates, and emit fire/clear
    TRANSITIONS only — an alert that stays red across ten evaluations
    produces one forced span and one event, not ten (no-flap contract,
    ``tests/test_observability/test_slo.py``). Cost per tick is a dict
    walk over the dump — no I/O, no materialized registry — so running it
    every scheduler step stays inside the ~1% overhead budget the traffic
    bench measures."""

    def __init__(self, spec: SLOSpec,
                 source: Callable[[], Dict[str, Any]], *,
                 clock: Callable[[], float] = time.time,
                 metrics=None, tracer=None):
        self.spec = spec
        self.source = source
        self.clock = clock
        self.metrics = (metrics if metrics is not None
                        else observability.get_registry())
        self._tracer = tracer
        keep_s = spec.alerting.slow_window_s
        #: (ts, {objective: (errors, total)}) ring, pruned past slow window
        self._series: deque = deque()
        self._keep_s = float(keep_s)
        self._firing: Dict[str, bool] = {
            o.name: False for o in spec.objectives}
        self._history: List[Dict[str, Any]] = []
        self._first: Optional[Dict[str, Any]] = None
        self._last: Optional[Dict[str, Any]] = None
        self._first_ts: Optional[float] = None
        self._last_ts: Optional[float] = None

    @property
    def tracer(self):
        return (self._tracer if self._tracer is not None
                else observability.get_tracer())

    # -- reading one dump --------------------------------------------------
    def _measure(self, obj: Objective, dump: Dict[str, Any]):
        """Cumulative (errors, total) for one objective from one dump."""
        if obj.kind == "latency":
            h = (dump.get("histograms") or {}).get(obj.histogram)
            if h is None:
                return 0.0, 0.0
            errors, total, exact = _hist_errors(h, obj.threshold)
            if not exact:
                self.metrics.warn_once(
                    f"slo-threshold-off-grid:{obj.name}",
                    f"SLO objective {obj.name!r}: threshold "
                    f"{obj.threshold} is not a bucket edge of "
                    f"{obj.histogram} — error counts are interpolated, "
                    "not exact; align bounds via "
                    "MetricsRegistry.configure_buckets / "
                    "ServingFleet(bucket_overrides=...)")
            return float(errors), float(total)
        counters = dump.get("counters") or {}
        if obj.kind == "ratio":
            return (float(counters.get(obj.numerator, 0.0)),
                    float(counters.get(obj.denominator, 0.0)))
        return float(counters.get(obj.counter, 0.0)), 0.0

    def _window_fraction(self, name: str, window_s: float, now: float):
        """(error_fraction, events) over the trailing window, from
        cumulative deltas between now and the snapshot at the window
        start. Windows with no new events return (0, 0): no traffic burns
        no budget."""
        cur = self._series[-1][1].get(name)
        ref = None
        for ts, states in self._series:
            if ts <= now - window_s:
                ref = states.get(name)
            else:
                break
        if ref is None:
            if len(self._series) < 2:
                # a single snapshot carries no delta: everything before
                # the evaluator started is out of scope, not a burn
                return 0.0, 0.0
            # window extends past recorded history: burn against the
            # oldest snapshot we have (startup transient, vanishes once
            # the series covers the window)
            ref = self._series[0][1].get(name)
        d_err = max(0.0, cur[0] - ref[0])
        d_tot = max(0.0, cur[1] - ref[1])
        if d_tot <= 0.0:
            return 0.0, 0.0
        return d_err / d_tot, d_tot

    def _transition(self, obj: Objective, phase: str,
                    fast: tuple, slow: tuple, now: float) -> None:
        fields = {
            "objective": obj.name, "phase": phase, "spec": self.spec.name,
            "burn_fast": round(fast[0], 6), "burn_slow": round(slow[0], 6),
            "events_fast": fast[1], "events_slow": slow[1],
            "burn_threshold": self.spec.alerting.burn_threshold,
            "error_budget": obj.error_budget, "at_s": now,
        }
        self._history.append(dict(fields))
        self.metrics.counter(
            f"slo/alerts_{'fired' if phase == 'fire' else 'cleared'}_total",
            help="SLO burn-rate alert transitions").inc()
        self.metrics.emit("slo_alert", **fields)
        tr = self.tracer
        if tr is not None and getattr(tr, "enabled", False):
            # forced span: an SLO transition is an anomaly — always
            # sampled regardless of trace sampling, error status on fire
            span = tr.start_span(f"slo.{phase}", force=True,
                                 attributes=fields)
            if phase == "fire":
                span.set_error(f"{obj.name} burning "
                               f"{fast[0]:.1f}x budget")
            span.end()

    # -- the tick ----------------------------------------------------------
    def evaluate(self, now: Optional[float] = None) -> Dict[str, Any]:
        """One evaluation tick. Returns the per-objective state map
        ``{name: {burn_fast, burn_slow, firing, ...}}`` (alert TRANSITIONS
        additionally emit spans/events — see the class docstring)."""
        now = float(self.clock()) if now is None else float(now)
        dump = self.source()
        states = {o.name: self._measure(o, dump)
                  for o in self.spec.objectives}
        self._series.append((now, states))
        while (len(self._series) > 2
               and self._series[1][0] <= now - self._keep_s):
            self._series.popleft()
        if self._first is None:
            self._first, self._first_ts = dump, now
        self._last, self._last_ts = dump, now
        pol = self.spec.alerting
        out: Dict[str, Any] = {}
        for obj in self.spec.objectives:
            if obj.kind == "counter_ceiling" or not obj.alert:
                continue
            fast_f, fast_n = self._window_fraction(
                obj.name, pol.fast_window_s, now)
            slow_f, slow_n = self._window_fraction(
                obj.name, pol.slow_window_s, now)
            budget = obj.error_budget
            fast = (fast_f / budget, fast_n)
            slow = (slow_f / budget, slow_n)
            firing = self._firing[obj.name]
            if not firing:
                if (fast_n >= pol.min_events
                        and fast[0] >= pol.burn_threshold
                        and slow[0] >= pol.burn_threshold):
                    self._firing[obj.name] = True
                    self._transition(obj, "fire", fast, slow, now)
            elif fast[0] < pol.burn_threshold:
                # clear on fast-window recovery: the slow window keeps the
                # historical burn for a while by construction, and waiting
                # it out would hold a resolved page open for minutes
                self._firing[obj.name] = False
                self._transition(obj, "clear", fast, slow, now)
            out[obj.name] = {
                "burn_fast": fast[0], "burn_slow": slow[0],
                "events_fast": fast[1], "events_slow": slow[1],
                "firing": self._firing[obj.name],
            }
        return out

    @property
    def active_alerts(self) -> List[str]:
        return sorted(n for n, f in self._firing.items() if f)

    @property
    def alert_history(self) -> List[Dict[str, Any]]:
        """Every fire/clear transition this evaluator emitted."""
        return list(self._history)

    # -- grading -----------------------------------------------------------
    def grade(self, scenario: Optional[str] = None,
              extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """One scored report over everything seen between the first and
        last :meth:`evaluate`. Attainment is computed from cumulative deltas over the full
        run, so a scenario is graded on ALL of its traffic, not on
        whichever alert window happened to be open at the end."""
        if self._first is None:
            raise RuntimeError("grade() before any evaluate() tick")
        objectives = []
        passed = 0
        gradeable = 0
        for obj in self.spec.objectives:
            e0, t0 = self._measure(obj, self._first)
            e1, t1 = self._measure(obj, self._last)
            d_err, d_tot = max(0.0, e1 - e0), max(0.0, t1 - t0)
            row: Dict[str, Any] = {"name": obj.name, "kind": obj.kind}
            if obj.kind == "counter_ceiling":
                row.update(counter=obj.counter, ceiling=obj.ceiling,
                           value=d_err, ok=d_err <= float(obj.ceiling))
            elif d_tot <= 0.0:
                # no traffic reached this objective: vacuous pass, but say
                # so — a scenario that never exercised an objective should
                # not read as evidence the objective holds
                row.update(value=None, ok=True, no_data=True,
                           error_budget=obj.error_budget)
            else:
                frac = d_err / d_tot
                row.update(
                    attained=round(1.0 - frac, 6),
                    error_fraction=round(frac, 6),
                    error_budget=obj.error_budget,
                    events=d_tot,
                    budget_consumed=round(frac / obj.error_budget, 4),
                    # tolerance: error_budget = 1 - target is already one
                    # float subtraction away from exact; landing precisely
                    # ON budget must grade as met
                    ok=frac <= obj.error_budget + 1e-9,
                )
                if obj.kind == "latency":
                    row.update(histogram=obj.histogram,
                               threshold=obj.threshold, target=obj.target)
                else:
                    row.update(numerator=obj.numerator,
                               denominator=obj.denominator)
            if obj.alert:
                row["alerts"] = sum(
                    1 for h in self._history
                    if h["objective"] == obj.name and h["phase"] == "fire")
            objectives.append(row)
            gradeable += 1
            passed += bool(row["ok"])
        score = round(100.0 * passed / max(1, gradeable), 1)
        report = {
            "spec": self.spec.name,
            "scenario": scenario,
            "objectives": objectives,
            "passed": passed,
            "total": gradeable,
            "score": score,
            "ok": passed == gradeable,
            "alerts": self.alert_history,
            "active_alerts": self.active_alerts,
            "window_s": (round(self._last_ts - self._first_ts, 6)
                         if self._last_ts is not None else 0.0),
            "evaluations": len(self._series),
        }
        if extra:
            report.update(extra)
        return report


def attribute_scale_ups(events: Sequence[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
    """Join the event stream into alert→reaction attribution records: for
    each ``slo_alert`` fire, the first ACTUATED scale-up
    ``autoscale_decision`` that follows it (by event order — both streams
    share one sink, so sink sequence IS causal order within a process),
    and the alert's clear if one followed. The per-incident record a
    degraded-run grade embeds: which breach paged, what the autoscaler saw
    when it reacted, and whether the page closed."""
    out: List[Dict[str, Any]] = []
    open_incident: Optional[Dict[str, Any]] = None
    for ev in events:
        kind = ev.get("kind")
        if kind == "slo_alert" and ev.get("phase") == "fire":
            open_incident = {
                "objective": ev.get("objective"),
                "fired_at_s": ev.get("at_s"),
                "burn_fast": ev.get("burn_fast"),
                "scale_up": None,
                "cleared_at_s": None,
            }
            out.append(open_incident)
        elif open_incident is not None:
            if (kind == "autoscale_decision" and ev.get("actioned")
                    and ev.get("verdict") == "up"
                    and open_incident["scale_up"] is None):
                open_incident["scale_up"] = {
                    "replica": ev.get("replica"),
                    "triggers": ev.get("triggers"),
                    "signals": ev.get("signals"),
                }
            elif (kind == "slo_alert" and ev.get("phase") == "clear"
                    and ev.get("objective") == open_incident["objective"]):
                open_incident["cleared_at_s"] = ev.get("at_s")
                open_incident = None
    return out


def write_report(report: Dict[str, Any], path: Union[str, Path]) -> Path:
    """Persist one scored report as JSON, atomically (a crashed bench must
    not leave a truncated report a dashboard later trusts)."""
    from agilerl_tpu.resilience.atomic import atomic_write_bytes

    path = Path(path)
    atomic_write_bytes(
        path, (json.dumps(report, indent=2, sort_keys=True) + "\n").encode())
    return path
