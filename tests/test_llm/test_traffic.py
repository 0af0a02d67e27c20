"""Traffic harness (agilerl_tpu/benchmarking/traffic.py): deterministic scenario
generation (same seed ⇒ identical trace), heavy-tail lengths clipped to
the bucket grid, prefix-skew prompt sharing, record/replay round-trip
(token-for-token, schema-gated); the TrafficDriver over a real 2-replica
ServingFleet — open-loop determinism across runs, replayed-trace ≡ live
outcome counts, closed-loop completion, replica-kill under flash crowd
with failover + autoscale reaction; fleet-wide merged_dump monotone
across scale_down; and the end-to-end SLO grading loop (continuous
evaluation over merged_dump, shed-rate burn alert fire → forced span →
clear, scored report)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agilerl_tpu.benchmarking.traffic import (
    TRACE_SCHEMA,
    ScenarioSpec,
    TrafficDriver,
    TrafficRequest,
    generate_trace,
    load_trace,
    save_trace,
    scenario_suite,
    trace_header,
)
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.autoscale import AutoscalePolicy
from agilerl_tpu.llm.fleet import ServingFleet
from agilerl_tpu.llm.serving import AdmissionPolicy
from agilerl_tpu.observability import (
    MemorySink,
    MetricsRegistry,
    SLOEvaluator,
    load_slo_spec,
)
from agilerl_tpu.observability.trace import Tracer
from agilerl_tpu.resilience.faults import FaultInjector

pytestmark = [pytest.mark.traffic, pytest.mark.serving]

CFG = M.GPTConfig(vocab_size=96, n_layer=2, n_head=4, n_kv_head=2,
                  d_model=32, max_seq_len=256, dtype=jnp.float32)
KW = dict(max_new_tokens=8, pad_id=0, eos_id=None, prompt_buckets=(32,),
          slots=3, block_size=8, decode_chunk=4)


class Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


def _spec(**over):
    """A scenario sized to the test fleet's grid (prompts ≤ bucket 32,
    outputs ≤ max_new_tokens, vocab inside CFG)."""
    kw = dict(name="t", vocab=90, duration_s=4.0, base_rate_rps=3.0,
              min_prompt=4, max_prompt=24, min_new=1, max_new=8)
    kw.update(over)
    return ScenarioSpec(**kw)


def _fleet(**over):
    kw = dict(KW)
    kw.update(over)
    return ServingFleet(CFG, kw.pop("n_replicas", 2),
                        metrics=kw.pop("metrics", MetricsRegistry()), **kw)


def _records(reqs):
    return [r.to_record() for r in reqs]


def _det(res):
    """The deterministic half of a run result — pure function of the
    trace and step schedule, never of host speed."""
    return (res.n_requests, res.submitted, res.shed, res.completed,
            res.steps, res.delivered_tokens)


# --------------------------------------------------------------------------- #
# scenario generation
# --------------------------------------------------------------------------- #


def test_generate_trace_deterministic():
    spec = _spec(kind="diurnal")
    a = generate_trace(spec, seed=7)
    b = generate_trace(spec, seed=7)
    assert a and _records(a) == _records(b)
    c = generate_trace(spec, seed=8)
    assert _records(a) != _records(c)


def test_lengths_clip_to_grid():
    reqs = generate_trace(_spec(duration_s=20.0, base_rate_rps=8.0), seed=1)
    assert len(reqs) > 50
    for r in reqs:
        assert 4 <= r.tokens.size <= 24
        assert 1 <= r.max_new <= 8
        assert r.tokens.min() >= 3 and r.tokens.max() < 90
    # heavy tail: lengths are not all the median
    assert len({r.tokens.size for r in reqs}) > 5
    arrivals = [r.arrival_s for r in reqs]
    assert arrivals == sorted(arrivals) and arrivals[-1] < 20.0


def test_rate_curves_and_flash_crowd_density():
    steady = _spec(kind="steady")
    assert steady.rate_at(0.0) == steady.rate_at(3.0) == steady.peak_rate()
    di = _spec(kind="diurnal", diurnal_period_s=4.0, diurnal_amplitude=0.8)
    assert math.isclose(di.rate_at(0.0), di.base_rate_rps)  # trough
    assert math.isclose(di.rate_at(2.0), di.peak_rate())    # mid-period peak
    fc = _spec(kind="flash_crowd", duration_s=10.0, burst_start_s=4.0,
               burst_duration_s=2.0, burst_x=6.0)
    assert fc.rate_at(3.9) == fc.base_rate_rps
    assert fc.rate_at(4.0) == fc.rate_at(5.9) == 6.0 * fc.base_rate_rps
    assert fc.rate_at(6.0) == fc.base_rate_rps
    reqs = generate_trace(fc, seed=3)
    burst = [r for r in reqs if 4.0 <= r.arrival_s < 6.0]
    outside = [r for r in reqs if not (4.0 <= r.arrival_s < 6.0)]
    # 2s of burst at 6x should out-arrive the other 8s combined
    assert len(burst) > len(outside)


def test_prefix_skew_shares_one_prompt():
    reqs = generate_trace(
        _spec(kind="prefix_skew", duration_s=15.0, base_rate_rps=6.0,
              shared_fraction=0.7, prefix_len=10), seed=5)
    shared = [r for r in reqs if r.shared_prefix]
    assert len(shared) > len(reqs) * 0.4
    head = shared[0].tokens[:10]
    for r in shared:
        assert r.tokens.size <= 24
        np.testing.assert_array_equal(r.tokens[:10], head)


def test_scenario_suite_covers_the_four_shapes():
    suite = scenario_suite(vocab=90, duration_s=4.0, base_rate_rps=3.0,
                           max_prompt=24, max_new=8)
    assert [s.name for s in suite] == [
        "steady_heavy_tail", "diurnal", "flash_crowd", "prefix_skew"]
    assert [s.kind for s in suite] == [
        "steady", "diurnal", "flash_crowd", "prefix_skew"]
    for s in suite:
        assert s.vocab == 90 and s.max_prompt == 24 and s.max_new == 8
        assert ScenarioSpec.from_dict(s.to_dict()) == s


def test_spec_dict_round_trip_ignores_unknown_fields():
    spec = _spec(kind="flash_crowd", burst_x=9.0)
    d = spec.to_dict()
    d["future_knob"] = 42  # forward-compat: old code reads new traces
    assert ScenarioSpec.from_dict(d) == spec


# --------------------------------------------------------------------------- #
# record / replay
# --------------------------------------------------------------------------- #


def test_trace_save_load_round_trip(tmp_path):
    spec = _spec(kind="prefix_skew")
    reqs = generate_trace(spec, seed=11)
    path = save_trace(tmp_path / "t.jsonl", reqs, spec=spec, seed=11)
    header = trace_header(path)
    assert header["schema"] == TRACE_SCHEMA
    assert header["n_requests"] == len(reqs)
    assert header["seed"] == 11
    assert ScenarioSpec.from_dict(header["spec"]) == spec
    loaded = load_trace(path)
    assert _records(loaded) == _records(reqs)
    for a, b in zip(loaded, reqs):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.tokens.dtype == np.int32


def test_trace_schema_gate(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "trace_header", "schema": 999}\n')
    with pytest.raises(ValueError, match="schema"):
        load_trace(bad)
    headerless = tmp_path / "raw.jsonl"
    headerless.write_text('{"index": 0}\n')
    with pytest.raises(ValueError, match="missing header"):
        load_trace(headerless)


# --------------------------------------------------------------------------- #
# the driver over a real fleet
# --------------------------------------------------------------------------- #


def test_open_loop_outcome_deterministic_across_fleets(params):
    trace = generate_trace(_spec(), seed=0)
    outs = []
    for _ in range(2):
        driver = TrafficDriver(_fleet(), steps_per_s=8.0, seed=0)
        outs.append(driver.run(trace, params, scenario="steady"))
    assert _det(outs[0]) == _det(outs[1])
    res = outs[0]
    assert res.submitted == res.completed == len(trace)
    assert res.shed == 0 and res.delivered_tokens > 0
    assert res.virtual_s == res.steps / 8.0


def test_replayed_trace_matches_live(params, tmp_path):
    spec = _spec(kind="diurnal")
    live = generate_trace(spec, seed=4)
    path = save_trace(tmp_path / "t.jsonl", live, spec=spec, seed=4)
    res_live = TrafficDriver(_fleet(), steps_per_s=8.0, seed=4).run(
        live, params, scenario="live")
    res_replay = TrafficDriver(_fleet(), steps_per_s=8.0, seed=4).run(
        load_trace(path), params, scenario="replay")
    assert _det(res_live) == _det(res_replay)


def test_closed_loop_completes_everything(params):
    trace = generate_trace(_spec(), seed=2)
    res = TrafficDriver(_fleet(), mode="closed", concurrency=4,
                        steps_per_s=8.0, seed=2).run(trace, params)
    assert res.mode == "closed"
    assert res.submitted == res.completed == len(trace)
    assert res.shed == 0  # closed loop submits no_shed by contract


def test_driver_rejects_bad_config():
    with pytest.raises(ValueError, match="mode"):
        TrafficDriver(object(), mode="sideways", metrics=MetricsRegistry())
    with pytest.raises(ValueError, match="steps_per_s"):
        TrafficDriver(object(), steps_per_s=0.0, metrics=MetricsRegistry())


def test_kill_under_burst_fails_over_and_scales_up(params):
    """The degraded run: a replica dies one second into a flash crowd.
    Every accepted ticket still completes (failover re-dispatch), the kill
    is recorded, and the autoscaler reacts to the pressure by growing the
    fleet."""
    spec = _spec(kind="flash_crowd", duration_s=5.0, burst_start_s=1.5,
                 burst_duration_s=1.5, burst_x=8.0)
    trace = generate_trace(spec, seed=6)
    fleet = _fleet(admission=AdmissionPolicy(max_queue=8), max_queue=3)
    clock = Clock()
    policy = AutoscalePolicy(min_replicas=2, max_replicas=4,
                             backlog_high=2.0, shed_rate_high=1.0,
                             up_cooldown_s=1.0, down_cooldown_s=1e9,
                             clock=clock, metrics=fleet.metrics)

    def on_step(step, vnow):
        clock.t = vnow

    driver = TrafficDriver(
        fleet, steps_per_s=8.0, seed=6, autoscale=policy,
        fault_injector=FaultInjector(kill_host_at={2: 1}), on_step=on_step)
    res = driver.run(trace, params, scenario="degraded")
    assert res.kills == [{"virtual_s": 2.0, "replica": 1}]
    assert res.completed == res.submitted  # tickets are commitments
    assert res.completed + res.shed == len(trace)
    ups = [e for e in res.scale_events if e["action"] == "up"]
    assert ups and ups[0]["virtual_s"] >= 1.5  # reaction, not prophecy
    # the kill dropped the fleet to one live member; the scale-up restored
    # capacity with a FRESH replica id, not a resurrected corpse
    assert len(fleet.replica_ids) >= 2
    assert 1 not in fleet.replica_ids and max(fleet.replica_ids) >= 2


def test_merged_dump_monotone_across_scale_down(params):
    """scale_down deletes the member, but its metrics are banked: the
    fleet-wide dump an SLO window is reading must not jump backwards."""
    fleet = _fleet()
    TrafficDriver(fleet, steps_per_s=8.0, seed=9).run(
        generate_trace(_spec(), seed=9), params)
    before = fleet.merged_dump()
    assert before["counters"]["serving/requests_total"] > 0
    ttft_count = before["histograms"]["serving/ttft_s"]["count"]
    fleet.scale_down(sorted(fleet.replica_ids)[0])
    after = fleet.merged_dump()
    for name, value in before["counters"].items():
        assert after["counters"].get(name, 0.0) >= value, name
    assert after["histograms"]["serving/ttft_s"]["count"] == ttft_count


# --------------------------------------------------------------------------- #
# end-to-end: traffic + SLO grading
# --------------------------------------------------------------------------- #


def test_slo_grades_degraded_run_and_alert_round_trips(params, tmp_path):
    """The graded traffic loop in miniature: continuous evaluation
    over the fleet's merged dump while a kill-under-burst run sheds; the
    shed-rate burn alert fires as a forced span, the objective fails the
    grade, and the alert clears once the burst passes."""
    from pathlib import Path

    spec_path = (Path(__file__).resolve().parents[2]
                 / "configs" / "slo" / "traffic_cpu.yaml")
    slo = load_slo_spec(spec_path)
    cnames, hnames = slo.metric_names()
    sink = MemorySink()
    fleet = _fleet(metrics=MetricsRegistry(sink=sink),
                   admission=AdmissionPolicy(max_queue=6), max_queue=2)
    clock = Clock()
    tracer = Tracer(sink=MemorySink(), sample_rate=0.0, metrics=fleet.metrics)

    def source():
        return fleet.merged_dump(counters=cnames, histograms=hnames)

    ev = SLOEvaluator(slo, source, clock=clock, metrics=fleet.metrics,
                      tracer=tracer)

    def on_step(step, vnow):
        clock.t = vnow
        ev.evaluate(now=vnow)

    scen = _spec(kind="flash_crowd", duration_s=8.0, base_rate_rps=2.0,
                 burst_start_s=2.0, burst_duration_s=2.0, burst_x=10.0)
    driver = TrafficDriver(
        fleet, steps_per_s=8.0, seed=13,
        fault_injector=FaultInjector(kill_host_at={3: 1}), on_step=on_step)
    res = driver.run(generate_trace(scen, seed=13), params,
                     scenario="degraded_burst")
    assert res.shed > 0 and res.kills
    phases = [(h["objective"], h["phase"]) for h in ev.alert_history]
    assert ("shed_rate", "fire") in phases
    assert ("shed_rate", "clear") in phases  # burst passed → page closed
    spans = [s["name"] for s in tracer.sink.events
             if str(s.get("name", "")).startswith("slo.")]
    assert "slo.fire" in spans and "slo.clear" in spans
    report = ev.grade(scenario="degraded_burst", extra=res.to_dict())
    rows = {r["name"]: r for r in report["objectives"]}
    assert not rows["shed_rate"]["ok"]
    assert rows["ttft_p95"]["events"] and rows["ttft_p95"]["events"] > 0
    assert 0.0 < report["score"] < 100.0
    assert report["scenario"] == "degraded_burst"
    # the driver's own structured events landed in the fleet sink
    kinds = [e["kind"] for e in sink.events]
    assert "traffic_scenario" in kinds and "traffic_fault" in kinds
    assert "traffic_scenario_done" in kinds
