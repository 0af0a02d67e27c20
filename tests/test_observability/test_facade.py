"""RunTelemetry facade + the profiling warn-once satellite (ISSUE 1)."""

import pytest

from agilerl_tpu.observability import (
    MemorySink,
    MetricsRegistry,
    RunTelemetry,
    init_run_telemetry,
    read_jsonl,
)


def _mem_telemetry(**kwargs):
    reg = MetricsRegistry(sink=MemorySink())
    return RunTelemetry(wb=False, registry=reg, **kwargs)


def test_log_step_reaches_sink_without_wandb():
    telem = _mem_telemetry()
    telem.log_step({"global_step": 10, "eval/mean_fitness": 1.5})
    events = telem.registry.sink.events
    (e,) = [x for x in events if x["kind"] == "metrics"]
    assert e["global_step"] == 10 and e["eval/mean_fitness"] == 1.5


def test_record_eval_emits_event_and_feeds_lineage():
    class A:
        def __init__(self, i):
            self.index = i

    telem = _mem_telemetry()
    telem.lineage.start_generation({0: 1.0})
    telem.lineage.record_selection(0, 1, 1.0)
    telem.lineage.record_mutation(1, "param")
    telem.record_eval([A(0), A(1)], [2.0, 4.0])
    ev = [e for e in telem.registry.sink.events if e["kind"] == "eval"]
    assert len(ev) == 1 and ev[0]["mean_fitness"] == pytest.approx(3.0)
    # child 1's record closed with delta 4.0 - 1.0
    lineage_ev = [e for e in telem.registry.sink.events if e["kind"] == "lineage"]
    assert lineage_ev[0]["fitness_delta"] == pytest.approx(3.0)
    assert telem.registry.gauge("eval/mean_fitness").value == pytest.approx(3.0)


def test_attach_evolution_points_hpo_at_tracker():
    class Stub:
        lineage = None

    telem = _mem_telemetry()
    t, m = Stub(), Stub()
    telem.attach_evolution(t, m)
    assert t.lineage is telem.lineage and m.lineage is telem.lineage


def test_attach_evolution_replaces_stale_facade_tracker_not_user_tracker():
    """Reusing tournament/mutation across two runs must re-attach to the new
    run's tracker (else generation events land in the closed first run) —
    but a tracker the user wired in explicitly is never clobbered."""
    from agilerl_tpu.observability import LineageTracker

    class Stub:
        lineage = None

    t, m = Stub(), Stub()
    run1 = _mem_telemetry()
    run1.attach_evolution(t, m)
    run1.close()
    run2 = _mem_telemetry()
    run2.attach_evolution(t, m)
    assert t.lineage is run2.lineage and m.lineage is run2.lineage

    user_tracker = LineageTracker()
    t2 = Stub()
    t2.lineage = user_tracker
    run2.attach_evolution(t2, None)
    assert t2.lineage is user_tracker


def test_jsonl_sink_drops_events_after_close(tmp_path):
    from agilerl_tpu.observability import JsonlSink

    sink = JsonlSink(tmp_path / "t.jsonl")
    sink.emit("a", {})
    sink.close()
    sink.emit("b", {})  # must not raise on the closed handle
    events = read_jsonl(tmp_path / "t.jsonl")
    assert [e["kind"] for e in events] == ["a"]


def test_jsonl_sink_append_continues_seq(tmp_path):
    from agilerl_tpu.observability import JsonlSink

    path = tmp_path / "t.jsonl"
    s1 = JsonlSink(path)
    s1.emit("a", {})
    s1.emit("a", {})
    s1.close()
    s2 = JsonlSink(path)  # second run appending to the same file
    s2.emit("b", {})
    s2.close()
    seqs = [e["seq"] for e in read_jsonl(path)]
    assert seqs == [0, 1, 2]


def test_reused_registry_gets_fresh_sink_after_close(tmp_path):
    from agilerl_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    run1 = RunTelemetry(wb=False, registry=reg,
                        jsonl_path=str(tmp_path / "r1.jsonl"))
    run1.log_step({"x": 1})
    run1.close()
    run2 = RunTelemetry(wb=False, registry=reg,
                        jsonl_path=str(tmp_path / "r2.jsonl"))
    run2.log_step({"y": 2})
    run2.close()
    assert any(e["kind"] == "metrics" for e in read_jsonl(tmp_path / "r2.jsonl"))
    # close is idempotent (atexit may fire after a normal close)
    run2.close()


def test_init_run_telemetry_reuses_caller_instance():
    telem = _mem_telemetry()
    assert init_run_telemetry(wb=False, telemetry=telem) is telem
    fresh = init_run_telemetry(wb=False)
    assert fresh is not telem
    fresh.close()


def test_jsonl_path_resolution(tmp_path):
    telem = RunTelemetry(wb=False, jsonl_path=str(tmp_path / "run.jsonl"))
    telem.log_step({"x": 1})
    telem.close(lineage_path=str(tmp_path / "lineage.json"))
    events = read_jsonl(tmp_path / "run.jsonl")
    assert any(e["kind"] == "metrics" for e in events)
    assert any(e["kind"] == "lineage_summary" for e in events)
    assert (tmp_path / "lineage.json").exists()


def test_env_var_directory_resolution(tmp_path, monkeypatch):
    from agilerl_tpu.observability.facade import TELEMETRY_ENV

    monkeypatch.setenv(TELEMETRY_ENV, str(tmp_path))
    telem = RunTelemetry(wb=False)
    telem.log_step({"y": 2})
    telem.close()
    files = list(tmp_path.glob("run-*.jsonl"))
    assert len(files) == 1
    assert any(e["kind"] == "metrics" for e in read_jsonl(files[0]))


def test_unknown_tpu_device_kind_is_an_error():
    """A TPU missing from PEAK_BF16_FLOPS raises: no peak is assumed for it,
    so no MFU can be silently wrong by the ratio of two chips' peaks."""
    from agilerl_tpu.utils.profiling import peak_flops_per_device

    class FakeTPU:
        platform = "tpu"
        device_kind = "tpu v99"

    with pytest.raises(KeyError, match="tpu v99.*PEAK_BF16_FLOPS"):
        peak_flops_per_device(FakeTPU())

    class CPU:
        platform = "cpu"
        device_kind = "cpu"

    assert peak_flops_per_device(CPU()) is None

    class KnownTPU:
        platform = "tpu"
        device_kind = "TPU v5p"

    assert peak_flops_per_device(KnownTPU()) == 459e12
