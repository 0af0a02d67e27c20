"""StepTimeline: per-step events, monotone indices, MFU accounting reuse,
CombineLogs aggregation ride-along (ISSUE 1 tentpole §2)."""

import jax.numpy as jnp
import pytest

from agilerl_tpu.observability import MemorySink, MetricsRegistry, StepTimeline


def test_step_events_monotone_with_throughput():
    sink = MemorySink()
    reg = MetricsRegistry(sink=sink)
    tl = StepTimeline(reg, name="train", memory_stats_every=0)
    assert tl.step(env_steps=4) is None  # first call only arms the timer
    events = [tl.step(env_steps=4, agent_index=1) for _ in range(5)]
    assert all(e is not None for e in events)
    steps = [e["step"] for e in events]
    assert steps == sorted(steps) == list(range(5))
    for e in events:
        assert e["step_time_s"] > 0
        assert e["env_steps_per_sec"] > 0
        assert e["agent"] == 1
        assert "mfu" not in e  # CPU: no defined peak, no fabricated MFU
    assert reg.counter("train/steps_total").value == 5
    assert reg.histogram("train/step_time_s").count == 5
    emitted = [e for e in sink.events if e["kind"] == "step"]
    assert [e["step"] for e in emitted] == list(range(5))


def test_mfu_reuses_profiling_flops_accounting(monkeypatch):
    """MFU = transformer_flops_per_token(config) * tokens / (dt * peak):
    the SAME accounting utils.profiling uses, against the device's table peak."""
    from agilerl_tpu.llm.model import GPTConfig
    from agilerl_tpu.observability import timeline as T

    cfg = GPTConfig(vocab_size=96, n_layer=2, n_head=4, n_kv_head=2,
                    d_model=32, max_seq_len=64, dtype=jnp.float32)
    monkeypatch.setattr(T, "peak_flops_per_device", lambda device=None: 1e12)
    reg = MetricsRegistry(sink=MemorySink())
    tl = StepTimeline(reg, name="llm", model_config=cfg, memory_stats_every=0)
    tl.step(tokens=1024)
    e = tl.step(tokens=1024)
    from agilerl_tpu.utils.profiling import transformer_flops_per_token

    expected = transformer_flops_per_token(cfg) * 1024 / (e["step_time_s"] * 1e12)
    assert e["mfu"] == pytest.approx(expected, rel=1e-3)
    assert "estimated" not in e  # the peak is the table's or the run fails
    assert reg.gauge("llm/mfu").value == e["mfu"]


def test_aggregate_rides_combine_logs_single_host():
    reg = MetricsRegistry()
    tl = StepTimeline(reg, memory_stats_every=0)
    tl.step(env_steps=2)
    for _ in range(3):
        tl.step(env_steps=2)
    # across_hosts=True on one process: same as local reduce (CombineLogs
    # skips the allgather at process_count()==1)
    agg = tl.aggregate(across_hosts=True)
    assert agg["step_time_s"] > 0
    assert agg["env_steps_per_sec"] > 0
    # aggregate() drains the accumulator
    assert tl.aggregate() == {}


def test_set_model_config_rebinding():
    from agilerl_tpu.llm.model import GPTConfig

    reg = MetricsRegistry()
    tl = StepTimeline(reg, memory_stats_every=0)
    assert tl._flops_per_token is None
    cfg = GPTConfig(vocab_size=96, n_layer=2, n_head=4, n_kv_head=2,
                    d_model=32, max_seq_len=64, dtype=jnp.float32)
    tl.set_model_config(cfg)
    assert tl._flops_per_token and tl._flops_per_token > 0
    tl.set_model_config(None)
    assert tl._flops_per_token is None
