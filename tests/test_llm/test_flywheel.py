"""Online GRPO flywheel (ISSUE 13 tentpole, ROADMAP item 3).

The acceptance gates: a staleness-0 (synchronous) flywheel reproduces the
in-process ``finetune_llm_reasoning`` loss/param stream on the same prompt
set; a staleness-2 run under an injected slow learner completes with ZERO
decode stalls, nonzero stale-dropped batches that are counted and never
trained on; torn weight publishes and torn trajectory batches are
skipped-and-warned (FaultInjector ``path_match``) and never loaded. Plus
the PR's serving regressions: GRPO rollouts route through the fleet router
token-for-token, a weight-epoch bump invalidates the prefix cache on
EVERY replica, and a queued stale prefilled import is dropped instead of
scattering old-epoch KV into a fresh cache."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agilerl_tpu.algorithms.grpo import GRPO, _grpo_loss_core
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.fleet import PrefillWorker, ServingFleet
from agilerl_tpu.llm.flywheel import (
    LearnerPod,
    OnlineGRPOFlywheel,
    RolloutPod,
    TrajectoryBatch,
    TrajectoryStore,
    WeightStore,
)
from agilerl_tpu.llm.serving import ContinuousGenerator
from agilerl_tpu.observability import MemorySink, MetricsRegistry, RunTelemetry
from agilerl_tpu.resilience import FaultInjector
from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym

pytestmark = pytest.mark.flywheel

TOK = CharTokenizer()
CFG = M.GPTConfig(vocab_size=TOK.vocab_size, n_layer=2, n_head=4, d_model=32,
                  max_seq_len=64, dtype=jnp.float32)


def reasoning_rows(n, seed):
    rng = np.random.default_rng(seed)
    return [
        {"question": f"{a}+{b}=", "answer": str(a + b)}
        for a, b in rng.integers(0, 5, (n, 2))
    ]


def spread_reward(completion, answer, prompt):
    """Reward with within-group variance (an all-equal group zeroes the
    advantage and the loss — PR 6's learn-test lesson)."""
    return 0.1 * len(completion) + float(completion.startswith(str(answer)))


def make_env(seed=0):
    return ReasoningGym(reasoning_rows(16, 0), reasoning_rows(4, 1), TOK,
                        reward_fn=spread_reward, data_batch_size=4)


def make_agent(seed=0, **over):
    kw = dict(config=CFG, pad_token_id=TOK.pad_token_id,
              eos_token_id=TOK.eos_token_id, group_size=2, batch_size=8,
              max_output_tokens=4, seed=seed)
    kw.update(over)
    return GRPO(**kw)


def make_flywheel(tmp_path, max_staleness=0, seed=0, **agent_over):
    env = make_env()
    agent = make_agent(seed, **agent_over)
    reg = MetricsRegistry()
    ws = WeightStore(tmp_path / "w", metrics=reg)
    ts = TrajectoryStore(tmp_path / "t", metrics=reg)
    learner = LearnerPod(agent, ws, ts, max_staleness_epochs=max_staleness,
                         metrics=reg)
    rollout = RolloutPod(agent, env, ws, ts, metrics=reg)
    return OnlineGRPOFlywheel(rollout, learner, metrics=reg), reg


# --------------------------------------------------------------------------- #
# stores
# --------------------------------------------------------------------------- #


def test_weight_store_roundtrip_and_gc(tmp_path):
    reg = MetricsRegistry()
    ws = WeightStore(tmp_path, keep_last=2, metrics=reg)
    lora = {"w": np.arange(4, dtype=np.float32)}
    for e in range(4):
        ws.publish(e, {"w": lora["w"] + e})
    # GC keeps the newest keep_last epochs only
    assert ws.epochs() == [2, 3]
    epoch, loaded = ws.load_latest()
    assert epoch == 3
    np.testing.assert_array_equal(loaded["w"], lora["w"] + 3)
    assert reg.counter("flywheel/weight_epochs_published_total").value == 4


def test_trajectory_store_seq_order_and_consume(tmp_path):
    reg = MetricsRegistry()
    ts = TrajectoryStore(tmp_path, metrics=reg)

    def batch(seq, actor=0):
        return TrajectoryBatch(
            seq=seq, actor_id=actor, weight_epoch=0, data_epoch=0,
            ids=np.zeros((2, 4), np.int32), action_masks=np.ones((2, 3)),
            rewards=np.zeros((1, 2)), behavior_lp=np.zeros((2, 3)))

    # out-of-order publishes from two actors read back in global seq order
    ts.publish(batch(1, actor=1))
    ts.publish(batch(0, actor=0))
    ts.publish(batch(2, actor=0))
    assert ts.pending() == 3
    got = ts.poll()
    assert [b.seq for b in got] == [0, 1, 2]
    assert ts.pending() == 0  # consumed
    assert reg.counter("flywheel/trajectories_published_total").value == 3
    assert reg.counter("flywheel/trajectories_consumed_total").value == 3


@pytest.mark.fault_injection
def test_gcd_entry_loads_silently_not_torn(tmp_path):
    """An entry deleted between listing and load (another process's
    keep-last GC — routine in the multi-process deployment) reads as None
    WITHOUT polluting the torn counter, which must stay an integrity
    signal."""
    import shutil

    reg = MetricsRegistry()
    ws = WeightStore(tmp_path, metrics=reg)
    ws.publish(0, {"w": np.zeros(2, np.float32)})
    ws.publish(1, {"w": np.ones(2, np.float32)})
    paths = ws._store.entries()
    shutil.rmtree(paths[0])  # the concurrent GC
    assert ws._store.load(paths[0]) is None
    assert reg.counter("flywheel/torn_weight_publishes_total").value == 0


def test_gc_ignores_digitless_stray_dirs(tmp_path):
    """A stray digitless dir matching the prefix neither counts toward the
    GC keep window (it would displace a real entry) nor gets deleted (it
    isn't ours); readers walk past it like any unloadable entry."""
    reg = MetricsRegistry()
    ws = WeightStore(tmp_path, keep_last=1, metrics=reg)
    (tmp_path / "epoch_junk").mkdir()
    ws.publish(0, {"w": np.zeros(2, np.float32)})
    ws.publish(1, {"w": np.ones(2, np.float32)})
    assert ws.epochs() == [1]                  # real entries GC normally
    assert (tmp_path / "epoch_junk").is_dir()  # junk untouched
    with pytest.warns(RuntimeWarning, match="torn"):
        epoch, _ = ws.load_latest()
    assert epoch == 1


def test_torn_weight_publish_skipped(tmp_path):
    """A truncated weights.pkl is never loaded: readers fall back to the
    previous intact epoch, count the torn entry, and warn once."""
    reg = MetricsRegistry()
    ws = WeightStore(tmp_path, metrics=reg)
    ws.publish(0, {"w": np.zeros(8, np.float32)})
    with FaultInjector(truncate_at_ops=[0], match=("wrote",),
                       path_match="weights.pkl"):
        ws.publish(1, {"w": np.ones(8, np.float32)})
    assert ws.latest_epoch() == 1  # committed, but torn
    with pytest.warns(RuntimeWarning, match="torn"):
        epoch, lora = ws.load_latest()
    assert epoch == 0  # fell back past the torn epoch — never loaded it
    np.testing.assert_array_equal(lora["w"], np.zeros(8, np.float32))
    assert reg.counter("flywheel/torn_weight_publishes_total").value == 1


@pytest.mark.fault_injection
def test_torn_trajectory_skipped_never_trained(tmp_path):
    """A truncated trajectory batch is counted, consumed (cannot wedge the
    queue), and excluded from training."""
    reg = MetricsRegistry()
    ts = TrajectoryStore(tmp_path, metrics=reg)

    def batch(seq):
        return TrajectoryBatch(
            seq=seq, actor_id=0, weight_epoch=0, data_epoch=0,
            ids=np.zeros((2, 4), np.int32), action_masks=np.ones((2, 3)),
            rewards=np.zeros((1, 2)), behavior_lp=np.zeros((2, 3)))

    ts.publish(batch(0))
    with FaultInjector(truncate_at_ops=[0], match=("wrote",),
                       path_match="trajectory.pkl"):
        ts.publish(batch(1))
    ts.publish(batch(2))
    with pytest.warns(RuntimeWarning, match="torn"):
        got = ts.poll()
    assert [b.seq for b in got] == [0, 2]  # torn seq 1 skipped, not loaded
    assert ts.pending() == 0
    assert reg.counter("flywheel/torn_trajectories_total").value == 1


def test_negative_lag_dropped_never_trained(tmp_path):
    """A batch decoded under an epoch NEWER than the learner's (pre-crash
    leftovers, foreign weight line) is dropped and counted like over-budget
    staleness — its behavior record belongs to no epoch this learner can
    correct against."""
    reg = MetricsRegistry()
    ws = WeightStore(tmp_path / "w", metrics=reg)
    ts = TrajectoryStore(tmp_path / "t", metrics=reg)
    learner = LearnerPod(make_agent(0), ws, ts, max_staleness_epochs=2,
                         metrics=reg)
    ts.publish(TrajectoryBatch(
        seq=0, actor_id=0, weight_epoch=5, data_epoch=0,  # lag = 0-5 = -5
        ids=np.zeros((2, 4), np.int32), action_masks=np.ones((2, 3)),
        rewards=np.zeros((1, 2)), behavior_lp=np.zeros((2, 3))))
    assert learner.step() == 1
    assert learner.learn_calls == 0
    assert reg.counter(
        "flywheel/trajectories_dropped_stale_total").value == 1


@pytest.mark.fault_injection
def test_all_torn_gated_poll_does_not_wedge(tmp_path):
    """A gated rollout whose entire in-flight window is torn must not
    wedge the driver: the poll drains the torn entries (counted, never
    returned), the gate reopens, and the run completes normally."""
    fly, reg = make_flywheel(tmp_path, max_staleness=0)
    with FaultInjector(truncate_at_ops=[0], match=("wrote",),
                       path_match="trajectory.pkl"):
        fly.rollout.traj_store.publish(TrajectoryBatch(
            seq=99, actor_id=7, weight_epoch=0, data_epoch=0,
            ids=np.zeros((2, 4), np.int32), action_masks=np.ones((2, 3)),
            rewards=np.zeros((1, 2)), behavior_lp=np.zeros((2, 3))))
    assert not fly.can_rollout()  # max_inflight=1, the torn entry gates
    with pytest.warns(RuntimeWarning, match="torn"):
        fly.run(max_epochs=1)
    assert reg.counter("flywheel/torn_trajectories_total").value == 1
    assert fly.learner.learn_calls == 1  # trained the real batch after


# --------------------------------------------------------------------------- #
# the loss core's importance correction
# --------------------------------------------------------------------------- #


def test_loss_core_rho_neutral_at_one_scales_pg_only():
    rng = np.random.default_rng(0)
    B, T = 4, 6
    lp = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    batch = {
        "loss_mask": jnp.ones((B, T), jnp.float32),
        "old_lp": jnp.asarray(rng.normal(size=(B, T)).astype(np.float32)),
        "ref_lp": jnp.asarray(rng.normal(size=(B, T)).astype(np.float32)),
        "advantage": jnp.asarray(rng.normal(size=(B,)).astype(np.float32)),
    }
    loss0, kl0 = _grpo_loss_core(lp, batch, 0.2, 0.04)
    loss1, kl1 = _grpo_loss_core(
        lp, {**batch, "rho": jnp.ones((B, T), jnp.float32)}, 0.2, 0.04)
    # rho == 1 is exactly neutral
    assert np.allclose(float(loss0), float(loss1)) and np.allclose(
        float(kl0), float(kl1))
    # rho scales ONLY the pg term: with beta=0 the whole loss halves
    loss_h, _ = _grpo_loss_core(
        lp, {**batch, "rho": jnp.full((B, T), 0.5, jnp.float32)}, 0.2, 0.0)
    loss_f, _ = _grpo_loss_core(lp, batch, 0.2, 0.0)
    assert np.allclose(float(loss_h), 0.5 * float(loss_f), rtol=1e-6)


def test_learn_from_trajectory_single_correction_anchor():
    """The clipped-ratio anchor stays at the LEARN-START policy and rho
    corrects the staleness exactly once: a uniformly 0.5-nat-stale
    behavior record scales the beta=0 loss by exactly exp(0.5). The
    behavior-anchored double correction would clip the ratio at 1+clip
    and scale by more (rho^2 lineage) — this pins the decomposition."""
    env = make_env()
    a_ref, a_fly = make_agent(0, beta=0.0), make_agent(0, beta=0.0)
    a_fly.base_params = a_ref.base_params
    prompts = env.reset()
    comp, cmask = a_ref.get_action(prompts)
    # completions of 1..4 tokens: at the first update (ratio 1) the loss is
    # -sum(A_i n_i) / sum(n_i), and with EQUAL lengths a z-scored advantage
    # makes that rounding noise, of which exp(0.5) times says nothing
    cmask = np.array(cmask)
    for row in range(cmask.shape[0]):
        cmask[row, 1 + row % 4:] = 0
    ids, am = env.assemble_learn_batch(comp, cmask)
    _, rewards = env.step(comp, cmask)
    behavior = a_fly.behavior_logprobs(ids, am) - 0.5  # uniformly behind
    loss_ref, _ = a_ref.learn((ids, am, rewards))
    loss_fly, _ = a_fly.learn_from_trajectory(ids, am, rewards, behavior,
                                              rho_clip=2.0)
    assert abs(loss_ref) > 1e-2
    assert np.allclose(loss_fly, np.exp(0.5) * loss_ref, rtol=1e-5)


def test_learn_from_trajectory_matches_learn_at_zero_staleness():
    """The flywheel's synchronous-mode contract at the algorithm level:
    behavior logprobs captured from the CURRENT adapter fed back through
    learn_from_trajectory give the same update as learn()."""
    env = make_env()
    a1, a2 = make_agent(0), make_agent(0)
    a2.base_params = a1.base_params
    prompts = env.reset()
    comp, cmask = a1.get_action(prompts)
    ids, am = env.assemble_learn_batch(comp, cmask)
    _, rewards = env.step(comp, cmask)
    behavior_lp = a2.behavior_logprobs(ids, am)
    loss1, kl1 = a1.learn((ids, am, rewards))
    loss2, kl2 = a2.learn_from_trajectory(ids, am, rewards, behavior_lp)
    assert np.allclose(loss1, loss2, rtol=1e-5)
    assert np.allclose(kl1, kl2, rtol=1e-5)
    for l1, l2 in zip(jax.tree_util.tree_leaves(a1.actor.params),
                      jax.tree_util.tree_leaves(a2.actor.params)):
        assert np.allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)


# --------------------------------------------------------------------------- #
# the acceptance gates
# --------------------------------------------------------------------------- #


def test_sync_flywheel_matches_interleaved_loop(tmp_path):
    """max_staleness_epochs=0 (learner waits each epoch) reproduces the
    in-process finetune_llm_reasoning loss/param stream on the same prompt
    set — THE equivalence gate: same env seed, same agent seed, same key
    consumption order, behavior logprobs standing in for the recomputed
    old logprobs, rho == 1 exactly."""
    from agilerl_tpu.training.train_llm import finetune_llm_reasoning

    sink = MemorySink()
    telem = RunTelemetry(registry=MetricsRegistry(sink=sink), lineage=False)
    env, agent = make_env(), make_agent(0)
    finetune_llm_reasoning(
        [agent], env, max_steps=3, evaluation_interval=10, verbose=False,
        telemetry=telem)
    ref_losses = [e["train/loss"] for e in sink.events
                  if e["kind"] == "metrics" and "train/loss" in e]
    assert len(ref_losses) == 3

    fly, reg = make_flywheel(tmp_path, max_staleness=0, seed=0)
    fly.run(3)
    assert np.allclose(ref_losses, fly.learner.losses, rtol=1e-5, atol=1e-7)
    assert any(abs(l) > 0 for l in ref_losses)  # a 0==0 stream proves nothing
    for l1, l2 in zip(jax.tree_util.tree_leaves(agent.actor.params),
                      jax.tree_util.tree_leaves(
                          fly.learner.agent.actor.params)):
        assert np.allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)
    assert fly.learner.dropped_seqs == []  # sync mode never drops


def test_staleness_budget_drops_counted_never_trained(tmp_path):
    """Injected slow learner (4 rollouts pile up before one learner pass,
    staleness budget 2): decode never stalls, batches at lag 0..2 train,
    the lag-3 batch is dropped, counted, and never trained on."""
    fly, reg = make_flywheel(tmp_path, max_staleness=2, seed=0)
    rollout, learner = fly.rollout, fly.learner
    rollout.poll_weights()
    for _ in range(4):  # the learner is "slow": it never runs in between
        rollout.rollout_once()
    assert rollout.traj_store.pending() == 4
    consumed = learner.step()
    assert consumed == 4
    # lags at consumption: 0, 1, 2 (trained, each publishing a new epoch),
    # then 3 > max_staleness -> dropped
    assert learner.trained_seqs == [0, 1, 2]
    assert learner.dropped_seqs == [3]
    assert learner.learn_calls == 3 and learner.epoch == 3
    assert reg.counter(
        "flywheel/trajectories_dropped_stale_total").value == 1
    assert reg.gauge("flywheel/weight_epoch_lag").value == 3
    # decode never blocked on learn
    assert reg.counter("flywheel/decode_stalls_total").value == 0
    assert reg.counter("flywheel/decode_stall_s").value == 0.0


def test_rollout_once_forwards_greedy(tmp_path, monkeypatch):
    """run(greedy=True) must reach get_action as training=False — a
    dropped flag silently changes the rollout distribution."""
    fly, _ = make_flywheel(tmp_path, max_staleness=0)
    fly.rollout.poll_weights()
    seen = {}
    orig = fly.rollout.agent.get_action

    def spy(prompts, training=True):
        seen["training"] = training
        return orig(prompts, training=training)

    monkeypatch.setattr(fly.rollout.agent, "get_action", spy)
    fly.rollout.rollout_once(greedy=True)
    assert seen["training"] is False
    fly.rollout.rollout_once(greedy=False)
    assert seen["training"] is True


def test_flywheel_run_staleness2_zero_stalls(tmp_path):
    """The interleaved driver at staleness 2 completes with zero decode
    stalls (the inflight gate never engages when the learner keeps up) and
    trains on every batch."""
    fly, reg = make_flywheel(tmp_path, max_staleness=2, seed=0)
    fly.run(3)
    assert fly.learner.epoch == 3
    assert fly.learner.dropped_seqs == []
    assert reg.counter("flywheel/decode_stalls_total").value == 0
    assert all(np.isfinite(l) for l in fly.learner.losses)


# --------------------------------------------------------------------------- #
# serving regressions (the bugfix satellite)
# --------------------------------------------------------------------------- #

SERVE_KW = dict(prompt_buckets=(32,), slots=3, block_size=8, decode_chunk=4)


@pytest.mark.serving
@pytest.mark.fleet
def test_grpo_rollouts_route_through_fleet():
    """continuous_decode group generation through an attached ServingFleet
    is token-for-token identical to the bare-generator path AND actually
    routes through the router (routed counter moves, group repeats hit the
    prefix cache)."""
    a_bare = make_agent(0, continuous_decode=True)
    a_fleet = make_agent(0, continuous_decode=True)
    a_fleet.base_params = a_bare.base_params
    a_fleet.actor.params = jax.tree_util.tree_map(
        jnp.copy, a_bare.actor.params)
    reg = MetricsRegistry()
    fleet = ServingFleet(
        CFG, n_replicas=2, metrics=reg,
        **{**SERVE_KW, **a_fleet._serving_knobs()})
    a_fleet.attach_rollout_fleet(fleet)
    env = make_env()
    prompts = env.reset()
    comp1, mask1 = a_bare.get_action(prompts)
    comp2, mask2 = a_fleet.get_action(prompts)
    np.testing.assert_array_equal(comp1, comp2)
    np.testing.assert_array_equal(mask1, mask2)
    routed = reg.counter("fleet/routed_requests_total").value
    assert routed == comp1.shape[0]  # every group row went through the router
    # group_size=2 repeats of each prompt: the repeat is a prefix hit on
    # the replica that owns the chain (router affinity + replica cache)
    hits = sum(m.gen.metrics.counter("serving/prefix_cache_hits_total").value
               for m in fleet._serving_members().values())
    assert hits > 0


def test_detach_rollout_fleet_restores_decode_path():
    """Detaching a fleet restores the pre-attach continuous_decode setting
    — it must not leave a bucketed-decode agent silently switched onto a
    private bare continuous generator."""
    agent = make_agent(0)
    assert agent.continuous_decode is False
    fleet = ServingFleet(CFG, n_replicas=1, metrics=MetricsRegistry(),
                         **{**SERVE_KW, **agent._serving_knobs()})
    agent.attach_rollout_fleet(fleet)
    assert agent.continuous_decode is True and agent.rollout_fleet is fleet
    agent.attach_rollout_fleet(None)
    assert agent.rollout_fleet is None
    assert agent.continuous_decode is False  # restored, not left True
    # an already-continuous agent stays continuous across attach/detach
    a2 = make_agent(0, continuous_decode=True)
    fleet2 = ServingFleet(CFG, n_replicas=1, metrics=MetricsRegistry(),
                          **{**SERVE_KW, **a2._serving_knobs()})
    a2.attach_rollout_fleet(fleet2)
    a2.attach_rollout_fleet(None)
    assert a2.continuous_decode is True


def test_attach_rollout_fleet_rejects_recipe_mismatch():
    agent = make_agent(0)
    fleet = ServingFleet(
        CFG, n_replicas=1, metrics=MetricsRegistry(),
        **{**SERVE_KW, **{**agent._serving_knobs(), "temperature": 0.123}})
    with pytest.raises(ValueError, match="sampling recipe"):
        agent.attach_rollout_fleet(fleet)


@pytest.mark.serving
@pytest.mark.fleet
def test_weight_bump_invalidates_every_replica():
    """A new adapter tree must flush the prefix cache on EVERY replica at
    its next step — not only the one that served the swap."""
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    lora_a = M.init_lora(jax.random.PRNGKey(1), CFG, 4, ("wq", "wv"))
    lora_b = jax.tree_util.tree_map(lambda x: x + 0.01, lora_a)
    fleet = ServingFleet(CFG, n_replicas=2, metrics=MetricsRegistry(),
                         max_new_tokens=4, pad_id=0, **SERVE_KW)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(3, 90, size=12).astype(np.int32) for _ in range(4)]
    fleet.generate(seqs, jax.random.PRNGKey(2), params, lora=lora_a,
                   greedy=True)
    fleet.generate(seqs, jax.random.PRNGKey(3), params, lora=lora_b,
                   greedy=True)
    for m in fleet._serving_members().values():
        assert m.gen.metrics.counter(
            "serving/prefix_cache_invalidations_total").value >= 1, \
            f"replica {m.rid} kept a stale prefix cache across the swap"


@pytest.mark.serving
def test_stale_prefilled_import_dropped_on_weight_bump():
    """A prefilled import computed under the OLD adapter that is still
    QUEUED (slot-starved) when the weights bump must be dropped and
    recomputed locally — admitting it would scatter stale KV into the pool
    and register it in the fresh prefix cache."""
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    lora_a = M.init_lora(jax.random.PRNGKey(1), CFG, 4, ("wq", "wv"))
    lora_b = jax.tree_util.tree_map(lambda x: x + 0.01, lora_a)
    gen = ContinuousGenerator(CFG, max_new_tokens=8, pad_id=0,
                              prompt_buckets=(32,), slots=1, block_size=8,
                              decode_chunk=4)
    rng = np.random.default_rng(1)
    tok_a = rng.integers(3, 90, size=10).astype(np.int32)
    tok_b = rng.integers(3, 90, size=12).astype(np.int32)
    key_b = jax.random.PRNGKey(7)
    # request A occupies the only slot under lora_a
    ta = gen.submit(tok_a, key=jax.random.PRNGKey(5))
    gen.step(params, lora=lora_a, greedy=True)
    # request B arrives as a prefill-worker import computed under lora_a
    worker = PrefillWorker.matching(gen, metrics=MetricsRegistry())
    payload = worker.prefill(tok_b, key_b, params, lora=lora_a, greedy=True)
    tb = gen.submit_prefilled(
        tok_b, k_prompt=payload["k"], v_prompt=payload["v"],
        tok0=payload["tok0"], done0=payload["done0"],
        key_next=payload["key_next"], key=key_b, no_shed=True)
    # weights bump while B still waits for a slot
    done = list(gen.run_until_drained(params, lora=lora_b, greedy=True))
    assert set(done) == {ta, tb}
    assert gen.metrics.counter(
        "serving/stale_imports_dropped_total").value == 1
    toks_b, _ = gen.result(tb)
    # B must match a fresh all-lora_b reference (local prefill under the
    # NEW weights), not the stale imported prefill
    ref = ContinuousGenerator(CFG, max_new_tokens=8, pad_id=0,
                              prompt_buckets=(32,), slots=1, block_size=8,
                              decode_chunk=4, metrics=MetricsRegistry())
    tr = ref.submit(tok_b, key=key_b)
    ref.run_until_drained(params, lora=lora_b, greedy=True)
    toks_ref, _ = ref.result(tr)
    np.testing.assert_array_equal(toks_b, toks_ref)
