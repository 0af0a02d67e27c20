"""The clipped ratio's anchor is optional (ISSUE 34): a ``GRPO.learn`` call of
exactly one optimizer step anchors the ratio at the update's own logprobs
under ``stop_gradient`` and skips the actor's no-grad pass; a call of several
steps, ``learn_from_trajectory`` and every batch that carries ``old_lp`` run
what they always ran. Tiny dense, hybrid and dropless-expert stacks, float32,
on the CPU."""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO, _grpo_loss_core, make_update_fn
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.parallel.mesh import make_mesh, make_sharded_grpo_step

DENSE = M.GPTConfig(vocab_size=61, n_layer=2, n_head=2, d_model=16, d_ff=32,
                    max_seq_len=64, dtype=jnp.float32)
STACKS = {
    "dense": (DENSE, ("wq", "wv")),
    # tests/test_llm/test_hybrid_stack.py's pattern at half its depth
    "hybrid": (M.GPTConfig(
        vocab_size=97, n_layer=4, n_head=4, n_kv_head=1, d_model=32, d_ff=64,
        max_seq_len=128, rope=False, attn_layer_period=4, attn_layer_offset=1,
        mamba_d_state=8, mamba_dt_rank=4, dtype=jnp.float32),
        ("wq", "in_proj")),
    "dropless": (preset("tiny-mla-moe", dtype=jnp.float32, remat=False,
                        use_flash_attention=False), ("wq", "wkv_b")),
}
ROWS, SEQ = 4, 12
ANCHOR_COUNTERS = ("grpo/anchor_reused_total", "grpo/anchor_recomputed_total")


def make_agent(stack="dense", **over):
    config, targets = STACKS[stack]
    kw = dict(config=config, pad_token_id=0, eos_token_id=1, group_size=2,
              batch_size=ROWS, max_output_tokens=4, lora_rank=2,
              lora_targets=targets, lr=1e-3, seed=0)
    kw.update(over)
    agent = GRPO(**kw)
    # B starts at zero: move the actor off the reference, so that the KL term
    # and its gradient are in the comparison
    agent.actor.params = jax.tree_util.tree_map(
        lambda x: x + 0.02 * jnp.cos(jnp.arange(x.size, dtype=x.dtype)
                                     ).reshape(x.shape),
        agent.actor.params)
    return agent


def experiences(config, rows=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, config.vocab_size - 1, size=(rows, SEQ)).astype(
        np.int32)
    action = np.zeros((rows, SEQ - 1), np.int32)
    for row in range(rows):  # completions of unequal length: a loss off zero
        action[row, 4 + row % 3:] = 1
    rewards = rng.normal(size=(rows // 2, 2)).astype(np.float32)
    return ids, action, rewards


def learn_with_anchor_pass(agent, exp):
    """``GRPO.learn`` as it ran before the anchor was optional: the actor's
    no-grad pass, handed to the epoch engine."""
    ids, mask, loss_mask = agent._learn_masks(exp[0], exp[1], None)
    advantage = agent._calculate_advantage(jnp.asarray(exp[2], jnp.float32))
    logprobs, update = agent._resolve_learn_fns(ids, mask)
    old_lp = logprobs(agent.actor.params, ids, mask) * loss_mask
    ref_lp = logprobs(agent.reference.params, ids, mask) * loss_mask
    return agent._run_update_epochs(
        update, ids, mask, loss_mask, old_lp, ref_lp, advantage)


def spy(agent):
    """Record the adapter of every no-grad pass and the keys of every
    minibatch that ``learn`` / ``learn_from_trajectory`` hand on."""
    seen = {"passes": [], "batches": []}
    resolve = agent._resolve_learn_fns

    def spying(ids, mask):
        logprobs, update = resolve(ids, mask)

        def counted_logprobs(lora, *args):
            seen["passes"].append(lora)
            return logprobs(lora, *args)

        def counted_update(lora, opt_state, batch, *args):
            seen["batches"].append(dict(batch))
            return update(lora, opt_state, batch, *args)

        return counted_logprobs, counted_update

    agent._resolve_learn_fns = spying
    return seen


def counters():
    reg = observability.get_registry()
    return [reg.counter(name).value for name in ANCHOR_COUNTERS]


def assert_same_adapters(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a.actor.params),
                    jax.tree_util.tree_leaves(b.actor.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-8)


# --------------------------------------------------------------------------- #
# the loss
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("with_rho", [False, True], ids=["on-policy", "rho"])
def test_loss_core_without_old_lp_is_the_stop_gradient_anchor(with_rho):
    rng = np.random.default_rng(0)
    B, T = 4, 6
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape).astype(np.float32))
    lp = draw(B, T)
    loss_mask = jnp.asarray(rng.integers(0, 2, (B, T)).astype(np.float32))
    batch = {"loss_mask": loss_mask, "ref_lp": draw(B, T) * loss_mask,
             "advantage": draw(B)}
    if with_rho:
        batch["rho"] = jnp.exp(0.3 * draw(B, T))

    def explicit(x):
        anchor = jax.lax.stop_gradient(x * loss_mask)
        return _grpo_loss_core(x, {**batch, "old_lp": anchor}, 0.2, 0.04)

    def implicit(x):
        return _grpo_loss_core(x, batch, 0.2, 0.04)

    (want, want_kl), want_grad = jax.value_and_grad(explicit, has_aux=True)(lp)
    (got, got_kl), got_grad = jax.value_and_grad(implicit, has_aux=True)(lp)
    assert float(got) == float(want) and float(got_kl) == float(want_kl)
    np.testing.assert_array_equal(np.asarray(got_grad), np.asarray(want_grad))
    assert float(jnp.abs(got_grad).sum()) > 0
    # the ratio is 1 in value: the surrogate is -advantage on every token
    pg, _ = _grpo_loss_core(lp, batch, 0.2, 0.0)
    weight = batch.get("rho", 1.0) * loss_mask
    np.testing.assert_allclose(
        float(pg), float(-(batch["advantage"][:, None] * weight).sum()
                         / loss_mask.sum()), rtol=1e-6)


def primitives(jaxpr):
    from jax._src import core

    found = Counter()
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] += 1
        for inner in core.jaxprs_in_params(eqn.params):
            found += primitives(inner)
    return found


def test_the_two_update_programs_differ_by_the_old_lp_operand_alone():
    """One tiny configuration, plain SGD (the step is the gradient): the
    program of a batch with ``old_lp`` and the program of a batch without it
    hold the same operations but for the anchor's — one input against a
    ``stop_gradient`` of the update's own logprobs — and move the adapters
    alike when the input is that very value."""
    config = DENSE
    base = M.init_params(jax.random.PRNGKey(0), config)
    lora = make_agent().actor.params
    reference = jax.tree_util.tree_map(jnp.zeros_like, lora)
    tx = optax.sgd(1e-1)
    update = make_update_fn(config, tx, 2.0, use_flash=False)
    ids, action, rewards = experiences(config)
    tokens, mask = jnp.asarray(ids), jnp.ones(ids.shape, jnp.int32)
    loss_mask = jnp.asarray(action, jnp.float32)
    logprobs = lambda lo: M.token_logprobs(  # noqa: E731
        config, base, tokens, attention_mask=mask, lora=lo, lora_scale=2.0)
    without = {"tokens": tokens, "mask": mask, "loss_mask": loss_mask,
               "ref_lp": logprobs(reference) * loss_mask,
               "advantage": GRPO._calculate_advantage(jnp.asarray(rewards))}
    with_key = {**without, "old_lp": logprobs(lora) * loss_mask}
    args = lambda batch: (base, lora, tx.init(lora), batch, 0.2, 0.04)  # noqa: E731

    jaxprs = {name: jax.make_jaxpr(update)(*args(batch))
              for name, batch in (("with", with_key), ("without", without))}
    assert (len(jaxprs["with"].jaxpr.invars)
            == len(jaxprs["without"].jaxpr.invars) + 1)
    ops = {name: primitives(j.jaxpr) for name, j in jaxprs.items()}
    only_with, only_without = ops["with"] - ops["without"], ops[
        "without"] - ops["with"]
    assert not only_with, only_with
    assert only_without == Counter({"stop_gradient": 1}), only_without

    # the update donates its adapters: a copy a call
    fresh = lambda batch: update(  # noqa: E731
        base, jax.tree_util.tree_map(jnp.copy, lora), tx.init(lora), batch,
        0.2, 0.04)
    lora_w, _, loss_w, kl_w = fresh(with_key)
    lora_o, _, loss_o, kl_o = fresh(without)
    np.testing.assert_allclose(float(loss_o), float(loss_w), rtol=1e-6)
    np.testing.assert_allclose(float(kl_o), float(kl_w), rtol=1e-6)
    assert float(kl_w) > 0
    moved = 0.0
    for before, x, y in zip(*map(jax.tree_util.tree_leaves,
                                 (lora, lora_w, lora_o))):
        np.testing.assert_allclose(np.asarray(y - before),
                                   np.asarray(x - before), rtol=1e-4,
                                   atol=1e-9)
        moved += float(jnp.abs(x - before).sum())
    assert moved > 0


# --------------------------------------------------------------------------- #
# GRPO.learn
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_single_step_learn_equals_the_anchor_pass(stack):
    reused, reference = make_agent(stack), make_agent(stack)
    reference.base_params = reused.base_params
    exp = experiences(STACKS[stack][0])
    before = [np.asarray(x) for x in
              jax.tree_util.tree_leaves(reused.actor.params)]
    loss, kl = reused.learn(exp)
    want_loss, want_kl = learn_with_anchor_pass(reference, exp)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(kl, want_kl, rtol=1e-5)
    assert abs(want_loss) > 1e-3 and want_kl > 0
    assert_same_adapters(reused, reference)
    assert any((np.asarray(x) != b).any() for x, b in zip(
        jax.tree_util.tree_leaves(reused.actor.params), before))


def test_a_single_step_call_skips_the_anchor_pass_and_counts_it():
    agent = make_agent()
    seen = spy(agent)
    reference = agent.reference.params
    was = counters()
    agent.learn(experiences(DENSE))
    assert len(seen["passes"]) == 1 and seen["passes"][0] is reference
    assert [sorted(b) for b in seen["batches"]] == [
        ["advantage", "loss_mask", "mask", "ref_lp", "tokens"]]
    assert counters() == [was[0] + 1, was[1]]
    # fewer rows than batch_size: still one step
    agent.learn(experiences(DENSE, rows=2, seed=1))
    assert len(seen["passes"]) == 2 and counters() == [was[0] + 2, was[1]]


@pytest.mark.parametrize("over, steps", [
    (dict(update_epochs=2), 2), (dict(batch_size=2), 2),
    (dict(update_epochs=2, batch_size=2), 4)],
    ids=["two-epochs", "two-minibatches", "both"])
def test_a_call_of_several_steps_recomputes_the_anchor(over, steps):
    agent, reference = make_agent(**over), make_agent(**over)
    reference.base_params = agent.base_params
    exp = experiences(DENSE)
    seen = spy(agent)
    actor_at_start = agent.actor.params
    ids, mask, loss_mask = agent._learn_masks(exp[0], exp[1], None)
    anchor = np.asarray(agent.jit_fn("logprobs", agent._logprob_fn)(
        actor_at_start, ids, mask) * loss_mask)
    was = counters()
    loss, kl = agent.learn(exp)
    assert counters() == [was[0], was[1] + 1]
    assert [p is actor_at_start for p in seen["passes"]] == [True, False]
    assert len(seen["batches"]) == steps
    # every step's anchor is the learn-start policy's rows, whatever the
    # adapter has done since
    rows = {tuple(r) for r in anchor.round(5).tolist()}
    for batch in seen["batches"]:
        assert {tuple(r) for r in np.asarray(
            batch["old_lp"]).round(5).tolist()} <= rows
    want_loss, want_kl = learn_with_anchor_pass(reference, exp)
    assert loss == want_loss and kl == want_kl
    assert_same_adapters(agent, reference)


def test_learn_from_trajectory_always_recomputes_the_anchor():
    agent = make_agent()  # update_epochs 1, rows <= batch_size
    exp = experiences(DENSE)
    behavior = agent.behavior_logprobs(exp[0], exp[1])
    seen = spy(agent)
    actor_at_start = agent.actor.params
    was = counters()
    agent.learn_from_trajectory(exp[0], exp[1], exp[2], behavior)
    assert [p is actor_at_start for p in seen["passes"]] == [True, False]
    assert all("old_lp" in b and "rho" in b for b in seen["batches"])
    assert counters() == was  # the counters are GRPO.learn's


def test_hpo_mutations_move_the_decision_call_by_call():
    agent = make_agent()
    seen = spy(agent)
    exp = experiences(DENSE)
    agent.learn(exp)
    agent.batch_size = 2  # as a mutation would
    agent.learn(exp)
    agent.batch_size = ROWS
    agent.learn(exp)
    assert ["old_lp" in b for b in seen["batches"]] == [
        False, True, True, False]


# --------------------------------------------------------------------------- #
# the mesh
# --------------------------------------------------------------------------- #


def test_sharded_step_takes_a_batch_without_old_lp():
    config = M.GPTConfig(vocab_size=128, n_layer=2, n_head=4, n_kv_head=2,
                         d_model=32, max_seq_len=64, dtype=jnp.float32)
    kw = dict(config=config, pad_token_id=0, eos_token_id=1, group_size=2,
              batch_size=8, seed=0)
    rng = np.random.default_rng(0)
    B, T = 8, 24
    loss_mask = np.zeros((B, T - 1), np.float32)
    loss_mask[:, T // 2:] = 1.0
    batch = {
        "tokens": jnp.asarray(rng.integers(2, 127, (B, T)).astype(np.int32)),
        "mask": jnp.ones((B, T), jnp.int32),
        "loss_mask": jnp.asarray(loss_mask),
        "ref_lp": jnp.asarray(
            -rng.uniform(1, 5, (B, T - 1)).astype(np.float32) * loss_mask),
        "advantage": jnp.asarray(rng.normal(size=(B,)).astype(np.float32)),
    }
    clip, beta = jnp.float32(0.2), jnp.float32(0.04)

    plain = GRPO(**kw)
    p_lora, _, p_loss, p_kl = plain.jit_fn("update", plain._update_fn)(
        plain.actor.params, plain.optimizer.opt_state, batch, clip, beta)

    mesh = make_mesh(dp=1, fsdp=4, tp=2)
    sharded = GRPO(**kw)
    step = make_sharded_grpo_step(sharded, mesh)
    with mesh:
        s_lora, _, s_loss, s_kl = step(
            sharded.actor.params, sharded.optimizer.opt_state, batch, clip,
            beta)
    np.testing.assert_allclose(float(s_loss), float(p_loss), rtol=1e-5)
    np.testing.assert_allclose(float(s_kl), float(p_kl), rtol=1e-5)
    assert float(p_kl) > 0
    for a, b in zip(jax.tree_util.tree_leaves(s_lora),
                    jax.tree_util.tree_leaves(p_lora)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_a_sampled_pad_id_in_a_completion_stays_attended():
    """The rollout attended to every token it sampled, the pad id among
    them: the learn side's default mask keeps a completion token whatever
    its id, and masks only the prompt's left padding and what follows EOS
    (action mask 0). An explicit attention mask still wins."""
    agent = make_agent()
    ids = np.array([[0, 0, 5, 6, 7, 0, 8, 1, 0, 0]], np.int32)
    action = np.zeros((1, 9), np.int32)
    action[0, 3:7] = 1  # predictions of ids 4..7: 7, the sampled 0, 8, EOS
    _, mask, _ = agent._learn_masks(ids, action, None)
    np.testing.assert_array_equal(mask[0], [0, 0, 1, 1, 1, 1, 1, 1, 0, 0])
    explicit = np.ones_like(ids)
    _, mask, _ = agent._learn_masks(ids, action, explicit)
    np.testing.assert_array_equal(mask, explicit)
