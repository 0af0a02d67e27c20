"""Declarative sharding-plan engine gates (ISSUE 9 acceptance):

- rule-resolved specs byte-identical to the hand-built ``gpt_param_specs`` /
  ``lora_specs`` trees for EVERY llm/presets.py config (+ interleaved MoE);
- plan-driven GRPO step grad-parity vs the legacy ``make_sharded_grpo_step``
  on the 8-device virtual mesh;
- strict mode raises on unmatched leaves; YAML plans round-trip;
- plans degrade gracefully on smaller meshes (the 7B YAML on 8 devices);
- the opt-in sharding-layout mutation swaps layouts without touching
  fitness math.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from agilerl_tpu.algorithms.grpo import GRPO, make_update_fn
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset, preset_names
from agilerl_tpu.parallel import plan as PL
from agilerl_tpu.parallel.mesh import (
    _handbuilt_gpt_param_specs,
    make_mesh,
    make_sharded_grpo_step,
)
from agilerl_tpu.parallel.plan import (
    ShardingPlan,
    UnmatchedLeafError,
    compile_step_with_plan,
    make_grpo_plan,
    match_partition_rules,
)

pytestmark = pytest.mark.sharding

CFG = M.GPTConfig(vocab_size=128, n_layer=2, n_head=4, n_kv_head=2,
                  d_model=32, max_seq_len=64, dtype=jnp.float32)


def _legacy_lora_specs(lora):
    """The pre-engine lora_specs logic, verbatim (the equivalence anchor)."""
    def spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "A":
            return P("fsdp", None)
        if name == "B":
            return P(None, "tp")
        return P()

    return jax.tree_util.tree_map_with_path(spec, lora)


def _assert_spec_trees_equal(got, want):
    mismatches = []

    def cmp(path, a, b):
        if tuple(a) != tuple(b):
            mismatches.append((jax.tree_util.keystr(path), a, b))
        return a

    jax.tree_util.tree_map_with_path(
        cmp, got, want, is_leaf=lambda x: isinstance(x, P))
    assert not mismatches, mismatches[:5]


# --------------------------------------------------------------------------- #
# spec equivalence
# --------------------------------------------------------------------------- #


def _has_leaves_without_a_rule(name):
    cfg = preset(name)
    return cfg.is_hybrid or cfg.is_mla or cfg.is_cca


# the hand-built specs know GQA attention stacks; a hybrid preset's
# state-space leaves and a latent-attention / dropless expert preset's have
# (and a CCA preset's) no rule yet (next test)
@pytest.mark.parametrize(
    "name", [n for n in preset_names() if not _has_leaves_without_a_rule(n)])
def test_plan_params_specs_match_handbuilt_for_every_preset(name):
    cfg = preset(name, max_seq_len=128)
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    plan = make_grpo_plan(fsdp=4, tp=2)
    _assert_spec_trees_equal(
        plan.resolve("params", shapes), _handbuilt_gpt_param_specs(cfg))


@pytest.mark.parametrize(
    "name", [n for n in preset_names() if _has_leaves_without_a_rule(n)])
def test_plan_names_the_hybrid_leaves_it_has_no_rule_for(name):
    """What GRPO.to_mesh's refusal rests on: resolved strictly, the GRPO
    plan lists a hybrid stack's state-space leaves (and a latent-attention
    or dropless expert stack's) instead of silently replicating them."""
    cfg = preset(name, max_seq_len=128)
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    with pytest.raises(UnmatchedLeafError,
                       match="in_proj|conv_w|A_log|wkv_a|kv_norm|ws_gate|"
                             "conv0_w|wv1|router_in|merge1|tau"):
        make_grpo_plan(fsdp=4, tp=2).resolve("params", shapes, strict=True)


def test_plan_params_specs_match_handbuilt_moe():
    cfg = M.GPTConfig(vocab_size=128, n_layer=4, n_head=4, n_kv_head=2,
                      d_model=32, max_seq_len=32, moe_every=2, n_experts=4)
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    plan = make_grpo_plan(fsdp=4, tp=2)
    _assert_spec_trees_equal(
        plan.resolve("params", shapes), _handbuilt_gpt_param_specs(cfg))


def test_plan_lora_specs_match_legacy():
    lora = jax.eval_shape(lambda k: M.init_lora(k, CFG, 8),
                          jax.random.PRNGKey(0))
    plan = make_grpo_plan(fsdp=4, tp=2)
    _assert_spec_trees_equal(plan.resolve("lora", lora),
                             _legacy_lora_specs(lora))


def test_optimizer_rules_shard_moments_like_params():
    """optax paths embed the param path, so the name-matched optimizer rules
    give adam moments their param's spec and scalars replicate — the
    shard_like outcome without the shape heuristic."""
    from agilerl_tpu.algorithms.core.optimizer import OptimizerWrapper

    lora = jax.eval_shape(lambda k: M.init_lora(k, CFG, 8),
                          jax.random.PRNGKey(0))
    opt_shapes = jax.eval_shape(
        OptimizerWrapper(optimizer="adamw", lr=5e-6, max_grad_norm=0.1).tx.init,
        lora)
    plan = make_grpo_plan(fsdp=4, tp=2)
    specs = plan.resolve("optimizer", opt_shapes)
    flat = {
        jax.tree_util.keystr(path): (leaf, spec)
        for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(opt_shapes)[0],
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0])
    }
    saw_moment = False
    for name, (leaf, spec) in flat.items():
        if name.endswith("['A']"):
            assert tuple(spec) == ("fsdp", None), name
            saw_moment = True
        elif name.endswith("['B']"):
            assert tuple(spec) == (None, "tp"), name
        elif leaf.ndim == 0:
            assert tuple(spec) == (), name
    assert saw_moment


# --------------------------------------------------------------------------- #
# matcher semantics
# --------------------------------------------------------------------------- #


def test_strict_mode_raises_on_unmatched_leaf():
    with pytest.raises(UnmatchedLeafError) as ei:
        match_partition_rules(
            [(r"(^|/)weight$", P("fsdp"))],
            {"weight": jnp.zeros((8, 8)), "mystery": jnp.zeros((4, 4))},
            strict=True,
        )
    assert "mystery" in str(ei.value)


def test_scalar_fast_path_skips_rules():
    # even a catch-all sharded rule must not partition scalars / size-1
    specs = match_partition_rules(
        [(r".*", P("fsdp"))],
        {"s": jnp.zeros(()), "one": jnp.zeros((1,)), "v": jnp.zeros((8,))},
    )
    assert tuple(specs["s"]) == ()
    assert tuple(specs["one"]) == ()
    assert tuple(specs["v"]) == ("fsdp",)


def test_rank_guard_orders_moe_vs_dense_rules():
    rules = [
        (r"(^|/)w_gate$", P("ep", "fsdp", "tp")),
        (r"(^|/)w_gate$", P("fsdp", "tp")),
    ]
    specs = match_partition_rules(
        rules,
        {"moe": {"w_gate": jnp.zeros((4, 8, 8))},
         "dense": {"w_gate": jnp.zeros((8, 8))}},
    )
    assert tuple(specs["moe"]["w_gate"]) == ("ep", "fsdp", "tp")
    assert tuple(specs["dense"]["w_gate"]) == ("fsdp", "tp")


def test_non_strict_unmatched_replicates_and_warns_once():
    from agilerl_tpu import observability

    plan = make_grpo_plan(fsdp=4, tp=2)
    tree = {"unmatched_leaf_name": jnp.zeros((8, 8))}
    specs = plan.resolve("params", tree, strict=False)
    assert tuple(specs["unmatched_leaf_name"]) == ()


# --------------------------------------------------------------------------- #
# YAML round-trip + committed plans
# --------------------------------------------------------------------------- #


def test_yaml_round_trip(tmp_path):
    plan = make_grpo_plan(name="rt", dp=2, fsdp=2, tp=2, dcn_dp=2,
                          strict=True, description="round trip")
    path = str(tmp_path / "rt.yaml")
    plan.to_yaml(path)
    loaded = ShardingPlan.from_yaml(path)
    assert loaded.to_dict() == plan.to_dict()
    # rules survive as real PartitionSpecs, including tuple axes
    lora = jax.eval_shape(lambda k: M.init_lora(k, CFG, 8),
                          jax.random.PRNGKey(0))
    _assert_spec_trees_equal(loaded.resolve("lora", lora),
                             plan.resolve("lora", lora))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
    assert tuple(loaded.resolve("batch", batch)["tokens"]) == (("dp", "fsdp"),)


@pytest.mark.parametrize("fname", [
    "grpo_7b_fsdp16xtp4.yaml",
    "grpo_7b_dp2xfsdp8xtp4.yaml",
    "grpo_test_fsdp4xtp2.yaml",
])
def test_committed_yaml_plans_round_trip(fname):
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, os.pardir, "configs", "sharding", fname)
    plan = ShardingPlan.from_yaml(path)
    assert plan.rules.keys() >= {"params", "lora", "optimizer", "batch", "kv"}
    assert plan.to_dict() == ShardingPlan.from_dict(plan.to_dict()).to_dict()
    # the 7B plans must resolve the llama3-8b params tree with ZERO
    # unmatched leaves (strict) — the guarantee the AOT sweep leans on
    shapes = jax.eval_shape(
        lambda k: M.init_params(k, preset("llama3-8b", max_seq_len=128)),
        jax.random.PRNGKey(0))
    plan.resolve("params", shapes, strict=True)


def test_7b_plan_degrades_to_8_device_mesh():
    """filter_spec degradation: the v5p-64 YAML plan resolves and PLACES on
    the 8-device test mesh — one plan file serves every scale point."""
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, os.pardir, "configs", "sharding",
                        "grpo_7b_fsdp16xtp4.yaml")
    plan = ShardingPlan.from_yaml(path)
    mesh = make_mesh(dp=1, fsdp=4, tp=2)  # NOT the plan's own shape
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    placed = plan.place("params", params, mesh)
    assert placed["blocks"]["0"]["wq"].sharding.spec == P("fsdp", "tp")
    # an sp-only mesh carries none of the rule axes -> full replication
    sp_mesh = Mesh(np.asarray(jax.devices()), axis_names=("sp",))
    specs = plan.resolve("params", params, mesh=sp_mesh)
    assert all(
        tuple(s) == () or set(jax.tree_util.tree_leaves(tuple(s))) <= {None}
        for s in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
    )


# --------------------------------------------------------------------------- #
# compile_step_with_plan: grad parity + AOT lowering
# --------------------------------------------------------------------------- #


def _batch(B=8, T=24, seed=0):
    rng = np.random.default_rng(seed)
    lm = np.zeros((B, T - 1), np.float32)
    lm[:, T // 2:] = 1.0
    return {
        "tokens": jnp.asarray(rng.integers(2, 127, size=(B, T)).astype(np.int32)),
        "mask": jnp.ones((B, T), jnp.int32),
        "loss_mask": jnp.asarray(lm),
        "old_lp": jnp.zeros((B, T - 1), jnp.float32),
        "ref_lp": jnp.zeros((B, T - 1), jnp.float32),
        "advantage": jnp.asarray(rng.normal(size=(B,)).astype(np.float32)),
    }


def test_plan_step_grad_parity_vs_make_sharded_grpo_step():
    mesh = make_mesh(dp=1, fsdp=4, tp=2)
    kw = dict(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
              batch_size=8, seed=0)
    legacy = GRPO(**kw)
    legacy_update = make_sharded_grpo_step(legacy, mesh)
    with mesh:
        l_lora, _, l_loss, l_kl = legacy_update(
            legacy.actor.params, legacy.optimizer.opt_state, _batch(),
            jnp.float32(0.2), jnp.float32(0.04))

    agent = GRPO(**kw)
    plan = make_grpo_plan(fsdp=4, tp=2)
    update = make_update_fn(CFG, agent.optimizer.tx,
                            lora_scale=agent.lora_scale, use_flash=False)
    step = compile_step_with_plan(
        update, plan, ("params", "lora", "optimizer", "batch", None, None),
        mesh=mesh, constrain_inputs=False)
    base, lora, opt = step.place_args(
        agent.base_params, agent.actor.params, agent.optimizer.opt_state)[:3]
    p_lora, _, p_loss, p_kl = step(base, lora, opt, _batch(),
                                   jnp.float32(0.2), jnp.float32(0.04))

    np.testing.assert_allclose(float(l_loss), float(p_loss), rtol=1e-6)
    np.testing.assert_allclose(float(l_kl), float(p_kl), rtol=1e-6, atol=1e-8)
    for a, b in zip(jax.tree_util.tree_leaves(l_lora),
                    jax.tree_util.tree_leaves(p_lora)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    # and the updated adapters actually carry the plan's shardings
    a_sh = p_lora["blocks"]["0"]["wq"]["A"].sharding
    assert a_sh.is_equivalent_to(NamedSharding(mesh, P("fsdp", None)), ndim=2)


@pytest.mark.flywheel
def test_flywheel_step_anchor_and_single_correction():
    """make_sharded_flywheel_step mirrors learn_from_trajectory's
    decomposition: the clipped-ratio anchor is the LEARN-START policy's
    logprobs (recomputed, not the shipped behavior record) and the
    staleness correction rho multiplies the pg term exactly once. At
    staleness 0 the step is identical to make_sharded_grpo_step with the
    on-policy anchor; a uniformly-stale behavior record scales the beta=0
    loss by exactly exp(delta) — the behavior-anchored double correction
    would clip the ratio instead."""
    from agilerl_tpu.parallel.mesh import make_sharded_flywheel_step

    mesh = make_mesh(dp=1, fsdp=4, tp=2)
    kw = dict(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
              batch_size=8, seed=0)
    agent = GRPO(**kw)
    fly = make_sharded_flywheel_step(agent, mesh, rho_clip=2.0)
    logprobs = agent.jit_fn("logprobs", agent._logprob_fn)
    batch = _batch()
    with mesh:
        lp_cur = np.asarray(
            logprobs(agent.actor.params, batch["tokens"], batch["mask"])
            * batch["loss_mask"])

    ref = GRPO(**kw)
    ref_update = make_sharded_grpo_step(ref, mesh)
    b_ref = dict(_batch())
    b_ref["old_lp"] = jnp.asarray(lp_cur)  # the on-policy anchor
    b_sync = dict(_batch())
    b_sync.pop("old_lp")
    b_sync["behavior_lp"] = jnp.asarray(lp_cur)  # staleness 0
    with mesh:
        _, _, f_loss, f_kl = fly(agent.actor.params,
                                 agent.optimizer.opt_state, b_sync,
                                 jnp.float32(0.2), jnp.float32(0.0))
        _, _, r_loss, r_kl = ref_update(ref.actor.params,
                                        ref.optimizer.opt_state, b_ref,
                                        jnp.float32(0.2), jnp.float32(0.0))
    np.testing.assert_allclose(float(f_loss), float(r_loss), rtol=1e-6)
    np.testing.assert_allclose(float(f_kl), float(r_kl), rtol=1e-6,
                               atol=1e-8)

    # uniformly behind by 0.5 nats: rho = exp(0.5) < rho_clip on every
    # masked token, ratio stays 1 at the anchor -> loss scales by exactly
    # exp(0.5); the double correction would give clip(exp(0.5)) = 1.2
    agent2 = GRPO(**kw)
    fly2 = make_sharded_flywheel_step(agent2, mesh, rho_clip=2.0)
    b_stale = dict(_batch())
    b_stale.pop("old_lp")
    b_stale["behavior_lp"] = jnp.asarray(lp_cur - 0.5)
    with mesh:
        _, _, s_loss, _ = fly2(agent2.actor.params,
                               agent2.optimizer.opt_state, b_stale,
                               jnp.float32(0.2), jnp.float32(0.0))
    np.testing.assert_allclose(float(s_loss),
                               float(np.exp(0.5)) * float(r_loss),
                               rtol=1e-5)
    # default args adopt an already-placed agent's mesh/plan WITHOUT
    # re-placing (to_mesh clears the jit cache — a full recompile at scale)
    placed_update = agent2.jit_fn("update", agent2._update_fn)
    make_sharded_flywheel_step(agent2)
    assert agent2.jit_fn("update", agent2._update_fn) is placed_update


def test_plan_aot_lowering_carries_shardings():
    """compile_step_with_plan().lower over plan.abstract trees yields a
    module with real sharding annotations — the tpu_aot_compile.py /
    grpo_7b_plan.py path, exercised on the CPU mesh."""
    from agilerl_tpu.algorithms.core.optimizer import OptimizerWrapper

    plan = make_grpo_plan(fsdp=4, tp=2)
    mesh = plan.build_mesh()
    opt = OptimizerWrapper(optimizer="adamw", lr=5e-6, max_grad_norm=0.1)
    base_shapes = jax.eval_shape(lambda k: M.init_params(k, CFG),
                                 jax.random.PRNGKey(0))
    lora_shapes = jax.eval_shape(lambda k: M.init_lora(k, CFG, 8),
                                 jax.random.PRNGKey(0))
    opt_shapes = jax.eval_shape(opt.tx.init, lora_shapes)
    B, T = 8, 24
    batch_shapes = {
        "tokens": jax.ShapeDtypeStruct((B, T), jnp.int32),
        "mask": jax.ShapeDtypeStruct((B, T), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((B, T - 1), jnp.float32),
        "old_lp": jax.ShapeDtypeStruct((B, T - 1), jnp.float32),
        "ref_lp": jax.ShapeDtypeStruct((B, T - 1), jnp.float32),
        "advantage": jax.ShapeDtypeStruct((B,), jnp.float32),
    }
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    update = make_update_fn(CFG, opt.tx, lora_scale=2.0, use_flash=False)
    step = compile_step_with_plan(
        update, plan, ("params", "lora", "optimizer", "batch", None, None),
        mesh=mesh, constrain_inputs=False)
    abs_args = step.abstract_args(base_shapes, lora_shapes, opt_shapes,
                                  batch_shapes, scalar, scalar)
    lowered = step.lower(*abs_args)
    hlo = lowered.as_text()
    assert hlo.count("sdy.sharding") + hlo.count("mhlo.sharding") > 0


def test_constrain_inputs_inserts_cut_points():
    """With constrain_inputs=True the batch group is pinned at entry — the
    step runs and produces the same numbers as the unconstrained path."""
    plan = make_grpo_plan(fsdp=4, tp=2)
    mesh = plan.build_mesh()

    def loss_step(params, batch):
        lp = M.token_logprobs(CFG, params, batch["tokens"],
                              attention_mask=batch["mask"])
        return (lp * batch["loss_mask"]).sum()

    params = M.init_params(jax.random.PRNGKey(0), CFG)
    batch = _batch()
    step = compile_step_with_plan(loss_step, plan, ("params", "batch"),
                                  mesh=mesh, constrain_inputs=True)
    got = step(*step.place_args(params, batch))
    want = loss_step(params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


# --------------------------------------------------------------------------- #
# registry + layout mutation
# --------------------------------------------------------------------------- #


def test_registry_and_device_count_filter():
    names = PL.register_default_plans(8)
    assert len(names) >= 2
    valid = PL.plans_for_device_count(8)
    assert {p.name for p in valid} >= set(names)
    assert all(p.device_count == 8 for p in valid)
    assert PL.get_plan(names[0]).name == names[0]


def test_sharding_layout_mutation_swaps_plans_without_fitness_change():
    """Acceptance gate: a pop=2 GRPO population mutated across two valid
    plans — layout changes, fitness math does not."""
    from agilerl_tpu.hpo.mutation import Mutations

    PL.register_default_plans(8)
    pop = [
        GRPO(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
             batch_size=8, seed=0, index=i)
        for i in range(2)
    ]
    for agent in pop:
        agent.to_mesh(plan="grpo-fsdp8")
    batch = _batch()
    exp = (batch["tokens"], batch["loss_mask"],
           jnp.asarray(np.random.default_rng(3).normal(size=(4, 2)),
                       jnp.float32))
    losses_before = [float(a.learn(exp)[0]) for a in pop]

    # sharding-only mutations, deterministic seed
    mut = Mutations(no_mutation=0.0, architecture=0.0, parameters=0.0,
                    activation=0.0, rl_hp=0.0, sharding=1.0, rand_seed=0,
                    sharding_plans=["grpo-fsdp8", "grpo-fsdp4xtp2"])
    mutated = mut.mutation(pop)
    assert all(m.mut.startswith("sharding:") for m in mutated), (
        [m.mut for m in mutated])
    assert all(m.sharding_plan.name == "grpo-fsdp4xtp2" for m in mutated)

    # fitness math is untouched: the SAME batch yields the SAME loss under
    # the new layout (tolerance = cross-layout reduction reordering)
    losses_after = [float(a.learn(exp)[0]) for a in mutated]
    # both agents took one extra optimizer step before the comparison would
    # be exact; instead compare across members — both layouts must agree
    np.testing.assert_allclose(losses_after[0], losses_after[1],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(losses_before[0], losses_before[1],
                               rtol=1e-4, atol=1e-6)


def test_sharding_mutation_is_opt_in():
    from agilerl_tpu.hpo.mutation import Mutations

    mut = Mutations(rand_seed=0)
    fns = [f for f, _ in [
        (mut.no_mutation, mut.no_mut),
        (mut.architecture_mutate, mut.architecture_mut),
        (mut.parameter_mutation, mut.parameters_mut),
        (mut.activation_mutation, mut.activation_mut),
        (mut.rl_hyperparam_mutation, mut.rl_hp_mut),
    ]]
    assert mut.sharding_mut == 0.0
    # default mutation() option list must not contain sharding_mutation
    # (probability 0 keeps it out entirely)
    pop = [GRPO(config=CFG, pad_token_id=0, eos_token_id=1, group_size=2,
                batch_size=8, seed=0)]
    out = mut.mutation(pop, pre_training_mut=True)
    assert not out[0].mut.startswith("sharding")


# --------------------------------------------------------------------------- #
# pod population layout via plan
# --------------------------------------------------------------------------- #


def test_pod_generation_with_population_plan_matches_mesh_path():
    """EvoPPO pod generation driven by a population plan produces the same
    fitness stream as the hand-built ("pop",) mesh path."""
    import optax

    from agilerl_tpu.envs import CartPole
    from agilerl_tpu.modules.mlp import MLPConfig
    from agilerl_tpu.networks import distributions as D
    from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
    from agilerl_tpu.parallel.population import EvoPPO

    env = CartPole()
    kind, enc = default_encoder_config(env.observation_space, latent_dim=16,
                                       encoder_config={"hidden_size": (16,)})
    actor_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc, latent_dim=16,
        head=MLPConfig(num_inputs=16, num_outputs=2, hidden_size=(16,)))
    critic_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc, latent_dim=16,
        head=MLPConfig(num_inputs=16, num_outputs=1, hidden_size=(16,)))
    algo = EvoPPO(env, actor_cfg, critic_cfg,
                  D.dist_config_from_space(env.action_space),
                  optax.adam(3e-4), num_envs=4, rollout_len=8,
                  update_epochs=1, num_minibatches=2)
    pop = algo.init_population(jax.random.PRNGKey(0), 8)
    key = jax.random.PRNGKey(1)

    mesh = Mesh(np.asarray(jax.devices()), axis_names=("pop",))
    gen_mesh = algo.make_pod_generation(mesh)
    pop_m, fit_m = gen_mesh(pop, key)

    plan = PL.make_population_plan(pop=8)
    gen_plan = algo.make_pod_generation(plan=plan)
    pop2 = algo.init_population(jax.random.PRNGKey(0), 8)
    pop_p, fit_p = gen_plan(pop2, key)

    np.testing.assert_allclose(np.asarray(fit_m), np.asarray(fit_p),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(pop_m),
                    jax.tree_util.tree_leaves(pop_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


# --------------------------------------------------------------------------- #
# serving KV rules
# --------------------------------------------------------------------------- #


def test_kv_rules_on_dense_and_paged_caches():
    plan = make_grpo_plan(fsdp=4, tp=2)
    mesh = plan.build_mesh()
    cache = M.init_caches(CFG, batch=8, max_len=32)
    specs = plan.resolve("kv", cache)
    assert tuple(specs.k) == (None, ("dp", "fsdp"), None, "tp", None)
    assert tuple(specs.mask) == (("dp", "fsdp"),)
    assert tuple(specs.length) == ()
    pool = M.init_paged_cache(CFG, n_blocks=9, block_size=8)
    pspecs = plan.resolve("kv_paged", pool)
    assert tuple(pspecs.k) == (None, None, None, "tp", None)


def test_continuous_generator_pool_uses_paged_rules():
    """Regression (review finding): the paged pool must be placed by the
    kv_paged group — the dense kv rules would shard the GLOBAL block-id
    axis over (dp, fsdp), crashing on any non-divisible n_blocks."""
    from agilerl_tpu.llm.serving import ContinuousGenerator

    cfg = M.GPTConfig(vocab_size=128, n_layer=2, n_head=4, n_kv_head=2,
                      d_model=64, max_seq_len=128, dtype=jnp.float32)
    plan = make_grpo_plan(fsdp=4, tp=2)
    gen = ContinuousGenerator(cfg, max_new_tokens=8, pad_id=0, eos_id=None,
                              prompt_buckets=(16,), slots=2, block_size=8,
                              n_blocks=9,  # NOT divisible by fsdp*dp=4
                              decode_chunk=8, sharding_plan=plan)
    gen._ensure_pool()
    spec = gen._pool.k.sharding.spec
    # kv-heads axis sharded over tp; block axis untouched
    assert spec == P(None, None, None, "tp", None) or spec == P(
        None, None, None, "tp"), spec


def test_pop_axis_follows_build_mesh_order():
    """Regression (review finding): the pod path must pick the population
    axis in build_mesh's canonical order, not dict insertion order."""
    plan = ShardingPlan(
        name="pop-first-dict-order", axes={"pop": 8, "fsdp": 1},
        rules={"member": PL.member_rules()})
    mesh = plan.build_mesh()
    assert mesh.axis_names[-1] == "pop"
    ordered = [a for a, _ in plan.ordered_axes()]
    assert ordered[-1] == "pop"


def test_bucketed_generator_with_plan_matches_unsharded():
    from agilerl_tpu.llm.serving import BucketedGenerator

    cfg = M.GPTConfig(vocab_size=128, n_layer=2, n_head=4, n_kv_head=2,
                      d_model=64, max_seq_len=128, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(2, 127, size=rng.integers(4, 16)).astype(np.int32)
            for _ in range(5)]
    ref_gen = BucketedGenerator(cfg, max_new_tokens=8, pad_id=0, eos_id=None,
                                prompt_buckets=(16,), row_buckets=(8,),
                                decode_chunk=8)
    ref, ref_mask, _ = ref_gen.generate(seqs, jax.random.PRNGKey(1), params,
                                        greedy=True)

    plan = make_grpo_plan(fsdp=4, tp=2)
    gen = BucketedGenerator(cfg, max_new_tokens=8, pad_id=0, eos_id=None,
                            prompt_buckets=(16,), row_buckets=(8,),
                            decode_chunk=8, sharding_plan=plan)
    placed = gen.place_params(params)
    assert placed["blocks"]["0"]["wq"].sharding.spec == P("fsdp", "tp")
    out, out_mask, _ = gen.generate(seqs, jax.random.PRNGKey(1), placed,
                                    greedy=True)
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(out_mask, ref_mask)
