#!/usr/bin/env python3
"""Smoke run of the two main paths on the chip, through the entry points a
user calls. One process; it touches JAX itself and starts no other.

    python chip_smoke.py [--seed N]      one TPU chip: RL path, then LLM path
    python chip_smoke.py --chips 4       four chips: the sharded paths only

Default run:

- RL path: ``EvoPPO.make_vmap_generation`` on ``envs.CartPole`` at the
  BASELINE shape (pop=64, envs=128, rollout=64): warm-up + 3 generations,
  each one program (rollout -> GAE -> PPO -> tournament -> mutation).
- LLM path: ``llm.presets.preset("qwen2-7b")`` at its published widths
  (d=3584, 28/4 heads of 128, ff=18944, vocab 152064, qkv bias, untied head)
  with depth as the only cut — the largest K of {6, 4, 2} whose compiled
  learn step plus rollout pool fit the chip. Then the
  ``benchmarking_grpo.py`` loop, ``ReasoningGym`` -> ``GRPO.get_action``
  (paged continuous tier) -> ``assemble_learn_batch`` -> ``GRPO.learn``, for
  3 steps, one ``get_action`` on the default bucketed tier, and the
  kernels-on vs kernels-off logprob comparison on the last batch.

``--chips 4`` runs only: one GRPO learn step on a 4-device fsdp mesh
(``GRPO.to_mesh``) against the same step on one device, and one
``EvoPPO.make_pod_generation`` over the four against the vmap generation.

All weights and data come from ``--seed``. There is no CPU branch: without a
TPU the run fails before any phase. Every line before the last is smoke
output, not a benchmark result. The last line of stdout is the contract's
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from agilerl_tpu.algorithms.grpo import GRPO, make_update_fn
from agilerl_tpu.envs import CartPole
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.presets import preset
from agilerl_tpu.llm.serving import ContinuousGenerator
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
from agilerl_tpu.ops import pallas_enabled
from agilerl_tpu.ops.kernel_mode import active_kill_switches
from agilerl_tpu.parallel.compile_cache import enable_jax_cache
from agilerl_tpu.parallel.mesh import make_mesh
from agilerl_tpu.parallel.population import EvoPPO
from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym

#: the depth ``pick_depth`` chooses on one v5e chip (my chip run, PR 21): the
#: one-device side of the ``--chips 4`` comparison has to hold it
ONE_CHIP_DEPTH = 4

#: kernels on vs off on logprobs of magnitude ~log(vocab) = 8..16, where one
#: bf16 ulp is 2**-4: the mean must agree within one ulp, the worst within 4
LP_MEAN_TOL = 2.0 ** -4
LP_MAX_TOL = 4 * 2.0 ** -4


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


class CompileMeter:
    """Seconds JAX spent in compile-or-load-from-cache, and how often its
    persistent cache hit, read from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self):
        return {"compile_seconds": round(self.seconds, 2),
                "programs": self.programs, "cache_hits": self.cache_hits}


def _flat(tree) -> np.ndarray:
    """Every leaf of a device tree, on the host, as one vector."""
    return np.concatenate(
        [np.ravel(x) for x in jax.tree_util.tree_leaves(jax.device_get(tree))])


def _devices_of(tree) -> set:
    return {d for leaf in jax.tree_util.tree_leaves(tree) for d in leaf.devices()}


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------------- #
# RL path
# --------------------------------------------------------------------------- #


def make_evo(num_envs: int, rollout_len: int) -> EvoPPO:
    """The bench.py evo-PPO construction: CartPole, 64-wide MLP encoders."""
    env = CartPole()
    kind, enc = default_encoder_config(
        env.observation_space, latent_dim=64,
        encoder_config={"hidden_size": (64,)})
    actor_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc, latent_dim=64,
        head=MLPConfig(num_inputs=64, num_outputs=2, hidden_size=(64,)))
    critic_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc, latent_dim=64,
        head=MLPConfig(num_inputs=64, num_outputs=1, hidden_size=(64,)))
    return EvoPPO(
        env, actor_cfg, critic_cfg, D.dist_config_from_space(env.action_space),
        optax.adam(3e-4), num_envs=num_envs, rollout_len=rollout_len,
        update_epochs=1, num_minibatches=4)


def rl_phase(seed: int, pop_size: int, num_envs: int, rollout_len: int,
             generations: int) -> dict:
    """Warm-up plus ``generations`` whole-population generations on the
    default device."""
    evo = make_evo(num_envs, rollout_len)
    pop = evo.init_population(jax.random.PRNGKey(seed), pop_size)
    gen = evo.make_vmap_generation()
    t0 = time.perf_counter()
    pop, fitness = gen(pop, jax.random.PRNGKey(seed + 1))
    jax.block_until_ready(fitness)
    warmup_s = time.perf_counter() - t0
    fits = [np.asarray(fitness)]
    t0 = time.perf_counter()
    for i in range(generations):
        pop, fitness = gen(pop, jax.random.PRNGKey(seed + 2 + i))
        fits.append(np.asarray(fitness))
    jax.block_until_ready(pop)
    run_s = time.perf_counter() - t0
    for f in fits:
        if f.shape != (pop_size,) or not np.isfinite(f).all():
            raise AssertionError(f"bad fitness {f.shape}: {f}")
    device = jax.devices()[0]
    if _devices_of(pop) != {device}:
        raise AssertionError(
            f"population on {_devices_of(pop)}, expected {device}")
    env_steps = pop_size * num_envs * rollout_len * generations
    return {
        "phase": "rl", "pop": pop_size, "envs": num_envs,
        "rollout": rollout_len, "generations": generations,
        "warmup_seconds": round(warmup_s, 2),
        "smoke_env_steps_per_s": round(env_steps / run_s),
        "fitness_mean_first": float(fits[0].mean()),
        "fitness_mean_last": float(fits[-1].mean()),
        "peak_bytes": _peak_bytes(device),
    }


# --------------------------------------------------------------------------- #
# LLM path
# --------------------------------------------------------------------------- #


class IdTokenizer(CharTokenizer):
    """CharTokenizer for the prompts whose ``decode`` keeps every id.

    A random 152064-way head emits ids the char table does not know and
    ``CharTokenizer.decode`` drops them, so every completion would decode to
    the same empty string and every reward, and so every advantage, would be
    equal. The model's vocabulary stays as published; the text the reward
    sees is the ids in decimal."""

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def seeded_reward(seed: int):
    """A reward in [0, 1) hashed from the completion's ids: it varies inside
    a group whenever the sampled completions differ."""

    def reward_fn(completion: str, answer, prompt: str) -> float:
        total = sum(int(w) for w in completion.split())
        return ((total + int(answer)) * 2654435761 + seed) % 1009 / 1009.0

    return reward_fn


def make_rows(seed: int, n: int, prompt_len: int):
    """``n`` dataset rows whose questions tokenize to ``prompt_len - 7`` ..
    ``prompt_len`` ids: one 32-multiple prompt bucket for every batch."""
    rng = np.random.default_rng(seed)
    alphabet = np.array(list("0123456789+-*=() abcdefghijklmnopqrstuvwxyz"))
    rows = []
    for _ in range(n):
        length = prompt_len - int(rng.integers(0, 8))
        rows.append({
            "question": "".join(rng.choice(alphabet, size=length)),
            "answer": int(rng.integers(0, 1000)),
        })
    return rows


def make_agent(seed: int, config, tok, *, group_size: int, rows: int,
               new_tokens: int, base_params=None, **kwargs) -> GRPO:
    """The ``benchmarking_grpo.py`` agent: its defaults for lr, beta, LoRA
    rank and sampling. ``base_params`` may be a tree of shapes — GRPO then
    materialises only the adapters."""
    return GRPO(
        config=config, base_params=base_params,
        pad_token_id=tok.pad_token_id, eos_token_id=tok.eos_token_id,
        group_size=group_size, batch_size=rows, max_output_tokens=new_tokens,
        seed=seed, **kwargs)


def learn_step_program(agent: GRPO, rows: int, seq_len: int):
    """The update ``GRPO.learn`` runs for this agent — ``make_update_fn``
    built as ``GRPO._update_fn`` builds it, under the same
    ``pallas_enabled()`` gate — compiled for a ``[rows, seq_len]`` batch on
    the default device. The agent's base may be a tree of shapes."""
    f32 = jnp.float32
    s = jax.ShapeDtypeStruct
    batch = {
        "tokens": s((rows, seq_len), jnp.int32),
        "mask": s((rows, seq_len), jnp.int32),
        "loss_mask": s((rows, seq_len - 1), f32),
        "old_lp": s((rows, seq_len - 1), f32),
        "ref_lp": s((rows, seq_len - 1), f32),
        "advantage": s((rows,), f32),
    }
    update = make_update_fn(agent.model_config, agent.optimizer.tx,
                            agent.lora_scale, use_flash=pallas_enabled())
    return update.lower(
        agent.base_params, agent.actor.params, agent.optimizer.opt_state,
        batch, s((), f32), s((), f32)).compile()


def pick_depth(seed: int, config, candidates, *, group_size: int, rows: int,
               seq_len: int, new_tokens: int, limit_bytes: int):
    """The largest depth of ``candidates`` whose compiled learn step
    (arguments + outputs + temporaries by ``memory_analysis()``) plus the
    rollout tier's paged pool fit ``limit_bytes``. No weight is
    materialised. Returns ``(config at that depth, a record per depth
    tried)``."""
    tok = IdTokenizer()
    tried = []
    for depth in sorted(candidates, reverse=True):
        cfg = dataclasses.replace(config, n_layer=depth)
        shapes = jax.eval_shape(
            lambda k: M.init_params(k, cfg), jax.random.PRNGKey(seed))
        agent = make_agent(seed, cfg, tok, group_size=group_size, rows=rows,
                           new_tokens=new_tokens, base_params=shapes)
        t0 = time.perf_counter()
        ma = learn_step_program(agent, rows, seq_len).memory_analysis()
        step_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                      + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        gen = ContinuousGenerator(cfg, max_new_tokens=new_tokens)
        pool = jax.eval_shape(
            lambda: M.init_paged_cache(cfg, gen.n_blocks, gen.block_size))
        pool_bytes = sum(x.size * x.dtype.itemsize
                         for x in jax.tree_util.tree_leaves(pool))
        fits = step_bytes + pool_bytes <= limit_bytes
        tried.append({
            "n_layer": depth, "learn_step_bytes": int(step_bytes),
            "pool_bytes": int(pool_bytes), "fits": bool(fits),
            "compile_seconds": round(time.perf_counter() - t0, 2),
        })
        if fits:
            return cfg, tried
    raise RuntimeError(
        f"no depth of {sorted(candidates)} fits {limit_bytes} bytes: {tried}")


def _completion_lp_diff(config, base, lora, lora_scale, ids, mask, loss_mask):
    """|kernels on - kernels off| of ``M.token_logprobs`` over the completion
    positions of one batch: (mean, max, mean |logprob|)."""

    def lp(kernels: bool):
        fn = jax.jit(lambda b, lo, t, m: M.token_logprobs(
            config, b, t, attention_mask=m, lora=lo, lora_scale=lora_scale,
            use_pallas=kernels, flash=kernels))
        return np.asarray(fn(base, lora, ids, mask))

    on, off = lp(True), lp(False)
    keep = np.asarray(loss_mask) > 0
    if not (np.isfinite(on[keep]).all() and np.isfinite(off[keep]).all()):
        raise AssertionError("non-finite logprobs in the kernel comparison")
    diff = np.abs(on[keep] - off[keep])
    return float(diff.mean()), float(diff.max()), float(np.abs(off[keep]).mean())


def llm_phase(seed: int, config, *, prompts_per_step: int, group_size: int,
              prompt_len: int, new_tokens: int, steps: int) -> dict:
    """``steps`` rounds of rollout (continuous tier) + learn, one rollout on
    the default bucketed tier, and kernels on vs off on the last batch."""
    tok = IdTokenizer()
    rows = prompts_per_step * group_size
    n_rows = prompts_per_step * (steps + 2)
    env = ReasoningGym(
        make_rows(seed, n_rows, prompt_len), make_rows(seed + 1, rows, prompt_len),
        tok, reward_fn=seeded_reward(seed), data_batch_size=prompts_per_step)
    agent = make_agent(seed, config, tok, group_size=group_size, rows=rows,
                       new_tokens=new_tokens, continuous_decode=True)
    device = jax.devices()[0]
    if _devices_of(agent.base_params) != {device}:
        raise AssertionError("base weights are not on the default device")

    step_records = []
    prompts = env.reset()
    for step in range(steps):
        before = _flat(agent.actor.params)
        t0 = time.perf_counter()
        comp, cmask = agent.get_action(prompts)
        rollout_s = time.perf_counter() - t0
        info = agent.last_generation_info
        if info is None or "slots" not in info:
            raise AssertionError(
                f"rollout did not go through the continuous tier: {info}")
        ids, masks = env.assemble_learn_batch(comp, cmask)
        prompts, rewards = env.step(comp, cmask)
        if not (rewards.std(axis=1) > 0).all():
            raise AssertionError(f"rewards do not vary in a group: {rewards}")
        t0 = time.perf_counter()
        loss, kl = agent.learn((ids, masks, rewards))
        learn_s = time.perf_counter() - t0
        after = _flat(agent.actor.params)
        if not (np.isfinite(loss) and np.isfinite(kl)):
            raise AssertionError(f"step {step}: loss {loss} kl {kl}")
        if not np.isfinite(after).all() or np.array_equal(before, after):
            raise AssertionError(f"step {step}: LoRA params did not change")
        step_records.append({
            "step": step, "loss": float(loss), "kl": float(kl),
            "reward_mean": float(rewards.mean()),
            "new_tokens": int(cmask.sum()), "learn_tokens": int(ids.size),
            "rollout_seconds": round(rollout_s, 2),
            "learn_seconds": round(learn_s, 2),
            "lora_max_abs_change": float(np.abs(after - before).max()),
        })

    # the default tier: same weights, a fresh agent without continuous_decode
    bucketed = make_agent(seed, config, tok, group_size=group_size, rows=rows,
                          new_tokens=new_tokens, base_params=agent.base_params)
    t0 = time.perf_counter()
    comp_b, cmask_b = bucketed.get_action(prompts)
    bucketed_s = time.perf_counter() - t0
    info_b = bucketed.last_generation_info
    if info_b is None or "row_bucket" not in info_b:
        raise AssertionError(
            f"rollout did not go through the bucketed tier: {info_b}")
    if comp_b.shape != (rows, new_tokens) or not cmask_b.any():
        raise AssertionError(f"bucketed rollout {comp_b.shape} emitted nothing")

    # the attention mask GRPO.learn derives for this batch
    mask = jnp.asarray(ids != tok.pad_token_id, jnp.int32)
    lp_mean, lp_max, lp_mag = _completion_lp_diff(
        config, agent.base_params, agent.actor.params, agent.lora_scale,
        jnp.asarray(ids), mask, masks)
    if lp_mean > LP_MEAN_TOL or lp_max > LP_MAX_TOL:
        raise AssertionError(
            f"kernels on vs off disagree: mean {lp_mean} max {lp_max}")

    learn_text = learn_step_program(agent, *ids.shape).as_text()
    return {
        "phase": "llm", "n_layer": config.n_layer, "d_model": config.d_model,
        "vocab": config.vocab_size, "rows": rows, "seq_len": int(ids.shape[1]),
        "steps": step_records,
        "bucketed_rollout_seconds": round(bucketed_s, 2),
        "bucketed_new_tokens": int(cmask_b.sum()),
        "kernel_lp_mean_abs_diff": lp_mean, "kernel_lp_max_abs_diff": lp_max,
        "lp_mean_abs": lp_mag,
        "learn_step_tpu_custom_calls": learn_text.count("tpu_custom_call"),
        "peak_bytes": _peak_bytes(device),
    }


# --------------------------------------------------------------------------- #
# Four chips: the sharded paths against one device
# --------------------------------------------------------------------------- #


def _bytes_per_device(tree) -> dict:
    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return held


def seeded_batch(seed: int, config, tok, *, prompts: int, group_size: int,
                 prompt_len: int, new_tokens: int):
    """A learn batch without a rollout: ``(ids, action_masks, rewards)`` in
    ``assemble_learn_batch``'s layout, rewards varying inside each group."""
    rng = np.random.default_rng(seed)
    rows = prompts * group_size
    ids = rng.integers(2, config.vocab_size, size=(rows, prompt_len + new_tokens))
    action_masks = np.zeros((rows, prompt_len + new_tokens - 1), np.float32)
    action_masks[:, prompt_len - 1:] = 1.0
    rewards = rng.random((prompts, group_size)).astype(np.float32)
    return ids.astype(np.int32), action_masks, rewards


def _two_learn_steps(agent: GRPO, batch):
    """Completion logprobs before any update, then two updates on one batch
    (the second one's ratio and KL see the first one's LoRA change)."""
    ids, action_masks, _ = batch
    lp = agent.behavior_logprobs(ids, action_masks)
    before = _flat(agent.actor.params)
    losses = [agent.learn(batch) for _ in range(2)]
    delta = _flat(agent.actor.params) - before
    return lp, losses, delta


def mesh_grpo_phase(seed: int, config, devices, *, prompts: int,
                    group_size: int, prompt_len: int, new_tokens: int) -> dict:
    """One batch, the same seed: ``GRPO.learn`` on one device, then on an
    fsdp mesh over ``devices`` via ``GRPO.to_mesh``. The config routes both
    kernels through ``shard_map`` on a mesh (``flash_shard_axes``,
    ``fused_loss_shard_axes``) and calls them plainly off it."""
    tok = IdTokenizer()
    rows = prompts * group_size
    config = dataclasses.replace(
        config, flash_shard_axes=(("dp", "fsdp"), "tp"),
        fused_loss_shard_axes=("dp", "fsdp"))
    batch = seeded_batch(seed, config, tok, prompts=prompts,
                         group_size=group_size, prompt_len=prompt_len,
                         new_tokens=new_tokens)

    def fresh_agent() -> GRPO:
        return make_agent(seed, config, tok, group_size=group_size, rows=rows,
                          new_tokens=new_tokens)

    agent = fresh_agent()
    lp_one, losses_one, delta_one = _two_learn_steps(agent, batch)
    del agent
    gc.collect()

    mesh = make_mesh(dp=1, fsdp=len(devices), tp=1, devices=list(devices))
    agent = fresh_agent()
    agent.to_mesh(mesh)
    held = _bytes_per_device(agent.base_params)
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(agent.base_params))
    shares = {str(d): held.get(d, 0) / total for d in devices}
    want = 1.0 / len(devices)
    if not all(0.8 * want <= s <= 1.2 * want for s in shares.values()):
        raise AssertionError(
            f"base weights are not spread evenly over the mesh: {shares}")
    in_use = {}
    for d in devices:
        stats = d.memory_stats()
        in_use[str(d)] = None if stats is None else stats.get("bytes_in_use")
    with mesh:
        lp_mesh, losses_mesh, delta_mesh = _two_learn_steps(agent, batch)

    keep = batch[1] > 0
    lp_diff = np.abs(lp_one[keep] - lp_mesh[keep])
    cosine = float(delta_one @ delta_mesh / max(
        np.linalg.norm(delta_one) * np.linalg.norm(delta_mesh), 1e-30))
    loss_diff = max(abs(a[0] - b[0]) for a, b in zip(losses_one, losses_mesh))
    record = {
        "phase": "mesh_grpo", "n_layer": config.n_layer, "rows": rows,
        "seq_len": prompt_len + new_tokens, "mesh": dict(mesh.shape),
        "base_share_per_device": shares, "bytes_in_use_per_device": in_use,
        "losses_one_device": [list(map(float, x)) for x in losses_one],
        "losses_mesh": [list(map(float, x)) for x in losses_mesh],
        "lp_mean_abs_diff": float(lp_diff.mean()),
        "lp_max_abs_diff": float(lp_diff.max()),
        "lora_update_cosine": cosine,
        "lora_update_norms": [float(np.linalg.norm(delta_one)),
                              float(np.linalg.norm(delta_mesh))],
    }
    if not np.isfinite(lp_diff).all() or lp_diff.mean() > LP_MEAN_TOL \
            or lp_diff.max() > LP_MAX_TOL:
        raise AssertionError(f"mesh vs one device logprobs disagree: {record}")
    if not np.linalg.norm(delta_mesh) > 0 or cosine < 0.9:
        raise AssertionError(f"mesh vs one device LoRA updates disagree: {record}")
    if not loss_diff <= 1e-3:
        raise AssertionError(f"mesh vs one device losses disagree: {record}")
    return record


def pod_phase(seed: int, devices, pop_size: int, num_envs: int,
              rollout_len: int) -> dict:
    """One ``make_pod_generation`` over ``devices`` against one
    ``make_vmap_generation`` from the same population and key. A sampled
    action can flip on a last-bit difference and change a member's return, so
    the comparison is per member: nine in ten must match, and the means."""
    evo = make_evo(num_envs, rollout_len)

    def fresh_population():  # both programs donate the one they are given
        return evo.init_population(jax.random.PRNGKey(seed), pop_size)

    key = jax.random.PRNGKey(seed + 1)
    _, fit_vmap = evo.make_vmap_generation()(fresh_population(), key)
    mesh = Mesh(np.asarray(list(devices)), axis_names=("pop",))
    pop, fit_pod = evo.make_pod_generation(mesh)(fresh_population(), key)
    fit_vmap, fit_pod = np.asarray(fit_vmap), np.asarray(fit_pod)
    per_device = pop_size // len(devices)
    for leaf in jax.tree_util.tree_leaves(pop):
        if {s.data.shape[0] for s in leaf.addressable_shards} != {per_device} \
                or leaf.devices() != set(devices):
            raise AssertionError(
                f"population leaf {leaf.shape} is not split {per_device} "
                f"members a device over {devices}")
    same = np.isclose(fit_vmap, fit_pod, rtol=1e-3, atol=1e-3)
    record = {
        "phase": "pod", "pop": pop_size, "members_per_device": per_device,
        "fitness_mean_vmap": float(fit_vmap.mean()),
        "fitness_mean_pod": float(fit_pod.mean()),
        "members_matching": float(same.mean()),
    }
    if fit_pod.shape != (pop_size,) or not np.isfinite(fit_pod).all() \
            or same.mean() < 0.9 \
            or not np.isclose(fit_pod.mean(), fit_vmap.mean(), rtol=0.05):
        raise AssertionError(f"pod vs vmap generation disagree: {record}")
    return record


# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="all weights and data are made from it")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded paths and their one-device "
                         "comparison")
    args = ap.parse_args(argv)

    cache_dir = enable_jax_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}"
            f"), nothing ran; compile cache at "
            f"{jax.config.jax_compilation_cache_dir}")
    if len(devices) < args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}")
    if active_kill_switches():
        raise SystemExit(
            f"chip_smoke: kill switches set: {active_kill_switches()}")
    meter = CompileMeter()
    emit(smoke="start", seed=args.seed, chips=args.chips,
         jax_cache_dir_set_in_code=cache_dir,
         device_kind=devices[0].device_kind, devices=len(devices))
    # qwen2-7b's published widths; depth is the only cut, context 2048
    config = preset("qwen2-7b", max_seq_len=2048)
    grpo_shape = dict(group_size=8, prompt_len=256, new_tokens=768)
    t_start = time.perf_counter()

    if args.chips == 4:
        record = mesh_grpo_phase(
            args.seed, dataclasses.replace(config, n_layer=ONE_CHIP_DEPTH),
            devices[:4], prompts=1, **grpo_shape)
        emit(smoke_output=record, **meter.snapshot())
        record = pod_phase(args.seed, devices[:4], pop_size=64, num_envs=128,
                           rollout_len=64)
        emit(smoke_output=record, **meter.snapshot())
    else:
        record = rl_phase(args.seed, pop_size=64, num_envs=128, rollout_len=64,
                          generations=3)
        emit(smoke_output=record, **meter.snapshot())
        limit_bytes = devices[0].memory_stats()["bytes_limit"]
        config, tried = pick_depth(
            args.seed, config, (2, 4, 6), group_size=8, rows=8, seq_len=1024,
            new_tokens=768, limit_bytes=limit_bytes)
        emit(smoke_output={"phase": "pick_depth", "K": config.n_layer,
                           "limit_bytes": limit_bytes, "tried": tried},
             **meter.snapshot())
        record = llm_phase(args.seed, config, prompts_per_step=1, steps=3,
                           **grpo_shape)
        emit(smoke_output=record, **meter.snapshot())
        if not record["learn_step_tpu_custom_calls"]:
            raise AssertionError(
                "the compiled learn step holds no tpu_custom_call: the "
                "kernels did not lower natively")

    emit(smoke="done", seconds=round(time.perf_counter() - t_start, 1),
         **meter.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
