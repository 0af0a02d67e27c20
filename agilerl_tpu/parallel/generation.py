"""Scan-native generation engine: the shared machinery behind every
fully-on-device evolutionary program (the Anakin tier — Hessel et al.,
*Podracer architectures*, 2021; the single-`lax.scan` shape popularized by
PureJaxRL).

What used to be two hand-built programs (`population.EvoPPO`,
`off_policy.EvoDQN`) is factored into components every value-based and
continuous-control algorithm plugs into:

- :class:`DeviceReplayRing` — a replay ring buffer as a pytree carried
  through ``lax.scan``: uniform sampling, inverse-CDF proportional PER and a
  vectorised sample-time n-step fold, all reusing the exact math proven in
  ``components/replay_buffer.py`` (``_sample`` / ``_per_sample`` /
  ``_per_update``) so the scan tier and the interop tier cannot drift.
- :func:`tournament_select` / :func:`gaussian_mutate` — evolution as pure
  array ops (deterministic same-key tournaments, no rank-0 broadcast),
  shared by every program including the refactored :class:`EvoPPO`.
- :func:`make_vmap_generation` / :func:`make_pod_generation` — the two
  execution contracts every program satisfies: vmapped members on one chip,
  shard_mapped members over a ``"pop"`` mesh axis on a pod. The pod path
  all-gathers ONLY what evolution needs (fitness + the learner pytree) over
  ICI — replay rings and env states stay device-local, which is the bulk of
  the member's HBM footprint.
- :class:`ScanOffPolicy` — the generic off-policy generation builder: one
  scan tick = env step → ring write → gated sample+learn → target update.
  Per-algorithm cores (`EvoDQN`, `EvoRainbow`, `EvoDDPG`, `EvoTD3` in
  ``parallel/off_policy.py``) only define ``_init_learner`` / ``_act`` /
  ``_learn``.
- :class:`ScanRun` — the host-side handle: drives generations, emits
  ``StepTimeline`` env_steps_per_sec through the PR-1 telemetry facade, and
  duck-types the resilience capture protocol (``checkpoint_dict`` /
  ``_restore`` / ``rng_state``) so PR-3 snapshots capture scan-resident
  populations bit-deterministically.

Fitness semantics: running episode returns are SEGMENTED at generation
boundaries — ``evolve`` zeroes the carried ``ep_ret`` so a member's fitness
never mixes returns accrued under the pre-mutation policy with the
post-mutation one (review finding on the old EvoDQN). Fitness is the
censored-return mean: finished episodes contribute their (segment) returns,
episodes still in flight at the window end contribute their partial return
as one observation each — a policy that survives the whole window is scored
by what it accrued, never zero and never an extrapolated leap past measured
members.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.envs.core import JaxEnv, VecState, make_autoreset_step
from agilerl_tpu.utils.spaces import preprocess_observation

PyTree = Any


# --------------------------------------------------------------------------- #
# DeviceReplayRing — the replay buffer as a scan-carried pytree
# --------------------------------------------------------------------------- #


class DeviceReplayRing(NamedTuple):
    """Ring replay buffer living inside the scan carry (per member).

    ``storage`` leaves are ``[capacity, ...]``; ``priorities`` always exists
    (uniform programs simply never read it) so one NamedTuple serves both
    sampling regimes and the pod/vmap pytree structures stay identical."""

    storage: PyTree
    pos: jax.Array  # [] int32 write cursor
    size: jax.Array  # [] int32 current fill
    priorities: jax.Array  # [capacity] float32 (alpha-powered)
    max_priority: jax.Array  # [] float32


def ring_init(example: PyTree, capacity: int) -> DeviceReplayRing:
    """Allocate a ring from an example (unbatched) transition pytree."""

    def alloc(x):
        x = jnp.asarray(x)
        return jnp.zeros((capacity,) + x.shape, x.dtype)

    return DeviceReplayRing(
        storage=jax.tree_util.tree_map(alloc, example),
        pos=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
        priorities=jnp.zeros((capacity,), jnp.float32),
        max_priority=jnp.ones((), jnp.float32),
    )


def ring_write(ring: DeviceReplayRing, batch: PyTree) -> DeviceReplayRing:
    """Write a ``[N, ...]`` transition batch at the cursor (same write order
    and cursor math as ``replay_buffer._add`` / ``_per_add``; new rows get
    the running max priority, exactly what per-step PER adds assign)."""
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    capacity = ring.priorities.shape[0]
    idx = (ring.pos + jnp.arange(n)) % capacity

    def write(buf, x):
        return buf.at[idx].set(x.astype(buf.dtype))

    return DeviceReplayRing(
        storage=jax.tree_util.tree_map(write, ring.storage, batch),
        pos=(ring.pos + n) % capacity,
        size=jnp.minimum(ring.size + n, capacity),
        priorities=ring.priorities.at[idx].set(ring.max_priority),
        max_priority=ring.max_priority,
    )


def ring_sample_uniform(
    ring: DeviceReplayRing, key: jax.Array, batch_size: int
) -> Tuple[PyTree, jax.Array, jax.Array]:
    """Uniform ``(batch, idx, weights)`` — op-for-op the buffer module's
    ``_sample`` (same randint bounds), so the cross-tier equivalence gate
    can replay identical indices from the same key."""
    idx = jax.random.randint(key, (batch_size,), 0, jnp.maximum(ring.size, 1))
    batch = jax.tree_util.tree_map(lambda buf: buf[idx], ring.storage)
    return batch, idx, jnp.ones((batch_size,), jnp.float32)


def ring_sample_per(
    ring: DeviceReplayRing, key: jax.Array, batch_size: int, beta: jax.Array
) -> Tuple[PyTree, jax.Array, jax.Array]:
    """Proportional PER via inverse-CDF on a dense cumsum — the same math as
    ``replay_buffer._per_sample`` (incl. the buffer-global min-priority IS
    normalisation), carried through the scan."""
    size = ring.size
    capacity = ring.priorities.shape[0]
    valid = jnp.arange(capacity) < size
    p = jnp.where(valid, ring.priorities, 0.0)
    cdf = jnp.cumsum(p)
    total = cdf[-1]
    u = jax.random.uniform(key, (batch_size,)) * total
    idx = jnp.searchsorted(cdf, u, side="right")
    idx = jnp.clip(idx, 0, jnp.maximum(size - 1, 0))
    batch = jax.tree_util.tree_map(lambda buf: buf[idx], ring.storage)
    probs = p[idx] / jnp.maximum(total, 1e-12)
    weights = (size.astype(jnp.float32) * probs) ** (-beta)
    p_min = jnp.min(jnp.where(valid, ring.priorities, jnp.inf)) / jnp.maximum(
        total, 1e-12
    )
    max_weight = (size.astype(jnp.float32) * jnp.maximum(p_min, 1e-12)) ** (-beta)
    weights = weights / jnp.maximum(max_weight, 1e-12)
    return batch, idx, weights


def ring_update_priorities(
    ring: DeviceReplayRing, idx: jax.Array, priorities: jax.Array, alpha: jax.Array
) -> DeviceReplayRing:
    """Priority write-back in the same tick (mirrors ``_per_update``: floor,
    alpha power, running max)."""
    powered = jnp.maximum(jnp.abs(priorities), 1e-5) ** alpha
    return ring._replace(
        priorities=ring.priorities.at[idx].set(powered),
        max_priority=jnp.maximum(ring.max_priority, jnp.max(powered)),
    )


def ring_nstep_gather(
    ring: DeviceReplayRing, idx: jax.Array, n_step: int, gamma: float,
    stride: int = 1,
) -> Dict[str, jax.Array]:
    """Vectorised SAMPLE-TIME n-step fold over ring windows.

    The interop tier folds at insert time (``MultiStepReplayBuffer``); a
    scan-carried ring cannot hold a host window, so the fold happens at the
    sampled start indices instead: gamma-fold rewards forward through the
    SAME env's consecutive ring rows, freezing at any episode ``boundary``
    (terminated OR truncated — stored ``done`` stays terminated-only for
    correct bootstrapping, the same split the host fold uses) and at the
    stream head (a window must not wrap past the write cursor into rows
    from a much older time — ``age`` masks those). Returns the folded
    ``reward`` / last-alive ``next_obs`` / ``done`` plus ``steps`` (how many
    rows actually folded per sample) so the learner can bootstrap with
    ``gamma**steps`` — windows clipped at the stream head then stay unbiased
    (k+1)-step returns instead of mislabelled n-step ones.

    ``stride`` is the ring distance between one env's consecutive
    transitions: :class:`ScanOffPolicy` writes an ``[num_envs]`` batch per
    tick (tick-major, env-minor rows), so the same env's next step lives
    ``num_envs`` rows ahead — a stride-1 fold there would mix unrelated env
    streams (review finding). Capacity must be a multiple of ``stride`` so
    wraparound preserves env alignment."""
    capacity = ring.priorities.shape[0]
    assert capacity % stride == 0, (
        f"ring capacity {capacity} must be a multiple of the n-step fold "
        f"stride {stride} (env alignment across wraparound)"
    )
    store = ring.storage
    # rows newer than idx in ring order: age 0 == the newest written row
    age = (ring.pos - 1 - idx) % capacity

    reward = jnp.zeros_like(store["reward"][idx].astype(jnp.float32))
    alive = jnp.ones_like(reward)
    next_obs = jax.tree_util.tree_map(lambda b: b[idx], store["next_obs"])
    done = store["done"][idx].astype(jnp.float32)
    steps = jnp.ones_like(reward)
    discount = 1.0
    for j in range(n_step):
        rows = (idx + j * stride) % capacity
        in_stream = (j * stride <= age).astype(jnp.float32)
        eff = alive * in_stream
        reward = reward + discount * store["reward"][rows].astype(jnp.float32) * eff
        if j > 0:
            upd = eff.astype(bool)
            next_obs = jax.tree_util.tree_map(
                lambda cur, buf: jnp.where(
                    upd.reshape(upd.shape + (1,) * (cur.ndim - upd.ndim)),
                    buf[rows], cur,
                ),
                next_obs, store["next_obs"],
            )
            done = jnp.where(upd, store["done"][rows].astype(jnp.float32), done)
            steps = jnp.where(upd, jnp.float32(j + 1), steps)
        boundary = store["boundary"][rows].astype(jnp.float32)
        alive = alive * (1.0 - boundary) * in_stream
        discount *= gamma
    return {
        "obs": jax.tree_util.tree_map(lambda b: b[idx], store["obs"]),
        "action": store["action"][idx],
        "reward": reward,
        "next_obs": next_obs,
        "done": done,
        "steps": steps,
    }


# --------------------------------------------------------------------------- #
# Evolution as pure array ops (shared by every scan-resident program)
# --------------------------------------------------------------------------- #


def tournament_select(
    fitness: jax.Array,
    key: jax.Array,
    tournament_size: int,
    elitism: bool,
    mutation_prob: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Deterministic tournament: same key on every host => same winners
    everywhere (replaces rank-0 + broadcast_object_list,
    hpo/tournament.py:161). Returns ``(winners [P], do_mut [P], mutate_keys
    [P, 2])`` — the elite slot 0 is never mutated."""
    P = fitness.shape[0]
    k_t, k_m, k_sel = jax.random.split(key, 3)
    entrants = jax.random.randint(k_t, (P, tournament_size), 0, P)
    winners = entrants[jnp.arange(P), jnp.argmax(fitness[entrants], axis=1)]
    if elitism:
        winners = winners.at[0].set(jnp.argmax(fitness))
    do_mut = (jax.random.uniform(k_sel, (P,)) < mutation_prob).astype(jnp.float32)
    if elitism:
        do_mut = do_mut.at[0].set(0.0)
    return winners, do_mut, jax.random.split(k_m, P)


def gaussian_mutate(
    trees: PyTree, keys: jax.Array, do_mut: jax.Array, sd: float
) -> PyTree:
    """Per-member Gaussian parameter mutation over a ``[P, ...]``-stacked
    pytree (vmapped; ``do_mut`` gates each member)."""

    def mutate_member(params, k, do):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        ks = jax.random.split(k, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef,
            [l + do * sd * jax.random.normal(kk, l.shape)
             for l, kk in zip(leaves, ks)],
        )

    return jax.vmap(mutate_member)(trees, keys, do_mut)


def evolve_actor_critic(
    extracted: Tuple[PyTree, PyTree, PyTree],
    fitness: jax.Array,
    key: jax.Array,
    *,
    tournament_size: int,
    elitism: bool,
    mutation_prob: float,
    mutation_sd: float,
) -> Tuple[PyTree, PyTree, PyTree]:
    """Tournament + actor-only Gaussian mutation over an ``(actor, critic,
    opt_state)`` triple — the one evolution step EvoPPO and EvoIPPO share
    (a single owner so the single- and multi-agent semantics cannot
    drift)."""
    actor, critic, opt_state = extracted
    winners, do_mut, mutate_keys = tournament_select(
        fitness, key, tournament_size, elitism, mutation_prob
    )
    gather = lambda x: x[winners]  # noqa: E731
    actor = jax.tree_util.tree_map(gather, actor)
    critic = jax.tree_util.tree_map(gather, critic)
    opt_state = jax.tree_util.tree_map(gather, opt_state)
    actor = gaussian_mutate(actor, mutate_keys, do_mut, mutation_sd)
    return actor, critic, opt_state


# --------------------------------------------------------------------------- #
# The two execution contracts: vmap on one chip, shard_map over a pod
# --------------------------------------------------------------------------- #


def make_vmap_generation(member_iteration: Callable, evolve: Callable) -> Callable:
    """Single-chip: vmapped members + on-device evolution, one donated jit
    (``pop, fitness = gen(pop, key)``)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def generation(pop, key: jax.Array):
        pop, fitness = jax.vmap(member_iteration)(pop)
        pop = evolve(pop, fitness, key)
        return pop, fitness

    return generation


def make_pod_generation(
    mesh,
    member_iteration: Callable,
    extract: Callable,
    evolve_extracted: Callable,
    insert: Callable,
    plan=None,
    pop_axis: str = "pop",
    donate: bool = True,
) -> Callable:
    """Pod-sharded: members shard over the population mesh axis (any number
    per device); training runs locally, then fitness + ONLY the extracted
    learner subtree all-gather over ICI and evolution runs
    replicated-deterministically on every device. Replay rings and env
    states never cross the interconnect — the old per-program pod paths
    gathered the whole member pytree, ring buffers included.

    ``extract(pop_local)`` picks the subtree evolution needs;
    ``evolve_extracted(gathered, fitness, key)`` returns the new ``[P, ...]``
    subtree; ``insert(pop_local, mine)`` splices this device's slice back
    (and applies any boundary resets, e.g. ep_ret segmentation).

    ``plan`` (a :class:`~agilerl_tpu.parallel.plan.ShardingPlan`, or a
    registered name) declares the member layout: its mesh is used when
    ``mesh`` is None, its population axis is the plan's last axis, and the
    member specs come from its ``member`` rule group instead of the
    hard-coded leading-axis split.

    ``donate=False`` compiles without donating the population carry —
    required when the program will be persisted through the executable
    store (``parallel/compile_cache``): this image's jaxlib double-frees
    when a DESERIALIZED executable's multi-device output buffers are
    donated back to it on the next generation (the self-feed pattern);
    the cost is one population copy of transient memory per generation."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if plan is not None:
        from agilerl_tpu.parallel import plan as PL

        plan, mesh = PL.resolve_plan_and_mesh(plan, mesh)
        # the population axis is the plan's LAST mesh axis in build_mesh's
        # canonical order (ordered_axes/AXIS_ORDER — raw dict order would
        # disagree with the mesh the plan itself builds)
        axis_candidates = [a for a, _ in plan.ordered_axes()
                           if a in mesh.axis_names]
        pop_axis = axis_candidates[-1] if axis_candidates else pop_axis
    if mesh is None:
        raise ValueError("make_pod_generation needs a mesh or a plan")
    assert pop_axis in mesh.axis_names

    def member_specs(pop):
        if plan is not None and "member" in plan.rules:
            return plan.resolve("member", pop, mesh)
        return jax.tree_util.tree_map(lambda _: P(pop_axis), pop)

    def gen(pop, key: jax.Array):
        def per_device(pop_local, key):
            pop_local, fit_local = jax.vmap(member_iteration)(pop_local)
            fit_all = jax.lax.all_gather(fit_local, pop_axis, tiled=True)
            gathered = jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, pop_axis, tiled=True),
                extract(pop_local),
            )
            evolved = evolve_extracted(gathered, fit_all, key)
            n_local = jax.tree_util.tree_leaves(pop_local)[0].shape[0]
            my = jax.lax.axis_index(pop_axis)
            mine = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, my * n_local, n_local),
                evolved,
            )
            return insert(pop_local, mine), fit_all

        specs = member_specs(pop)
        return shard_map(
            per_device,
            mesh=mesh,
            in_specs=(specs, P()),
            out_specs=(specs, P()),
            check_vma=False,
        )(pop, key)

    return jax.jit(gen, donate_argnums=(0,) if donate else ())


# --------------------------------------------------------------------------- #
# The generic off-policy generation builder
# --------------------------------------------------------------------------- #


class ScanMemberState(NamedTuple):
    """One member's full scan carry: learner (algorithm-specific params /
    targets / optimizer states), its device-resident replay ring, vectorised
    env state, running episode returns and exploration/cadence scalars."""

    learner: Any
    ring: DeviceReplayRing
    env_state: Any  # VecState
    obs: jax.Array
    ep_ret: jax.Array  # [num_envs], segmented at generation boundaries
    tick: jax.Array  # [] int32 — lifetime env-step ticks (learn cadence)
    learn_count: jax.Array  # [] int32 — lifetime learn steps (target/actor cadence)
    epsilon: jax.Array  # [] float32 exploration scalar (eps-greedy algos)
    key: jax.Array


class ScanOffPolicy:
    """Base engine: composes env-step → ring write → gated sample+learn into
    one ``lax.scan`` tick. Subclasses define the learner pytree and the
    algorithm math:

    - ``_init_learner(key) -> learner``
    - ``_act(learner, obs, epsilon, key) -> actions``  (exploration included)
    - ``_learn(learner, batch, n_batch, weights, key, learn_count)
      -> (learner, loss, td_abs)``
    - ``_action_example() -> unbatched action array`` (ring dtype/shape)
    - ``_mutate_fields`` — learner fields that receive Gaussian mutation
    """

    _mutate_fields: Tuple[str, ...] = ("params",)

    def __init__(
        self,
        env: JaxEnv,
        tx,
        *,
        num_envs: int = 64,
        steps_per_iter: int = 128,
        buffer_size: int = 10_000,
        batch_size: int = 64,
        gamma: float = 0.99,
        tau: float = 0.01,
        learn_every: int = 1,
        warmup: Optional[int] = None,
        per: bool = False,
        per_alpha: float = 0.6,
        per_beta: float = 0.4,
        n_step: int = 1,
        target_every: int = 0,
        prior_eps: float = 1e-6,
        eps_start: float = 1.0,
        eps_decay: float = 0.999,
        eps_end: float = 0.05,
        elitism: bool = True,
        tournament_size: int = 2,
        mutation_sd: float = 0.02,
        mutation_prob: float = 0.5,
    ):
        self.env = env
        self.tx = tx
        self.num_envs = int(num_envs)
        self.steps_per_iter = int(steps_per_iter)
        self.buffer_size = int(buffer_size)
        self.batch_size = int(batch_size)
        self.gamma = float(gamma)
        self.tau = float(tau)
        self.learn_every = int(learn_every)
        self.warmup = int(warmup) if warmup is not None else int(batch_size)
        self.per = bool(per)
        self.per_alpha = float(per_alpha)
        self.per_beta = float(per_beta)
        self.n_step = int(n_step)
        self.target_every = int(target_every)
        self.prior_eps = float(prior_eps)
        self.eps_start = float(eps_start)
        self.eps_decay = float(eps_decay)
        self.eps_end = float(eps_end)
        self.elitism = bool(elitism)
        self.tournament_size = int(tournament_size)
        self.mutation_sd = float(mutation_sd)
        self.mutation_prob = float(mutation_prob)
        if self.n_step > 1 and self.buffer_size % self.num_envs != 0:
            # the ring is tick-major/env-minor and the n-step fold strides by
            # num_envs, so wraparound must preserve env alignment — round the
            # capacity UP to the next multiple rather than making every
            # caller discover the constraint via an exception
            self.buffer_size += self.num_envs - self.buffer_size % self.num_envs
        self._vec_step = make_autoreset_step(env)
        self._reset = jax.vmap(env.reset_fn)
        self.obs_space = env.observation_space

    # -- per-algorithm hooks ------------------------------------------------ #
    def _init_learner(self, key: jax.Array):  # pragma: no cover
        raise NotImplementedError

    def _act(self, learner, obs, epsilon, key):  # pragma: no cover
        raise NotImplementedError

    def _learn(self, learner, batch, n_batch, weights, key, learn_count):
        raise NotImplementedError  # pragma: no cover

    def _action_example(self) -> jax.Array:  # pragma: no cover
        raise NotImplementedError

    # -- shared algorithm plumbing ------------------------------------------ #
    def _td_fields(self, batch, n_batch):
        """The TD target's ingredients from either the 1-step batch or the
        n-step fold: preprocessed ``(obs, reward, done, next_obs, gamma_n)``
        where ``gamma_n`` is the per-sample bootstrap discount
        (``gamma**steps_actually_folded`` for n-step windows). One helper so
        the discrete and continuous cores cannot drift."""
        obs = preprocess_observation(self.obs_space, batch["obs"])
        if n_batch is not None:
            reward = n_batch["reward"]
            done = n_batch["done"]
            next_obs = preprocess_observation(self.obs_space, n_batch["next_obs"])
            gamma_n = jnp.float32(self.gamma) ** n_batch["steps"]
        else:
            reward = batch["reward"].astype(jnp.float32)
            done = batch["done"].astype(jnp.float32)
            next_obs = preprocess_observation(self.obs_space, batch["next_obs"])
            gamma_n = jnp.float32(self.gamma)
        return obs, reward, done, next_obs, gamma_n

    def _update_target(self, target, params, learn_count):
        """Target cadence shared by the value-based cores: hard copy every
        ``target_every`` learns when set, else per-learn polyak with
        ``tau``."""
        if self.target_every > 0:
            hard = (learn_count % self.target_every == 0)
            return jax.tree_util.tree_map(
                lambda t, p: jnp.where(hard, p, t), target, params
            )
        return jax.tree_util.tree_map(
            lambda t, p: (1.0 - self.tau) * t + self.tau * p, target, params
        )

    # -- member init -------------------------------------------------------- #
    @property
    def env_steps_per_generation(self) -> int:
        return self.num_envs * self.steps_per_iter

    def init_member(self, key: jax.Array) -> ScanMemberState:
        k1, k2, k3 = jax.random.split(key, 3)
        learner = self._init_learner(k1)
        env_state, obs = self._reset(jax.random.split(k2, self.num_envs))
        example_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
        example = {
            "obs": example_obs,
            "action": self._action_example(),
            "reward": jnp.float32(0.0),
            "next_obs": example_obs,
            "done": jnp.float32(0.0),
            "boundary": jnp.float32(0.0),
        }
        return ScanMemberState(
            learner=learner,
            ring=ring_init(example, self.buffer_size),
            env_state=VecState(env_state, jnp.zeros(self.num_envs, jnp.int32), k3),
            obs=obs,
            ep_ret=jnp.zeros(self.num_envs),
            tick=jnp.zeros((), jnp.int32),
            learn_count=jnp.zeros((), jnp.int32),
            epsilon=jnp.float32(self.eps_start),
            key=key,
        )

    def init_population(self, key: jax.Array, pop_size: int) -> ScanMemberState:
        return jax.vmap(self.init_member)(jax.random.split(key, pop_size))

    # -- one generation of one member --------------------------------------- #
    def _run_iteration(self, s: ScanMemberState, collect: bool):
        def tick_fn(carry, _):
            s, ep_ret, fsum, fn = carry
            key, k_act, k_samp, k_learn = jax.random.split(s.key, 4)
            obs_in = preprocess_observation(self.obs_space, s.obs)
            action = self._act(s.learner, obs_in, s.epsilon, k_act)
            vstate, next_obs, reward, term, trunc, final_obs = self._vec_step(
                s.env_state, action
            )
            done = jnp.logical_or(term, trunc).astype(jnp.float32)
            transition = {
                "obs": s.obs,
                "action": action,
                "reward": reward.astype(jnp.float32),
                # true successor, pre-autoreset (gymnasium final_observation
                # semantics) so truncated transitions bootstrap correctly
                "next_obs": final_obs,
                "done": term.astype(jnp.float32),
                "boundary": done,
            }
            ring = ring_write(s.ring, transition)
            tick = s.tick + 1
            do_learn = jnp.logical_and(
                ring.size >= jnp.int32(max(self.warmup, self.batch_size)),
                tick % self.learn_every == 0,
            )
            learn_count = s.learn_count + do_learn.astype(jnp.int32)

            def run_learn(args):
                learner, ring = args
                if self.per:
                    batch, idx, weights = ring_sample_per(
                        ring, k_samp, self.batch_size, jnp.float32(self.per_beta)
                    )
                else:
                    batch, idx, weights = ring_sample_uniform(
                        ring, k_samp, self.batch_size
                    )
                n_batch = (
                    ring_nstep_gather(ring, idx, self.n_step, self.gamma,
                                      stride=self.num_envs)
                    if self.n_step > 1 else None
                )
                learner, loss, td_abs = self._learn(
                    learner, batch, n_batch, weights, k_learn, learn_count
                )
                if self.per:
                    ring = ring_update_priorities(
                        ring, idx, td_abs + self.prior_eps,
                        jnp.float32(self.per_alpha),
                    )
                return learner, ring, loss

            def skip_learn(args):
                learner, ring = args
                return learner, ring, jnp.float32(0.0)

            learner, ring, loss = jax.lax.cond(
                do_learn, run_learn, skip_learn, (s.learner, ring)
            )
            ep_ret = ep_ret + reward
            fsum = fsum + jnp.sum(ep_ret * done)
            fn = fn + jnp.sum(done)
            ep_ret = ep_ret * (1.0 - done)
            s = s._replace(
                learner=learner, ring=ring, env_state=vstate, obs=next_obs,
                tick=tick, learn_count=learn_count,
                epsilon=jnp.maximum(s.epsilon * self.eps_decay, self.eps_end),
                key=key,
            )
            ys = None
            if collect:
                ys = {
                    "loss": loss,
                    "do_learn": do_learn,
                    "sample_key": k_samp,
                    "learn_key": k_learn,
                    "transition": transition,
                }
            return (s, ep_ret, fsum, fn), ys

        # derive zero accumulators from obs so they carry the right
        # varying-axis type under shard_map (vma checks)
        zero = 0.0 * jnp.sum(
            jax.tree_util.tree_leaves(s.obs)[0].astype(jnp.float32)
        )
        (s, ep_ret, fsum, fn), ys = jax.lax.scan(
            tick_fn, (s, s.ep_ret + zero, zero, zero), None,
            length=self.steps_per_iter,
        )
        s = s._replace(ep_ret=ep_ret)
        # censored-return fitness: finished episodes contribute their full
        # (segment) return; episodes still in flight at the window end
        # contribute their partial return as one observation each. A policy
        # that survives the whole window is scored by how much it accrued —
        # never zero, and never an extrapolated leap past measured members.
        fitness = (fsum + jnp.sum(ep_ret)) / (fn + self.num_envs)
        return s, fitness, ys

    def member_iteration(self, s: ScanMemberState) -> Tuple[ScanMemberState, jax.Array]:
        s, fitness, _ = self._run_iteration(s, collect=False)
        return s, fitness

    def member_iteration_debug(self, s: ScanMemberState):
        """Like :meth:`member_iteration` but also returns per-tick aux
        (losses, sampling keys, the transitions written) — the cross-tier
        equivalence gate replays these through the interop path."""
        return self._run_iteration(s, collect=True)

    # -- evolution ----------------------------------------------------------- #
    def _evolve_learners(self, learners, fitness: jax.Array, key: jax.Array):
        winners, do_mut, keys = tournament_select(
            fitness, key, self.tournament_size, self.elitism, self.mutation_prob
        )
        gathered = jax.tree_util.tree_map(lambda x: x[winners], learners)
        updates = {
            f: gaussian_mutate(getattr(gathered, f), keys, do_mut, self.mutation_sd)
            for f in self._mutate_fields
        }
        return gathered._replace(**updates)

    def evolve(self, pop: ScanMemberState, fitness: jax.Array, key: jax.Array):
        """Tournament + mutation over the learner pytrees; env state and the
        replay ring stay with the slot. ``ep_ret`` is zeroed: the carried
        partial returns belong to the pre-evolution policy and must not leak
        into the next generation's fitness (segmented-fitness fix)."""
        return pop._replace(
            learner=self._evolve_learners(pop.learner, fitness, key),
            ep_ret=jnp.zeros_like(pop.ep_ret),
        )

    # -- generation programs -------------------------------------------------- #
    def make_vmap_generation(self) -> Callable:
        return make_vmap_generation(self.member_iteration, self.evolve)

    def make_pod_generation(self, mesh=None, plan=None,
                            donate: bool = True) -> Callable:
        return make_pod_generation(
            mesh,
            self.member_iteration,
            extract=lambda pop: pop.learner,
            evolve_extracted=self._evolve_learners,
            insert=lambda pop, mine: pop._replace(
                learner=mine, ep_ret=jnp.zeros_like(pop.ep_ret)
            ),
            plan=plan,
            donate=donate,
        )

    # -- snapshots ------------------------------------------------------------ #
    def state_dict(self, pop: ScanMemberState) -> Dict[str, Any]:
        return population_state_dict(pop)

    def load_state_dict(self, pop: ScanMemberState, blob: Dict[str, Any]):
        return population_load_state_dict(pop, blob)


# --------------------------------------------------------------------------- #
# Population snapshots (host blobs; used by the resilience integration)
# --------------------------------------------------------------------------- #


def population_state_dict(pop: PyTree) -> Dict[str, Any]:
    """Host-picklable capture of a stacked population pytree (leaf order is
    the treedef's; restore validates count/shape/dtype)."""
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(jax.device_get(pop))]
    return {"leaves": leaves}


def population_load_state_dict(pop: PyTree, blob: Dict[str, Any]) -> PyTree:
    """Rebuild a population pytree from :func:`population_state_dict` using
    ``pop`` (a live population of the same program) as the structure
    template — bit-exact round-trip."""
    treedef = jax.tree_util.tree_structure(pop)
    live = jax.tree_util.tree_leaves(pop)
    saved = blob["leaves"]
    if len(saved) != len(live):
        raise ValueError(
            f"snapshot has {len(saved)} leaves, live population has {len(live)}"
        )
    out = []
    for l, s in zip(live, saved):
        if tuple(l.shape) != tuple(s.shape):
            raise ValueError(
                f"snapshot leaf shape {s.shape} != live {tuple(l.shape)}"
            )
        out.append(jnp.asarray(s, dtype=l.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------- #
# ScanRun — the host handle: telemetry + resilience integration
# --------------------------------------------------------------------------- #


class ScanRun:
    """Drives a scan-resident population from the host: one ``run()`` call =
    N generations, each a single device dispatch. Emits ``StepTimeline``
    ``env_steps_per_sec`` (one timeline step per generation) through the
    telemetry facade, and duck-types the resilience capture protocol
    (``checkpoint_dict`` / ``_restore`` / ``rng_state`` / ``set_rng_state``)
    so ``Resilience.attach(pop=[run])`` + ``snapshot()`` / ``resume()``
    capture and restore the whole population bit-deterministically."""

    def __init__(
        self,
        engine,
        pop_size: int,
        seed: int = 0,
        mesh=None,
        telemetry=None,
        index: int = 0,
        plan=None,
    ):
        self.engine = engine
        self.pop_size = int(pop_size)
        if plan is not None:
            from agilerl_tpu.parallel import plan as PL

            plan, mesh = PL.resolve_plan_and_mesh(plan, mesh)
        self.mesh = mesh
        self.plan = plan
        self.telemetry = telemetry
        self.index = index  # lineage/eval-facade compatibility
        key = jax.random.PRNGKey(int(seed))
        init_key, self._key = jax.random.split(key)
        self.pop = engine.init_population(init_key, self.pop_size)
        self.generation = 0
        self.fitness_history: list = []
        self._gen_fn: Optional[Callable] = None

    def _generation_fn(self) -> Callable:
        if self._gen_fn is None:
            self._gen_fn = (
                self.engine.make_pod_generation(self.mesh, plan=self.plan)
                if self.mesh is not None
                else self.engine.make_vmap_generation()
            )
        return self._gen_fn

    def run(self, generations: int) -> np.ndarray:
        """Run N generations; returns the ``[N, P]`` fitness history of this
        call (also appended to ``fitness_history``)."""
        gen = self._generation_fn()
        steps = self.pop_size * self.engine.env_steps_per_generation
        out = []
        for _ in range(int(generations)):
            self._key, k = jax.random.split(self._key)
            t0 = time.perf_counter()
            self.pop, fitness = gen(self.pop, k)
            fitness = np.asarray(jax.block_until_ready(fitness))
            dt = time.perf_counter() - t0
            self.generation += 1
            out.append(fitness)
            self.fitness_history.append(fitness.tolist())
            if self.telemetry is not None:
                self.telemetry.step(
                    env_steps=steps,
                    metrics={
                        "fitness_best": float(fitness.max()),
                        "fitness_mean": float(fitness.mean()),
                        "generation_time_s": dt,
                    },
                )
        return np.asarray(out)

    # -- resilience capture protocol (duck-typed agent) ---------------------- #
    def checkpoint_dict(self) -> Dict[str, Any]:
        sd = population_state_dict(self.pop)
        return {
            "agilerl_tpu_class": type(self).__name__,
            "pop_size": self.pop_size,
            "generation": self.generation,
            "fitness_history": list(self.fitness_history),
            "pop": sd,
        }

    def _restore(self, ckpt: Dict[str, Any]) -> None:
        if int(ckpt["pop_size"]) != self.pop_size:
            raise ValueError(
                f"snapshot pop_size {ckpt['pop_size']} != live {self.pop_size}"
            )
        self.pop = population_load_state_dict(self.pop, ckpt["pop"])
        self.generation = int(ckpt["generation"])
        self.fitness_history = list(ckpt["fitness_history"])

    def rng_state(self) -> Dict[str, Any]:
        return {"key": np.asarray(jax.device_get(self._key))}

    def set_rng_state(self, state: Dict[str, Any]) -> None:
        self._key = jnp.asarray(state["key"])
