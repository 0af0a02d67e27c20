"""``shuffled_minibatches`` moves rows as payload of the permutation's sorts;
the expression it replaced, ``x[jax.random.permutation(key, total)]`` leaf by
leaf, is kept here as the reference. Equality is exact everywhere: same key,
same minibatches, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from agilerl_tpu.components.rollout_buffer import (
    MAX_SORT_CARRIED_ROW,
    shuffled_minibatches,
)
from agilerl_tpu.parallel import population
from tests.test_parallel.test_population import make_evo


def gathered_minibatches(key, flat, num_minibatches, minibatch_size):
    total = jax.tree_util.tree_leaves(flat)[0].shape[0]
    perm = jax.random.permutation(key, total)[: num_minibatches * minibatch_size]
    return jax.tree_util.tree_map(
        lambda x: x[perm].reshape((num_minibatches, minibatch_size) + x.shape[1:]),
        flat)


def rollout_rows(key, total):
    ks = jax.random.split(key, 6)
    image = jax.random.randint(ks[5], (total, 6, 6, 2), 0, 256).astype(jnp.uint8)
    assert image[0].size > MAX_SORT_CARRIED_ROW  # the leaf that is gathered
    return {
        "logp": jax.random.normal(ks[0], (total,)),
        "adv": jax.random.normal(ks[1], (total,)),
        "ret": jax.random.normal(ks[2], (total,)),
        "action": jax.random.randint(ks[3], (total,), 0, 2),
        "obs": {"vector": jax.random.normal(ks[4], (total, 4)), "image": image},
    }


def assert_trees_identical(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g), np.asarray(w))


# one round of sorting up to 1625 rows, two beyond; 2050 = 4 x 512 + 2 leaves
# rows over; (1000, 3, 300) is PPO.scan_learn's cut, led by the batch size
CUTS = [(1000, 4, 250), (2050, 4, 512), (4096, 4, 1024), (1000, 3, 300)]


@pytest.mark.parametrize("total,num_minibatches,minibatch_size", CUTS)
def test_equals_the_gather_by_permutation(total, num_minibatches, minibatch_size):
    flat = rollout_rows(jax.random.PRNGKey(total), total)
    key = jax.random.PRNGKey(3)
    got = jax.jit(shuffled_minibatches, static_argnums=(2, 3))(
        key, flat, num_minibatches, minibatch_size)
    assert_trees_identical(
        got, gathered_minibatches(key, flat, num_minibatches, minibatch_size))
    # with narrow leaves only, no index column rides the sort
    del flat["obs"]["image"]
    assert_trees_identical(
        shuffled_minibatches(key, flat, num_minibatches, minibatch_size),
        gathered_minibatches(key, flat, num_minibatches, minibatch_size))


@pytest.mark.parametrize("total,num_minibatches,minibatch_size", CUTS)
def test_equals_the_gather_under_vmap_with_a_key_a_member(
        total, num_minibatches, minibatch_size):
    members = 3
    flat = jax.vmap(lambda k: rollout_rows(k, total))(
        jax.random.split(jax.random.PRNGKey(total), members))
    keys = jax.random.split(jax.random.PRNGKey(9), members)
    got = jax.jit(jax.vmap(lambda k, f: shuffled_minibatches(
        k, f, num_minibatches, minibatch_size)))(keys, flat)
    want = jax.vmap(lambda k, f: gathered_minibatches(
        k, f, num_minibatches, minibatch_size))(keys, flat)
    assert_trees_identical(got, want)


def test_member_iteration_is_bit_identical_to_the_gathers(monkeypatch):
    # 64 x 32 = 2048 rows: two rounds, as at the benchmark's size
    sizes = dict(num_envs=64, rollout_len=32, update_epochs=2, num_minibatches=4)
    state = make_evo(**sizes).init_member(jax.random.PRNGKey(0))
    got = jax.jit(make_evo(**sizes).member_iteration)(state)
    monkeypatch.setattr(population, "shuffled_minibatches", gathered_minibatches)
    want = jax.jit(make_evo(**sizes).member_iteration)(state)
    assert_trees_identical(got, want)  # actor, critic, opt_state, env, key, fitness


def test_pod_generation_is_bit_identical_to_the_gathers(monkeypatch):
    devices = jax.devices()
    assert len(devices) == 8, "conftest must provide 8 CPU devices"
    mesh = Mesh(np.asarray(devices), axis_names=("pop",))
    pop = make_evo(num_envs=4, rollout_len=8).init_population(
        jax.random.PRNGKey(0), pop_size=8)
    key = jax.random.PRNGKey(1)
    got = make_evo(num_envs=4, rollout_len=8).make_pod_generation(mesh)(pop, key)
    monkeypatch.setattr(population, "shuffled_minibatches", gathered_minibatches)
    want = make_evo(num_envs=4, rollout_len=8).make_pod_generation(mesh)(pop, key)
    assert_trees_identical(got, want)
