"""Numeric parity for the action-distribution layer (parity:
agilerl/networks/distributions.py — EvolvableDistribution:110, apply_mask:239).

The reference builds on torch.distributions; here torch is the independent
oracle: log_prob / entropy for every family are pinned against
torch.distributions closed forms on shared random inputs, masking is checked
both statistically (masked actions never sampled) and analytically (masked
log-softmax == renormalised over the valid set), and the tanh-squashed Normal
is compared against torch's TransformedDistribution(TanhTransform).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.distributions as tdist

from agilerl_tpu.networks.distributions import (
    DistConfig,
    dist_config_from_space,
    entropy,
    extra_params,
    log_prob,
    mode,
    sample,
)
from gymnasium import spaces

KEY = jax.random.PRNGKey(0)
RTOL = 1e-5
ATOL = 1e-5


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


class TestCategorical:
    CFG = DistConfig(kind="categorical", action_dim=5)

    def test_log_prob_matches_torch(self):
        logits = _rand((7, 5))
        actions = np.array([0, 1, 2, 3, 4, 0, 3])
        ours = log_prob(self.CFG, jnp.asarray(logits), jnp.asarray(actions))
        ref = tdist.Categorical(logits=torch.tensor(logits)).log_prob(
            torch.tensor(actions)
        )
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=RTOL, atol=ATOL)

    def test_entropy_matches_torch(self):
        logits = _rand((7, 5))
        ours = entropy(self.CFG, jnp.asarray(logits))
        ref = tdist.Categorical(logits=torch.tensor(logits)).entropy()
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=RTOL, atol=ATOL)

    def test_sample_frequencies_match_probs(self):
        logits = jnp.asarray([[2.0, 0.0, -1.0, 0.5, 1.0]])
        n = 20_000
        acts = sample(
            self.CFG, jnp.broadcast_to(logits, (n, 5)), KEY
        )
        freqs = np.bincount(np.asarray(acts), minlength=5) / n
        probs = np.asarray(jax.nn.softmax(logits[0]))
        np.testing.assert_allclose(freqs, probs, atol=0.02)

    def test_mask_blocks_sampling_and_renormalises(self):
        logits = _rand((4, 5))
        m = np.array([1, 0, 1, 0, 1], np.float32)
        acts = sample(
            self.CFG, jnp.asarray(np.tile(logits, (500, 1))), KEY,
            mask=jnp.asarray(np.tile(m, (2000, 1))),
        )
        assert not np.isin(np.asarray(acts), [1, 3]).any()
        # masked log_prob == log-softmax renormalised over the valid subset
        ours = log_prob(
            self.CFG, jnp.asarray(logits), jnp.zeros((4,), jnp.int32),
            mask=jnp.asarray(np.tile(m, (4, 1))),
        )
        valid = logits[:, m.astype(bool)]
        ref = valid[:, 0] - np.log(np.exp(valid).sum(axis=1))
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-4, atol=1e-4)

    def test_mode_is_argmax_respecting_mask(self):
        logits = jnp.asarray([[5.0, 10.0, 1.0]])
        cfg = DistConfig(kind="categorical", action_dim=3)
        assert int(mode(cfg, logits)[0]) == 1
        assert int(mode(cfg, logits, mask=jnp.asarray([[1.0, 0.0, 1.0]]))[0]) == 0


class TestMultiDiscrete:
    CFG = DistConfig(kind="multidiscrete", action_dim=9, nvec=(2, 3, 4))

    def test_log_prob_is_sum_of_branches(self):
        logits = _rand((6, 9))
        actions = np.stack(
            [np.random.default_rng(i).integers(0, n, 6) for i, n in enumerate((2, 3, 4))],
            axis=-1,
        )
        ours = log_prob(self.CFG, jnp.asarray(logits), jnp.asarray(actions))
        ref = np.zeros(6)
        for i, (s, n) in enumerate(((0, 2), (2, 3), (5, 4))):
            ref += (
                tdist.Categorical(logits=torch.tensor(logits[:, s : s + n]))
                .log_prob(torch.tensor(actions[:, i]))
                .numpy()
            )
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=RTOL, atol=ATOL)

    def test_entropy_is_sum_of_branches(self):
        logits = _rand((6, 9))
        ours = entropy(self.CFG, jnp.asarray(logits))
        ref = sum(
            tdist.Categorical(logits=torch.tensor(logits[:, s : s + n])).entropy().numpy()
            for s, n in ((0, 2), (2, 3), (5, 4))
        )
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=RTOL, atol=ATOL)

    def test_samples_within_ranges(self):
        acts = np.asarray(sample(self.CFG, jnp.asarray(_rand((1000, 9))), KEY))
        assert acts.shape == (1000, 3)
        for i, n in enumerate((2, 3, 4)):
            assert acts[:, i].min() >= 0 and acts[:, i].max() < n


class TestBernoulli:
    CFG = DistConfig(kind="bernoulli", action_dim=4)

    def test_log_prob_matches_torch(self):
        logits = _rand((5, 4))
        actions = (np.random.default_rng(1).random((5, 4)) < 0.5).astype(np.float32)
        ours = log_prob(self.CFG, jnp.asarray(logits), jnp.asarray(actions))
        ref = (
            tdist.Bernoulli(logits=torch.tensor(logits))
            .log_prob(torch.tensor(actions))
            .sum(-1)
        )
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=RTOL, atol=ATOL)

    def test_entropy_matches_torch(self):
        logits = _rand((5, 4))
        ours = entropy(self.CFG, jnp.asarray(logits))
        ref = tdist.Bernoulli(logits=torch.tensor(logits)).entropy().sum(-1)
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=RTOL, atol=ATOL)

    def test_mode_thresholds_at_zero(self):
        logits = jnp.asarray([[-1.0, 0.5, 3.0, -0.1]])
        np.testing.assert_array_equal(np.asarray(mode(self.CFG, logits))[0], [0, 1, 1, 0])


class TestNormal:
    CFG = DistConfig(kind="normal", action_dim=3, log_std_init=-0.3)

    def _extra(self):
        return {k: jnp.asarray(v) for k, v in extra_params(self.CFG).items()}

    def test_log_prob_matches_torch_diag_normal(self):
        mean = _rand((8, 3))
        actions = _rand((8, 3), seed=2)
        extra = self._extra()
        ours = log_prob(
            self.CFG, jnp.asarray(mean), jnp.asarray(actions), dist_extra=extra
        )
        std = np.exp(np.asarray(extra["log_std"]))
        ref = (
            tdist.Normal(torch.tensor(mean), torch.tensor(std))
            .log_prob(torch.tensor(actions))
            .sum(-1)
        )
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-4, atol=1e-4)

    def test_entropy_matches_torch(self):
        mean = _rand((8, 3))
        extra = self._extra()
        ours = entropy(self.CFG, jnp.asarray(mean), dist_extra=extra)
        std = np.exp(np.asarray(extra["log_std"]))
        ref = (
            tdist.Normal(torch.tensor(mean), torch.tensor(np.tile(std, (8, 1))))
            .entropy()
            .sum(-1)
        )
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-4, atol=1e-4)

    def test_sample_statistics(self):
        mean = jnp.asarray([[0.5, -1.0, 2.0]])
        extra = self._extra()
        acts = np.asarray(
            sample(self.CFG, jnp.broadcast_to(mean, (50_000, 3)), KEY, dist_extra=extra)
        )
        np.testing.assert_allclose(acts.mean(0), np.asarray(mean)[0], atol=0.02)
        np.testing.assert_allclose(
            acts.std(0), np.exp(np.asarray(extra["log_std"])), atol=0.02
        )

    def test_squashed_log_prob_matches_torch_tanh_transform(self):
        cfg = DistConfig(kind="normal", action_dim=3, log_std_init=-0.3, squash=True)
        mean = _rand((8, 3))
        extra = {k: jnp.asarray(v) for k, v in extra_params(cfg).items()}
        u = _rand((8, 3), seed=3)
        a = np.tanh(u).astype(np.float32)
        ours = log_prob(cfg, jnp.asarray(mean), jnp.asarray(a), dist_extra=extra)
        std = np.exp(np.asarray(extra["log_std"]))
        base = tdist.Normal(torch.tensor(mean), torch.tensor(np.tile(std, (8, 1))))
        ref = tdist.TransformedDistribution(
            base, [tdist.transforms.TanhTransform(cache_size=1)]
        ).log_prob(torch.tensor(a)).sum(-1)
        # both sides guard atanh/log with small epsilons — keep tolerance loose
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-3, atol=1e-3)

    def test_squash_bounds_samples_and_mode(self):
        cfg = DistConfig(kind="normal", action_dim=2, log_std_init=0.5, squash=True)
        extra = {k: jnp.asarray(v) for k, v in extra_params(cfg).items()}
        mean = jnp.asarray(np.full((1000, 2), 3.0, np.float32))
        acts = np.asarray(sample(cfg, mean, KEY, dist_extra=extra))
        assert (np.abs(acts) <= 1.0).all()
        assert (np.abs(np.asarray(mode(cfg, mean))) < 1.0).all()


class TestSpaceMapping:
    @pytest.mark.parametrize(
        "space,kind,dim",
        [
            (spaces.Discrete(6), "categorical", 6),
            (spaces.MultiDiscrete([2, 3]), "multidiscrete", 5),
            (spaces.MultiBinary(4), "bernoulli", 4),
            (spaces.Box(-1, 1, (3,)), "normal", 3),
        ],
    )
    def test_config_from_space(self, space, kind, dim):
        cfg = dist_config_from_space(space)
        assert cfg.kind == kind and cfg.action_dim == dim


# --------------------------------------------------------------------------- #
# The chosen action's log-probability is picked by compare-select-reduce, not
# by a gather (PERF.md section 6, PR 30). The gather form lives here as the
# reference: the pick has to equal it bit for bit, in value and in gradient.


def _gather_log_prob(config, logits, action, dist_extra=None, mask=None):
    logits = jnp.where(mask.astype(bool), logits, -1e8) if mask is not None else logits
    nvec = config.nvec if config.kind == "multidiscrete" else (config.action_dim,)
    columns = action if config.kind == "multidiscrete" else action[..., None]
    total, start = 0.0, 0
    for i, n in enumerate(nvec):
        logp = jax.nn.log_softmax(logits[..., start : start + n], axis=-1)
        total = total + jnp.take_along_axis(
            logp, columns[..., i][..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        start += n
    return total


def _bits(x):
    x = np.asarray(x)
    assert x.dtype == np.float32
    return x.view(np.uint32)


def _assert_same_bits(fn, ref, logits, *rest):
    """fn == ref bit for bit, in value and in d(sum of weighted values)/d(logits)."""
    np.testing.assert_array_equal(_bits(fn(logits, *rest)), _bits(ref(logits, *rest)))
    w = jnp.asarray(_rand(np.shape(ref(logits, *rest)), seed=99))
    g_fn = jax.grad(lambda lg: jnp.sum(w * fn(lg, *rest)))(logits)
    g_ref = jax.grad(lambda lg: jnp.sum(w * ref(lg, *rest)))(logits)
    assert np.isfinite(np.asarray(g_ref)).all()
    np.testing.assert_array_equal(_bits(g_fn), _bits(g_ref))


def _actions(shape, n, seed, keep=None):
    """Seeded actions in [0, n); with `keep` (a 0/1 mask over the last axis of
    n) only actions the mask allows."""
    rng = np.random.default_rng(seed)
    if keep is None:
        return rng.integers(0, n, shape)
    allowed = np.flatnonzero(keep)
    return allowed[rng.integers(0, len(allowed), shape)]


class TestChosenLogProbPick:
    @pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
    @pytest.mark.parametrize("n", [2, 3, 6, 18])
    def test_categorical_equals_gather_bit_for_bit(self, n, masked):
        cfg = DistConfig(kind="categorical", action_dim=n)
        logits = jnp.asarray(_rand((5, 33, n), seed=n))
        keep = None
        if masked:
            keep = np.ones(n, np.float32)
            keep[1::2] = 0.0  # every odd action ruled out; action 0 stays
        action = jnp.asarray(_actions((5, 33), n, seed=n + 1, keep=keep))
        mask = None if keep is None else jnp.broadcast_to(jnp.asarray(keep), logits.shape)
        _assert_same_bits(
            lambda lg, a: log_prob(cfg, lg, a, mask=mask),
            lambda lg, a: _gather_log_prob(cfg, lg, a, mask=mask),
            logits, action,
        )

    @pytest.mark.parametrize("how", ["apply_mask", "inf_logits"])
    def test_masked_chosen_action_is_low_not_nan(self, how):
        cfg = DistConfig(kind="categorical", action_dim=3)
        logits = jnp.asarray(_rand((4, 3)))
        action = jnp.asarray([1, 1, 0, 2])
        if how == "apply_mask":
            mask = jnp.asarray(np.tile(np.array([1, 0, 1], np.float32), (4, 1)))
            fn = lambda lg, a: log_prob(cfg, lg, a, mask=mask)  # noqa: E731
            ref = lambda lg, a: _gather_log_prob(cfg, lg, a, mask=mask)  # noqa: E731
        else:
            logits = logits.at[:, 1].set(-jnp.inf)
            fn = lambda lg, a: log_prob(cfg, lg, a)  # noqa: E731
            ref = lambda lg, a: _gather_log_prob(cfg, lg, a)  # noqa: E731
        out = np.asarray(fn(logits, action))
        assert not np.isnan(out).any()
        assert (out[:2] < -1e7).all() and np.isfinite(out[2:]).all()
        if how == "inf_logits":
            assert np.isneginf(out[:2]).all()
        _assert_same_bits(fn, ref, logits, action)

    @pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
    def test_multidiscrete_equals_gather_bit_for_bit(self, masked):
        nvec = (3, 2, 5)
        cfg = DistConfig(kind="multidiscrete", action_dim=sum(nvec), nvec=nvec)
        logits = jnp.asarray(_rand((7, 11, sum(nvec)), seed=5))
        keep = np.ones(sum(nvec), np.float32)
        if masked:
            keep[[1, 4, 6, 9]] = 0.0  # one or two entries of each branch
        bounds = np.cumsum((0,) + nvec)
        action = jnp.asarray(np.stack(
            [_actions((7, 11), n, seed=i, keep=keep[bounds[i]:bounds[i + 1]])
             for i, n in enumerate(nvec)], axis=-1))
        mask = jnp.broadcast_to(jnp.asarray(keep), logits.shape) if masked else None
        _assert_same_bits(
            lambda lg, a: log_prob(cfg, lg, a, mask=mask),
            lambda lg, a: _gather_log_prob(cfg, lg, a, mask=mask),
            logits, action,
        )

    def test_equals_gather_under_vmap_over_a_population(self):
        cfg = DistConfig(kind="categorical", action_dim=6)
        logits = jnp.asarray(_rand((4, 16, 6), seed=3))  # population 4
        action = jnp.asarray(_actions((4, 16), 6, seed=4))
        _assert_same_bits(
            jax.vmap(lambda lg, a: log_prob(cfg, lg, a)),
            jax.vmap(lambda lg, a: _gather_log_prob(cfg, lg, a)),
            logits, action,
        )

    @pytest.mark.parametrize("dtype", [jnp.int8, jnp.int32, jnp.float32])
    def test_action_dtypes(self, dtype):
        cfg = DistConfig(kind="categorical", action_dim=6)
        logits = jnp.asarray(_rand((40, 6), seed=8))
        action = jnp.asarray(_actions((40,), 6, seed=9)).astype(dtype)
        _assert_same_bits(
            lambda lg, a: log_prob(cfg, lg, a),
            lambda lg, a: _gather_log_prob(cfg, lg, a),
            logits, action,
        )

    @pytest.mark.parametrize(
        "cfg",
        [DistConfig(kind="categorical", action_dim=2),
         DistConfig(kind="multidiscrete", action_dim=10, nvec=(3, 2, 5))],
        ids=["categorical", "multidiscrete"],
    )
    def test_lowers_to_no_gather_and_no_scatter(self, cfg):
        logits = jnp.zeros((8, cfg.action_dim))
        action = jnp.zeros((8,) + ((3,) if cfg.nvec else ()), jnp.int32)

        def texts(lp):
            fwd = jax.jit(lambda lg, a: lp(cfg, lg, a))
            bwd = jax.jit(jax.grad(lambda lg, a: jnp.sum(lp(cfg, lg, a))))
            return fwd.lower(logits, action).as_text(), bwd.lower(logits, action).as_text()

        fwd, bwd = texts(log_prob)
        assert "gather" not in fwd and "gather" not in bwd
        assert "scatter" not in fwd and "scatter" not in bwd
        # the reference does hold one of each, so the words are the right ones
        ref_fwd, ref_bwd = texts(_gather_log_prob)
        assert "gather" in ref_fwd and "scatter" in ref_bwd

    @pytest.mark.parametrize("bad", [-1, 3, 100])
    def test_out_of_range_action_selects_nothing(self, bad):
        """What the docstring states: an index outside [0, n) adds 0 and has
        a zero gradient; a negative one does not wrap."""
        cfg = DistConfig(kind="categorical", action_dim=3)
        logits = jnp.asarray(_rand((2, 3)))
        action = jnp.asarray([bad, 1])
        out = np.asarray(log_prob(cfg, logits, action))
        good = np.asarray(log_prob(cfg, logits, jnp.asarray([1, 1])))
        assert out[0] == 0.0 and out[1] == good[1]
        g = np.asarray(jax.grad(lambda lg: jnp.sum(log_prob(cfg, lg, action)))(logits))
        assert (g[0] == 0.0).all() and np.abs(g[1]).sum() > 0

        md = DistConfig(kind="multidiscrete", action_dim=5, nvec=(3, 2))
        md_logits = jnp.asarray(_rand((1, 5), seed=2))
        whole = float(log_prob(md, md_logits, jnp.asarray([[2, 1]]))[0])
        part = float(log_prob(md, md_logits, jnp.asarray([[bad, 1]]))[0])
        second = float(jax.nn.log_softmax(md_logits[0, 3:])[1])
        assert part == np.float32(second) and part != whole
