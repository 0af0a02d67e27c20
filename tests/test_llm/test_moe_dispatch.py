"""The dropless dispatch (``moe.dropless_experts``): one permutation a
forward, rows moved by gathers both ways. Against a plain per-pair
computation (every pair through its own expert, no sort) and against the
argsort formulation it replaced (``argsort_experts`` below: ``order`` from
one sort, its inverse from a second, the group sizes from a scatter-add,
rows moved by ``jnp.take`` and autodiff's scatter-add transposes): the
forward bit for bit, the gradients w.r.t. the rows and the weights within
bfloat16 summation (the weights' bit for bit, the rows' the float32 sum
of each token's k terms rounded once); and what the lowered gradient holds
(no scatter, one sort) and under which names."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agilerl_tpu.llm import moe

BF16 = jnp.bfloat16


def scatter_load(choice, n_experts):
    """``moe.expert_load`` as it was: a scalar scatter-add."""
    return jnp.zeros((n_experts,), jnp.int32).at[choice.reshape(-1)].add(1)


def argsort_experts(x, choice, weights, w_gate, w_up, w_down, layer=None,
                    act="silu", first=None):
    """``moe.dropless_experts`` as it was before the permutation was built
    once, operation for operation: the reference for its values and
    gradients, and for the programs that held it."""
    N, k = choice.shape
    E = (w_up if w_gate is None else w_gate).shape[-3]
    dtype = x.dtype
    flat = choice.reshape(-1)
    if first is not None:
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < E), flat, E)
    order = jnp.argsort(flat, stable=True)
    sizes = (scatter_load(choice, E) if first is None else
             jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E])
    if layer is not None:
        n = w_gate.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n * E,), jnp.int32), sizes, (layer * E,))
        w_gate, w_up, w_down = (
            None if w is None else w.reshape(n * E, *w.shape[2:])
            for w in (w_gate, w_up, w_down))
    xs = jnp.take(x, order // k, axis=0)
    if first is not None:
        valid = (jnp.arange(N * k) < sizes.sum())[:, None]
        xs = jnp.where(valid, xs, jnp.zeros((), xs.dtype))
    fn = moe.EXPERT_ACTS[act]
    if w_gate is None:
        y = jax.lax.ragged_dot(fn(jax.lax.ragged_dot(
            xs, w_up.astype(dtype), sizes)), w_down.astype(dtype), sizes)
        if first is not None:
            y = jnp.where(valid, y, jnp.zeros((), y.dtype))
    else:
        g = jax.lax.ragged_dot(xs, w_gate.astype(dtype), sizes)
        u = jax.lax.ragged_dot(xs, w_up.astype(dtype), sizes)
        y = jax.lax.ragged_dot(fn(g) * u, w_down.astype(dtype), sizes)
    back = jnp.argsort(order)
    y = jnp.take(y, back, axis=0).reshape(N, k, -1)
    return jnp.einsum("nkd,nk->nd", y, weights.astype(dtype))


#: the experts' activations written out, not read from ``moe``
PER_PAIR_ACTS = {"silu": lambda v: v * jax.nn.sigmoid(v),
                 "relu": lambda v: jnp.maximum(v, 0.0),
                 "relu2": lambda v: jnp.maximum(v, 0.0) ** 2}


def per_pair(x, choice, weights, w_gate, w_up, w_down, layer=None,
             act="silu", first=None):
    """Every (token, choice) pair through its own expert's weights, no
    sort: ``sum_k weights[n, k] E_choice[n, k](x[n])``, a pair routed off
    the held experts ``[first, first + E)`` contributing nothing."""
    if layer is not None:
        w_gate, w_up, w_down = (None if w is None else w[layer]
                                for w in (w_gate, w_up, w_down))
    E = w_up.shape[0]
    e = choice - (first or 0)
    held = (e >= 0) & (e < E)
    e = jnp.clip(e, 0, E - 1)
    fn = PER_PAIR_ACTS[act]
    hid = fn(jnp.einsum("nd,nkdf->nkf", x, w_up[e]))
    if w_gate is not None:
        hid = fn(jnp.einsum("nd,nkdf->nkf", x, w_gate[e])) * \
            jnp.einsum("nd,nkdf->nkf", x, w_up[e])
    y = jnp.einsum("nkf,nkfd->nkd", hid, w_down[e])
    return jnp.einsum("nkd,nk->nd", jnp.where(held[..., None], y, 0.0),
                      weights)


#: (N, k, E, act, gated, routing, layer, first) a case
CASES = {
    "k1": (16, 1, 8, "silu", True, "random", None, None),
    "k6": (20, 6, 16, "silu", True, "random", None, None),
    "k22": (9, 22, 32, "relu2", False, "random", None, None),
    "spread": (40, 2, 8, "silu", True, "random", None, None),
    "all_to_one": (40, 2, 8, "silu", True, "one", None, None),
    "empty_experts": (24, 3, 8, "relu", True, "two", None, None),
    "layer": (18, 2, 8, "silu", True, "random", 1, None),
    "first": (30, 4, 4, "relu2", False, "random", None, 4),
    "act_relu2_plain": (13, 3, 6, "relu2", False, "random", None, None),
    "act_silu_gated": (13, 3, 6, "silu", True, "random", None, None),
    "act_relu_gated": (13, 3, 6, "relu", True, "random", None, None),
}


def case(name, seed=0):
    """(x, choice, weights, (w_gate, w_up, w_down), keywords), float32,
    drawn with numpy (a shape drawn by ``jax.random`` compiles)."""
    N, k, E, act, gated, routing, layer, first = CASES[name]
    d, f = 16, 12
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((N, d)), jnp.float32)
    routed = E if first is None else 4 * E  # the router's experts
    choice = rng.integers(0, routed, (N, k))
    if routing == "one":  # every pair on expert 3: 8 x a fair share
        choice = np.full((N, k), 3)
    if routing == "two":  # six of the eight experts serve nothing
        choice = np.where(choice % 2 == 0, 1, 5)
    weights = jnp.asarray(rng.uniform(0.5, 1.5, (N, k)), jnp.float32)
    lead = () if layer is None else (3,)
    w = [jnp.asarray(0.3 * rng.standard_normal(lead + s), jnp.float32)
         for s in [(E, d, f), (E, d, f), (E, f, d)]]
    experts = (w[0] if gated else None, w[1], w[2])
    kw = dict(act=act, layer=None if layer is None else jnp.int32(layer),
              first=first)
    return x, jnp.asarray(choice, jnp.int32), weights, experts, kw


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_dispatch_against_the_pairs_and_the_argsort_formulation(name):
    x, choice, weights, experts, kw = case(name)
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(x.shape),
                        jnp.float32)

    def run(f, x):
        """(forward, gradients w.r.t. the rows and the weights) of ``f``."""
        def loss(x_, w_):
            out = f(x_, choice, w_, *experts, **kw)
            return jnp.sum(out * probe.astype(out.dtype)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(x, weights)
        return out, grads

    # float32 against every pair through its own expert
    got, got_grads = run(moe.dropless_experts, x)
    want, want_grads = run(per_pair, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert kw["first"] or float(jnp.abs(got).sum(-1).min()) > 0
    routed = int(choice.max()) + 1
    np.testing.assert_array_equal(  # the rows each expert serves
        moe.expert_load(choice, routed),
        np.bincount(np.asarray(choice).ravel(), minlength=routed))
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
    # bfloat16 rows, as the cells run: against the argsort formulation the
    # forward and the weights' gradient bit for bit; the rows' gradient is
    # the k cotangents of a token's pairs summed in float32 and rounded
    # once, exactly, and lies within the error bound of a bfloat16 sum of
    # k terms of the old one (a scatter-add that rounds as it goes)
    xb = x.astype(BF16)
    got, got_grads = run(moe.dropless_experts, xb)
    want, want_grads = run(argsort_experts, xb)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_grads[1], want_grads[1])
    terms = pair_cotangents(xb, choice, weights, experts, kw, probe)
    np.testing.assert_array_equal(
        got_grads[0], jnp.asarray(terms.sum(1)).astype(BF16))
    k = choice.shape[1]
    u = 2.0 ** -8  # bfloat16's unit roundoff
    bound = ((k - 1) * u / (1 - (k - 1) * u) + u) * np.abs(terms).sum(1)
    gap = np.abs(np.asarray(got_grads[0], np.float32)
                 - np.asarray(want_grads[0], np.float32))
    assert (gap <= bound).all(), float((gap / np.maximum(bound, 1e-30)).max())


def pair_cotangents(xb, choice, weights, experts, kw, probe):
    """The k terms of each token's row cotangent, [N, k, d] float32: the
    gradient of the argsort formulation with every pair a token of its own
    (k 1). Its sort, grouped matmuls and weighted rows are the k-pair
    dispatch's, and its scatter-add moves one row to a token: exact."""
    N, k = choice.shape
    rows = jax.jit(jax.grad(lambda p: jnp.sum(argsort_experts(
        p, choice.reshape(-1, 1), weights.reshape(-1, 1), *experts,
        **kw).reshape(N, k, -1) * probe.astype(BF16)[:, None])))(
            jnp.repeat(xb, k, axis=0))
    return np.asarray(rows, np.float32).reshape(N, k, -1)


def grad_text(name, dispatch=moe.dropless_experts):
    x, choice, weights, experts, kw = case(name)
    return jax.jit(jax.grad(
        lambda x_, w_: dispatch(x_, choice, w_, *experts, **kw).sum(),
        argnums=(0, 1))).lower(x.astype(BF16), weights)


@pytest.mark.parametrize("name", ["k6", "layer", "first"])
def test_the_gradient_moves_no_row_by_scatter_and_sorts_once(name):
    """The lowered gradient of one dispatch: no scatter of any kind (the
    transposes of the row gathers were scatter-adds of [N k, d] and [N, d]
    rows, the group sizes a scalar scatter-add) and one sort (the inverse
    permutation was a second)."""
    text = grad_text(name).as_text()
    assert len(re.findall(r"stablehlo\.scatter", text)) == 0
    assert len(re.findall(r"stablehlo\.sort", text)) == 1
    # what it looks for
    assert re.search(r"stablehlo\.scatter",
                     grad_text(name, argsort_experts).as_text())


@pytest.mark.parametrize("name", ["k6", "first"])
def test_every_operation_of_the_dispatch_lies_under_its_names(name):
    """Forward and backward, every operation of the compiled gradient lies
    under ``moe/route`` or ``moe/combine`` (the backward's own
    ``moe/route_bwd`` / ``moe/combine_bwd`` among them) or under the
    experts' ``moe/experts``, but for the cotangent seed and the sum of the
    two cotangents of the weights: nothing of the dispatch goes unnamed."""
    hlo = grad_text(name).compile().as_text()
    unnamed = []
    for line in hlo.splitlines():
        m = re.search(r'= \S+ ([\w-]+)\(.*op_name="([^"]*)"', line)
        if m and "/" in m.group(2) and not re.search(
                r"moe/(route|combine|experts)", m.group(2)):
            unnamed.append(m.groups())
    assert {op for op, _ in unnamed} <= {"broadcast", "add", "fusion",
                                         "bitcast", "copy"}, unnamed
    assert all(n.endswith("transpose(jvp())") or "/transpose(jvp())/" in n
               or n.count("/") == 1 for _, n in unnamed), unnamed
    assert "moe/route_bwd" in hlo and "moe/combine_bwd" in hlo

