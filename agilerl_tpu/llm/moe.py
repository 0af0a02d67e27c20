"""Mixture-of-Experts FFN with expert parallelism (beyond reference parity —
the reference has no MoE or expert-parallel path at all, SURVEY.md §2.8 row
"Expert parallelism: n/a"; this completes the dp/fsdp/tp/sp/ep strategy menu).

Two dispatches. A configuration that states a capacity
(``GPTConfig.capacity_factor``) takes ``moe_ffn``, the Switch-style bucketed
dispatch below: it drops what overflows a bucket, and GSPMD shards it over
an ``ep`` axis. A configuration that states none takes the dropless path —
``route`` (score -> choice -> weights), ``dropless_experts`` (sort the
(token, choice) pairs by expert, one grouped matmul a projection, un-sort
and weight) and an always-on ``shared`` expert beside it — which serves
every token whatever the imbalance (``dropless_ffn`` is the whole layer).
The logits ``route`` turns into a choice come from outside it:
``linear_logits`` (``x @ router``; of the FFN's input, or — a router placed
before attention — of the attention block's normed input, computed by the
caller under ``moe/score``), or ``mlp_logits``, a small network over a
router state that is carried from layer to layer. The experts are gated
units with a SiLU or a ReLU gate (``EXPERT_ACTS``).

The bucketed dispatch: dense capacity-bucketed dispatch — routing is expressed as
one-hot einsums over static shapes ([tokens, E, C] dispatch/combine tensors),
so the whole layer is three big MXU matmuls plus elementwise gating. No
scatter/gather, no dynamic shapes, nothing XLA can't tile. With the stacked
expert weights sharded ``P("ep", ...)`` and tokens sharded on the batch axis,
GSPMD inserts the canonical all-to-all pair around the expert compute.

Load balancing is the Switch-Transformer auxiliary loss
(E * sum_e fraction_e * mean_prob_e), returned alongside the output so the
training loss can add ``router_aux_weight * aux``.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def moe_capacity(num_tokens: int, n_experts: int, top_k: int, capacity_factor: float) -> int:
    """Static per-expert capacity bucket size."""
    return max(1, int(math.ceil(top_k * num_tokens / n_experts * capacity_factor)))


def moe_ffn(
    x: jax.Array,  # [N, d] tokens (flattened batch*seq)
    router_w: jax.Array,  # [d, E]
    w_gate: jax.Array,  # [E, d, f] stacked expert SwiGLU gate
    w_up: jax.Array,  # [E, d, f]
    w_down: jax.Array,  # [E, f, d]
    top_k: int,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out [N, d], aux_loss scalar float32).

    Tokens overflowing an expert's capacity bucket are dropped for that expert
    (their other top-k routes still apply; a fully-dropped token passes through
    the residual connection unchanged — standard Switch semantics).
    """
    N, d = x.shape
    E = router_w.shape[-1]
    dtype = x.dtype

    logits = (x @ router_w.astype(dtype)).astype(jnp.float32)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [N, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    C = moe_capacity(N, E, top_k, capacity_factor)

    # position of each (token, route) inside its expert's bucket: priority is
    # (k-slot major, token minor) so top-1 routes win bucket slots over top-2
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [N, k, E]
    flat = onehot.transpose(1, 0, 2).reshape(top_k * N, E)  # k-major ordering
    pos_flat = jnp.cumsum(flat, axis=0) - flat  # [k*N, E]
    pos = (pos_flat * flat).sum(-1).reshape(top_k, N).T  # [N, k]
    keep = (pos < C).astype(jnp.float32)

    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)  # [N, k, C]
    pos_oh = pos_oh * keep[..., None]
    # dispatch [N, E, C]: 1 where token n occupies slot c of expert e
    dispatch = jnp.einsum("nke,nkc->nec", onehot, pos_oh).astype(dtype)
    # combine adds the normalised gate weight
    combine = jnp.einsum("nke,nkc,nk->nec", onehot, pos_oh, gate_vals).astype(dtype)

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)  # [E, C, d]
    g = jnp.einsum("ecd,edf->ecf", expert_in, w_gate.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down.astype(dtype))
    out = jnp.einsum("nec,ecd->nd", combine, y)

    # Switch aux loss: E * sum_e f_e * p_e over the top-1 assignment
    top1 = onehot[:, 0, :]  # [N, E]
    frac = top1.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = (E * jnp.sum(frac * mean_prob)).astype(jnp.float32)
    return out, aux


# --------------------------------------------------------------------------- #
# The dropless path
# --------------------------------------------------------------------------- #

ROUTE_SCOPE = "moe/route"
SCORE_SCOPE = "moe/score"  # a router that is a network; NOT under moe/route
EXPERTS_SCOPE = "moe/experts"
SHARED_SCOPE = "moe/shared"
COMBINE_SCOPE = "moe/combine"


def linear_logits(x, router_w):
    """``x @ router_w`` in float32 whatever ``x`` is."""
    return jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def init_router_mlp(key, d: int, r: int, n_experts: int):
    """The leaves ``mlp_logits`` reads, float32 all. Matrices normal(0,
    0.02) but the two hidden ones, which at that size would pass nothing on
    (256 x 0.02**2): they are drawn at 1/sqrt(r), and the last at 4/sqrt(r)
    so that the softmax has a favourite (a top probability near 1/2, where
    1/sqrt(r) leaves it near 1/E). Biases and ``gamma`` drawn, not at 0 and
    1 where they would check nothing."""
    from agilerl_tpu.llm.model import _normal as normal

    ks = jax.random.split(key, 8)
    return {
        "router_in": normal(ks[0], (d, r), 0.02),
        "router_in_b": normal(ks[1], (r,), 0.02),
        "router_gamma": 1.0 + normal(ks[2], (r,), 0.1),
        "router_norm": jnp.ones((r,), jnp.float32),
        "router_w1": normal(ks[3], (r, r), r ** -0.5),
        "router_b1": normal(ks[4], (r,), 0.02),
        "router_w2": normal(ks[5], (r, r), r ** -0.5),
        "router_b2": normal(ks[6], (r,), 0.02),
        "router": normal(ks[7], (r, n_experts), 4 * r ** -0.5),
    }


def mlp_logits(x, blk, s_prev, eps: float):
    """A router that is a network with a memory across layers, float32
    throughout: ``z = x W_in + b_in + gamma * s_prev`` (``s_prev`` the
    previous layer's ``z``; zeros into the first layer), then
    ``logits = gelu(gelu(rms(z) W1 + b1) W2 + b2) W_out`` (GELU by erf).
    x [N, d], s_prev [N, r] -> (logits [N, E], z [N, r])."""
    f32 = jnp.float32
    w = lambda name: blk[name].astype(f32)  # noqa: E731
    with jax.named_scope(SCORE_SCOPE):
        z = (linear_logits(x, w("router_in")) + w("router_in_b")
             + w("router_gamma") * s_prev)
        u = z * jax.lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps)
        u = u * w("router_norm")
        for i in ("1", "2"):
            u = jax.nn.gelu(linear_logits(u, w("router_w" + i))
                            + w("router_b" + i), approximate=False)
        return linear_logits(u, w("router")), z


def route(logits, select_bias=None, *, top_k: int, score: str = "softmax",
          norm_topk: bool = True, scale: float = 1.0):
    """Score -> choice -> weights, in float32.

    ``s = score(logits)`` (``sigmoid`` or ``softmax``); the choice is
    the top-k of ``s + select_bias`` — the bias moves the CHOICE only —; the
    weights are ``s`` at the chosen experts, renormalised to sum to one
    where ``norm_topk``, times ``scale``. Returns (choice [N, k] int32,
    weights [N, k] float32)."""
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"router score {score!r}: sigmoid or softmax")
    biased = s if select_bias is None else s + select_bias.astype(jnp.float32)
    _, choice = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(s, choice, axis=-1)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return choice.astype(jnp.int32), w * scale


def expert_load(choice, n_experts: int):
    """Rows each expert serves, [E] int32."""
    return jnp.zeros((n_experts,), jnp.int32).at[choice.reshape(-1)].add(1)


#: a gated expert's activation by its published name: SwiGLU or ReGLU
EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def dropless_experts(x, choice, weights, w_gate, w_up, w_down, layer=None,
                     act: str = "silu"):
    """``out[n] = sum_k weights[n, k] * E_choice[n, k](x[n])`` with every
    ``E`` a gated unit ``down(act(gate x) * up x)`` (``act`` "silu": a
    SwiGLU; "relu": a ReGLU), for ALL N x k pairs: the pairs are sorted by expert, each
    projection is one grouped matmul over the experts (``lax.ragged_dot``:
    XLA:TPU tiles the rows by group and reads an expert's weights only where
    its group is not empty), and the result is un-sorted by the inverse
    permutation (a gather, no scatter-add) and weighted. x [N, d]; choice,
    weights [N, k]; w_gate, w_up [E, d, f]; w_down [E, f, d].

    With ``layer`` (a traced index) the weights are a whole run's,
    ``[n, E, ...]``, and this layer's experts are the groups ``[layer E,
    (layer + 1) E)`` of a matmul grouped over ``n E``: every other group is
    empty, so nothing of the other layers is read and nothing is sliced out
    of the stacked array (``model._hold_experts`` says why)."""
    N, k = choice.shape
    E = w_gate.shape[-3]
    dtype = x.dtype
    with jax.named_scope(ROUTE_SCOPE):
        flat = choice.reshape(-1)
        order = jnp.argsort(flat, stable=True)  # pair p = token p // k
        sizes = expert_load(choice, E)
        if layer is not None:
            n = w_gate.shape[0]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n * E,), jnp.int32), sizes, (layer * E,))
            w_gate, w_up, w_down = (
                w.reshape(n * E, *w.shape[2:]) for w in (w_gate, w_up, w_down))
        xs = jnp.take(x, order // k, axis=0)  # [N k, d] sorted by expert
    with jax.named_scope(EXPERTS_SCOPE):
        g = jax.lax.ragged_dot(xs, w_gate.astype(dtype), sizes)
        u = jax.lax.ragged_dot(xs, w_up.astype(dtype), sizes)
        y = jax.lax.ragged_dot(EXPERT_ACTS[act](g) * u, w_down.astype(dtype),
                               sizes)
    with jax.named_scope(COMBINE_SCOPE):
        back = jnp.argsort(order)  # where pair p went
        y = jnp.take(y, back, axis=0).reshape(N, k, -1)
        return jnp.einsum("nkd,nk->nd", y, weights.astype(dtype))


def shared_expert(x, w_gate, w_up, w_down):
    """The always-on expert: a plain SwiGLU on every token."""
    dtype = x.dtype
    with jax.named_scope(SHARED_SCOPE):
        return (jax.nn.silu(x @ w_gate.astype(dtype))
                * (x @ w_up.astype(dtype))) @ w_down.astype(dtype)


def dropless_ffn(x, blk, *, top_k: int, score: str, norm_topk: bool,
                 scale: float, logits=None, act: str = "silu"):
    """The whole expert layer on ``x`` [N, d] from a block's weights
    (``router``, optional ``router_bias``, ``w_gate/w_up/w_down`` stacked
    over experts, optional ``ws_gate/ws_up/ws_down``). ``logits`` [N, E]
    where the router is not ``x @ router`` (``mlp_logits``, or a router fed
    from outside the FFN). ``act``: the experts' activation. Returns (out
    [N, d], load [E] int32: the rows each expert served)."""
    with jax.named_scope(ROUTE_SCOPE):
        if logits is None:
            logits = linear_logits(x, blk["router"])
        choice, weights = route(
            logits, blk.get("router_bias"), top_k=top_k,
            score=score, norm_topk=norm_topk, scale=scale)
    out = dropless_experts(x, choice, weights, blk["w_gate"], blk["w_up"],
                           blk["w_down"], layer=blk.get("expert_layer"),
                           act=act)
    if "ws_gate" in blk:
        out = out + shared_expert(x, blk["ws_gate"], blk["ws_up"],
                                  blk["ws_down"])
    return out, expert_load(choice, blk["router"].shape[-1])
