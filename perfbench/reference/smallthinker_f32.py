"""Plain reference for a SmallThinker-class decoder (``model_name:
smallthinker_*``; the family is described in arXiv 2507.20984): pre-norm
layers of grouped-query attention — a layer is GLOBAL (full causal attention,
NO positional encoding) or a WINDOW layer (the last ``window`` positions,
rotary positions), by the two published layout lists — and 64 ReGLU experts
of which 6 a token, chosen by a router that reads the ATTENTION block's
normed input; a final RMSNorm; an untied head. One unpadded sequence ``x``
[T, d]; layer ``i``; H query heads on K key heads of ``hd``:

1.  ``h = rms(x; g1)``; ``r = h`` (the router's input).
2.  ``q = h Wq`` [T, H, hd], ``k = h Wk`` [T, K, hd], ``v = h Wv``.
3.  if ``rope_layout[i]``: rotary (``theta``) on all ``hd`` dimensions of q
    and k; else no positions at all.
4.  ``allowed(t, j) = j <= t and (t - j < window if
    sliding_window_layout[i] else True)``;
    ``x' = x + softmax(q k^T / sqrt(hd) | allowed) v Wo``.
5.  ``u = rms(x'; g2)``; ``l = r Wr`` [T, E] in float32; ``(val, idx) =
    top6(l)``; ``p = softmax(val)`` over the 6.
6.  ``y = sum_e p_e Wdown_e (relu(Wgate_e u) * Wup_e u)``; ``out = x' + y``.
7.  After the last layer ``rms(.; ln_f)`` and logits over ``lm_head``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no scan
over layers, no sort and no grouped matmul: one unpadded sequence, layer by
layer, the mask built from ``allowed`` literally, every expert applied
densely to every row and weighted by ``p`` (zero where the reference's OWN
top-6 did not choose it: it is never told the program's choice). So that it
fits beside the program on the chip it upcasts one layer at a time
(``EXPERT_GROUP`` experts and ``VOCAB_BLOCK`` columns of the head at a time)
and runs a row's attention ``QUERY_BLOCK`` queries at a time (the scores of
8192 queries x 8192 keys x 28 heads are 7.5 GB in float32). Imports nothing
from the program. It is handed the program's weight arrays: ``tok_emb``,
``ln_f``, ``lm_head`` [d, V] and ``runs``, each run one tree stacked over
its layers or — the program's period scan — a list of trees, tree p stacked
over the layers at position p of every period; per layer ``ln1 wq wk wv wo
ln2 router`` [d, E] ``w_gate w_up`` [E, d, f] ``w_down`` [E, f, d].

Departures from the published description, and what it leaves open (each is
an entry under ``assumed`` in ``perfbench/configs/smallthinker-21b-a3b.json``):

- Rotary turns all ``hd`` dimensions and pairs dimension 2i with 2i + 1,
  where the published code splits halves: the same angles on a fixed
  permutation of a head's columns, which ``Wq`` / ``Wk``'s columns absorb
  with seeded weights.
- The router reads the NORMED input of the attention block (the family is
  described as "router placed before attention"; the config has no key).
- ``top6`` then softmax over the six, which equals softmax over 64, top 6,
  renormalised (``moe_primary_router_apply_softmax`` + ``norm_topk_prob``).
- No attention bias; the "secondary" sparsity inside an expert that the
  family's description mentions has no key in the config and is left out.

TOLERANCES, with their reasons, are at the constants below.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: The limits, on |log p(program) - log p(this file)| over 2 rows x 128
#: completion positions (every one of them deeper than the window) of
#: log-probabilities of magnitude ~log(vocab) = 11.9 (read: 11.4), set from
#: readings on a TPU v5e at published widths, 8 layers and rows of 8192
#: (PERF.md section 6, PR 38, has the runs and their seeds): what the
#: program gives (bf16 matrices, activations and residual stream; f32
#: accumulation, norm statistics, softmax, and the router from the residual
#: stream on), and what this file gives against itself in bfloat16
#: THROUGHOUT (``dtype=jnp.bfloat16``: the router and its input, softmax and
#: logits too), with every matrix rounded to float8 (``store=``), with the
#: window layers run as full attention, and with rotary in the global
#: layers too.
#:
#: ROUTING IS DISCONTINUOUS, and it is what a log-probability's error is
#: made of here: where this file's sixth and seventh logit lie closer than
#: a rounding moves them, the token goes to another expert, and the flips
#: of 8192 positions x 8 layers of context reach every checked position
#: through attention (136-190 of the 2048 checked (position, layer) choices
#: a run have a margin under ``MARGIN``; leaving those positions out moves
#: neither side's median by a tenth). A prompt of the traffic generator's
#: 45 letters makes it lumpy: in layer 0 (global, no positions) the router
#: reads 45 distinct inputs, so ONE fragile letter flips ~180 positions at
#: once. While the program's router read the normed input rounded to
#: bfloat16, 3 seeds of 12 read twice the others (median 0.0132-0.0184
#: against 0.0074-0.0087); since it reads the norm in float32
#: (``model._early_router_logits``) the program's median is 0.0070-0.0093
#: on 21 seeds of the control and 0.0065-0.0092 in the cell's own checks
#: (12 seeds, learn side and paged tier). This file with the router ALONE
#: in bfloat16 reads 0.0080-0.0212: the router's precision is most of what
#: "bfloat16 throughout" adds (0.0109-0.0280 on those 21 seeds), and how
#: much that is on one seed's weights is as lumpy (a seed whose 45 letters
#: have no fragile choice reads 0.0109). So no fixed limit that leaves the
#: program room tells bfloat16 on EVERY seed, and the comparison is made
#: twice, the second time in pairs:
#:
#: - ``LP_MEDIAN_TOL`` / ``LP_MEAN_TOL`` / ``LP_MAX_TOL``, over ALL checked
#:   positions (none left out) against this file in float32. Median:
#:   program 0.0065-0.0093, bfloat16 throughout 0.0109-0.0280 (over the
#:   limit on 20 seeds of 21), float8 weights 0.0669-0.0911, the window
#:   ignored 0.141-0.185, rotary in the global layers 0.351-0.467; 0.0135
#:   is 1.45 times the program's largest. Mean: program 0.0106-0.0154,
#:   bfloat16 throughout 0.0178-0.0354, float8 0.0866-0.1099, the window
#:   ignored 0.176-0.217, rotary 0.435-0.535; 0.0225 is 1.46 times the
#:   program's largest. Largest: it cannot tell a precision (program
#:   0.072-0.150, bfloat16 0.107-0.170, float8 0.34-0.43: one flipped
#:   expert decides it) and guards against a gross fault — a wrong mask,
#:   window edge, position or cache slot moves positions by nats (the
#:   window ignored 0.61-1.01, rotary in the global layers 1.5-2.2) —, at
#:   0.5, three times the program's largest.
#: - ``LP_NEARER_SLACK``: the median against this file in float32 may
#:   exceed the median against this file in bfloat16 throughout, on the same
#:   weights and tokens, by that much at most — a side has to lie NEARER to
#:   the float32 answer than to the bfloat16 one, or as near. The
#:   difference of the two medians: program -0.0176 to 0.0000 (21 seeds of
#:   the control, 12 of the cell on both sides: 45 readings; it is near 0
#:   where bfloat16 costs this file little or where the program and the
#:   bfloat16 answer flip the same letter, and both happen); bfloat16
#:   throughout reads its own median against float32, 0.0109-0.0280 (it IS
#:   the second answer: the other median is 0); and, what makes the limit
#:   more than an identity, a PROGRAM whose router computes in bfloat16
#:   (the control's ``program_bf16_router``) reads -0.0009 to +0.0153, over
#:   the limit on 16 seeds of 21, because the bfloat16 grid rounds two
#:   nearly equal logits the same way in both. 0.004 lies 0.004 above the
#:   program's largest (four readings within 0.0013 of 0: eight times
#:   their scatter) and is 0.37 of bfloat16 throughout's smallest. (A
#:   limit on the RATIO of the two medians was tried first, at 1.0: the
#:   program read 0.27-0.80 on 22 seeds and then 0.958 on the 23rd, where
#:   both medians are 0.009: a ratio has no room where bfloat16 is
#:   harmless, the difference has.)
LP_MEDIAN_TOL = 0.0135
LP_MEAN_TOL = 0.0225
LP_MAX_TOL = 0.5
LP_NEARER_SLACK = 0.004
#: A (position, layer) choice of this file whose sixth and seventh logit lie
#: closer than ``MARGIN`` is counted as fragile in the record: how much of
#: the batch a rounding can re-route.
MARGIN = 2.0 ** -7
LOGIT_TOL = 5e-5  # CPU tests: the program in float32 differs by the order
# of its sums alone
QUERY_BLOCK = 512  # queries a block of attention: [28, 512, T] scores
HEAD_BLOCK = 128  # positions per head call
VOCAB_BLOCK = 18992  # columns of the head upcast at a time: 151936 / 8
EXPERT_GROUP = 8  # experts upcast at a time: 8 x 5.9 M x 4 B = 0.19 GB


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _cast(tree, dtype, store=None):
    """To the compute dtype; with ``store`` (the precision control's float8)
    every matrix is first rounded to that type, as if stored in it."""
    def one(a):
        if store is not None and a.ndim >= 2:
            a = a.astype(store)
        return a.astype(dtype)

    return jax.tree_util.tree_map(one, tree)


def _rope(x, theta):
    """x [T, N, hd]; position t rotates the pair (2i, 2i+1) by t * theta **
    (-2i / hd)."""
    t, _, r = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, hd/2]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "theta", "eps", "rope", "dtype", "store"))
def project_part(x, w, *, n_head, n_kv, theta, eps, rope, dtype, store):
    """Steps 1-3 on one sequence: (h = r [T, d], q [T, K, G, hd], k, v)."""
    w = _cast({k: w[k] for k in ("ln1", "wq", "wk", "wv")}, dtype, store)
    t = x.shape[0]
    hd = w["wq"].shape[1] // n_head
    h = _rms(x, w["ln1"], eps)
    q = (h @ w["wq"]).reshape(t, n_head, hd)
    k = (h @ w["wk"]).reshape(t, n_kv, hd)
    v = (h @ w["wv"]).reshape(t, n_kv, hd)
    if rope:
        q, k = _rope(q, theta), _rope(k, theta)
    return h, q.reshape(t, n_kv, n_head // n_kv, hd), k, v


@functools.partial(jax.jit, static_argnames=("window",))
def attend_block(q, k, v, first, *, window):
    """Step 4's softmax for the queries ``first .. first + len(q)`` against
    all keys: the mask is ``allowed`` written out."""
    nq, t = q.shape[0], k.shape[0]
    hd = q.shape[-1]
    tq = first + jnp.arange(nq)[:, None]
    j = jnp.arange(t)[None, :]
    allowed = j <= tq
    if window:
        allowed = jnp.logical_and(allowed, tq - j < window)
    scores = jnp.einsum("tjgd,sjd->jgts", q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return jnp.einsum("jgts,sjd->tjgd", probs, v).reshape(nq, -1)


@functools.partial(jax.jit, static_argnames=("dtype", "store"))
def merge_part(x, o, wo, *, dtype, store):
    return x + o @ _cast(wo, dtype, store)


@functools.partial(jax.jit, static_argnames=("top_k", "eps", "dtype"))
def route_part(x1, r, w, *, top_k, eps, dtype):
    """Step 5: (u, the weight of every expert [T, E] — ``p`` at the chosen
    six, zero elsewhere —, margin [T]: how far the sixth logit lies above
    the seventh). ``dtype`` is the precision control's: the logits are
    float32 unless it says bfloat16 of everything."""
    w = _cast({k: w[k] for k in ("ln2", "router")}, dtype)
    u = _rms(x1, w["ln2"], eps)
    logits = r @ w["router"]
    top, idx = jax.lax.top_k(logits, top_k + 1)
    p = jax.nn.softmax(top[:, :top_k], axis=-1)
    weights = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], idx[:, :top_k]].set(p)
    margin = (top[:, top_k - 1] - top[:, top_k]).astype(jnp.float32)
    return u, weights, margin


@functools.partial(jax.jit, static_argnames=("dtype", "store"))
def expert_group_part(u, gate, up, down, weights, *, dtype, store):
    """sum over a group of experts of ``weights[:, e] * E_e(u)``, E a ReGLU:
    every expert on every row."""
    gate, up, down = _cast((gate, up, down), dtype, store)
    out = jnp.zeros_like(u)
    for j in range(gate.shape[0]):
        y = (jax.nn.relu(u @ gate[j]) * (u @ up[j])) @ down[j]
        out = out + weights[:, j, None].astype(u.dtype) * y
    return out


def layer(x, w, *, n_head, n_kv, theta, eps, top_k, window, rope, dtype,
          store):
    """One layer on one sequence: (out [T, d], margins [T])."""
    h, q, k, v = project_part(x, w, n_head=n_head, n_kv=n_kv, theta=theta,
                              eps=eps, rope=rope, dtype=dtype, store=store)
    o = jnp.concatenate([
        attend_block(q[s:s + QUERY_BLOCK], k, v, s, window=window)
        for s in range(0, x.shape[0], QUERY_BLOCK)])
    x1 = merge_part(x, o, w["wo"], dtype=dtype, store=store)
    u, weights, margin = route_part(x1, h, w, top_k=top_k, eps=eps,
                                    dtype=dtype)
    y = jnp.zeros_like(u)
    for e in range(0, w["w_gate"].shape[0], EXPERT_GROUP):
        g = slice(e, e + EXPERT_GROUP)
        y = y + expert_group_part(
            u, w["w_gate"][g], w["w_up"][g], w["w_down"][g], weights[:, g],
            dtype=dtype, store=store)
    return x1 + y, margin


def layers(params):
    """The per-layer weight trees in layer order, out of the stored runs: a
    run is one stacked tree, or a list of trees by position in the period
    (layer ``j * P + p`` of the run is row j of tree p)."""
    for run in params["runs"]:
        trees = run if isinstance(run, (list, tuple)) else [run]
        n = sum(jax.tree_util.tree_leaves(t)[0].shape[0] for t in trees)
        for i in range(n):
            p, j = i % len(trees), i // len(trees)
            yield jax.tree_util.tree_map(lambda a, j=j: a[j], trees[p])


def hidden_states(params, tokens, *, n_head, n_kv, theta, eps, top_k, window,
                  window_layout, rope_layout, dtype=jnp.float32, store=None):
    """(hidden states [T, D] before the final norm, routing margins
    [layers, T]) for one unpadded sequence of token ids."""
    x = _cast(jnp.take(params["tok_emb"], jnp.asarray(tokens, jnp.int32),
                       axis=0), dtype, store)
    margins = []
    for i, w in enumerate(layers(params)):
        x, margin = layer(
            x, w, n_head=n_head, n_kv=n_kv, theta=theta, eps=eps,
            top_k=top_k, window=window if window_layout[i] else 0,
            rope=bool(rope_layout[i]), dtype=dtype, store=store)
        margins.append(margin)
    return x, jnp.stack(margins)


@functools.partial(jax.jit, static_argnames=("dtype", "store"))
def _head_block(hn, head, *, dtype, store=None):
    return (hn @ _cast(head, dtype, store)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dtype", "store"))
def _head_block_lse(hn, head, local, *, dtype, store=None):
    """(logsumexp over this block of the head's columns, the logit at column
    ``local`` of the block, clipped into it) for every position."""
    lg = _head_block(hn, head, dtype=dtype, store=store)
    at = jnp.clip(local, 0, head.shape[1] - 1)[:, None]
    return (jax.nn.logsumexp(lg, axis=-1),
            jnp.take_along_axis(lg, at, axis=-1)[:, 0])


def _final_norm(params, h, eps, dtype):
    return _rms(h, params["ln_f"].astype(dtype), eps)


def logits(params, tokens, **hp):
    """Logits [T, V] for one unpadded sequence (small sizes: tests)."""
    dtype = hp.get("dtype", jnp.float32)
    with jax.default_matmul_precision("highest"):
        h, _ = hidden_states(params, tokens, **hp)
        return np.asarray(_head_block(
            _final_norm(params, h, hp["eps"], dtype), params["lm_head"],
            dtype=dtype, store=hp.get("store")))


def token_logprobs(params, tokens, at, **hp):
    """(log p(tokens[t + 1] | tokens[:t + 1]) for every t in ``at``, the
    routing margins [layers, len(at)] at those positions) for one unpadded
    sequence ``tokens`` ([T] ints), as float32 numpy. ``dtype=jnp.bfloat16``
    computes ALL of it in bfloat16, ``store=jnp.float8_e4m3fn`` rounds every
    matrix to float8 first: what the limits must tell from this file's own
    answer. A ``window_layout`` of zeros (the window ignored) or a
    ``rope_layout`` of ones (rotary in the global layers too) are the two
    other controls. The head is taken ``VOCAB_BLOCK`` columns at a time."""
    dtype = hp.get("dtype", jnp.float32)
    tokens = np.asarray(tokens, np.int32)
    at = np.asarray(at)
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        h, margins = hidden_states(params, tokens, **hp)
        hn = _final_norm(params, h[jnp.asarray(at)], hp["eps"], dtype)
        target = tokens[at + 1]
        lses, chosen = [], np.zeros(at.size, np.float32)
        for first in range(0, head.shape[1], VOCAB_BLOCK):
            block = head[:, first:first + VOCAB_BLOCK]
            here = (target >= first) & (target < first + block.shape[1])
            lse, pick = zip(*(
                _head_block_lse(hn[s:s + HEAD_BLOCK], block,
                                jnp.asarray(target[s:s + HEAD_BLOCK] - first),
                                dtype=dtype, store=hp.get("store"))
                for s in range(0, at.size, HEAD_BLOCK)))
            lses.append(np.concatenate(lse))
            chosen = np.where(here, np.concatenate(pick), chosen)
    total = np.asarray(jax.nn.logsumexp(jnp.asarray(np.stack(lses)), axis=0))
    return (chosen - total).astype(np.float32), np.asarray(margins)[:, at]
