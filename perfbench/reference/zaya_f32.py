"""Plain reference for a ZAYA1-class decoder (``model_type: zaya``): every
layer is an attention sublayer (compressed convolutional attention, CCA,
arXiv 2510.04476) and then an expert sublayer (16 experts, ONE a token,
chosen by a router MLP whose state is carried from layer to layer; the ZAYA1
report, arXiv 2511.17127), each merged into the residual stream with a
learned per-channel scale and bias on both arms; a final RMSNorm; the head
tied to the embedding. One unpadded sequence ``h`` [T, d]; H query heads on
K key heads of ``hd``, ``G = H / K``:

1.  ``x = rms(h; ln1)``; ``qt = x Wq`` [T, H hd], ``kt = x Wk`` [T, K hd].
2.  ``mq[t, i] = (qt[t, i] + kt[t, i // G]) / 2`` a query head; ``mk[t, j]``
    the mean of ``mq[t, i]`` over the G heads of group j.
3.  ``p = [qt ; kt]`` as H + K heads of hd; ``c0[t, c] = b0[c] + sum_u
    w0[u, c] p[t - (k0-1) + u, c]`` (depthwise, causal: rows before 0 are
    zero, by explicit padding); ``c1[t, n, o] = b1[n, o] + sum_u sum_i
    W1[u, n, i, o] c0[t - (k1-1) + u, n, i]`` (a head's channels among
    themselves, causal likewise). ``q' = c1[:H] + mq``, ``k' = c1[H:] + mk``.
4.  ``v[t] = [x[t] Wv1 ; x[t-1] Wv2]`` with ``x[-1] = 0``: the first half
    of the key heads holds the current token's values, the second the
    previous token's.
5.  ``q = sqrt(hd) q'/|q'|``, ``k = tau_j sqrt(hd) k'/|k'|`` (L2 a head);
    rotary on the first ``rotary * hd`` dimensions of every head of q and k.
6.  ``o = softmax(q k^T / sqrt(hd)) v``, causal; ``a = o Wo``.
7.  ``h1 = (m1[0] h + m1[1]) + (m1[2] a + m1[3])``.
8.  ``x2 = rms(h1; ln2)``; ``z = x2 Wr + br``, from layer 1 on ``z +=
    gamma * s_prev``; ``s = z`` goes to the next layer; ``logits = gelu(
    gelu(rms(z; gr) W1 + b1) W2 + b2) W3``; ``pr = softmax(logits)``; ``e =
    argmax(pr + bias)`` — the bias moves the choice only —; weight ``pr[e]``,
    not renormalised.
9.  ``y = pr[e] E_e(x2)``, E a SwiGLU; ``h_out = (m2[0] h1 + m2[1]) +
    (m2[2] y + m2[3])``.
10. After the last layer ``rms(.; ln_f)`` and logits over ``tok_emb^T``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no rolling
state, no scan over layers, no sort and no grouped matmul: one unpadded
sequence, layer by layer, every expert applied to every row and kept where
the reference's OWN argmax chose it (it is never told the program's choice),
``EXPERT_GROUP`` experts and ``VOCAB_BLOCK`` rows of the head upcast at a
time so that it fits beside the program on the chip. Imports nothing from
the program. It is handed the program's weight arrays, one stacked tree a
run of layers (``runs[r]``, leading axis = the run's layers), under the
names ``tok_emb``, ``ln_f`` and per layer ``ln1 wq wk wv1 wv2 wo conv0_w``
[k0, C] ``conv0_b conv1_w`` [k1, H + K, hd in, hd out] ``conv1_b tau merge1``
[4, d] ``ln2 router_in router_in_b router_gamma router_norm router_w1
router_b1 router_w2 router_b2 router`` [r, E] ``router_bias w_gate w_up
w_down`` [E, ...] ``merge2``.

Departures from the published description, and what it leaves open (each is
an entry under ``assumed`` in ``perfbench/configs/zaya1-8b.json``):

- Rotary pairs dimension 2i with 2i + 1 of the 64 rotated dimensions, where
  the published code splits halves: the same angles on a fixed permutation
  of the rotated columns, which the head-wise convolution's output channels
  (and ``mq`` / ``mk``, through ``Wq`` / ``Wk``'s columns) absorb with
  seeded weights.
- ``tau`` is a plain multiplier a key head; the merge applies scale then
  bias on each arm; the router MLP has biases on its two hidden layers and
  none on its output, GELU by erf, and an RMSNorm in front; the first
  layer has no ``gamma`` term (the program stores one and multiplies zeros).
- The family's mixture-of-depths skip route is left out: this model's
  configuration names 16 experts, one a token, and no key for it, so the
  router has 16 outputs.

TOLERANCES, with their reasons, are at the constants below.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: The limits, on |log p(program) - log p(this file)| over 2 rows x 256
#: positions of log-probabilities of magnitude ~log(vocab) = 12.5 (read:
#: 11.9-12.1), set from two readings on a TPU v5e at published widths and 24
#: layers (PERF.md section 6, PR 33, has the runs): what the program gives
#: (bf16 matmul inputs, f32 accumulation, f32 convolution taps, norms and
#: router; the cell's warm-up check, learn side and paged tier, on 7 seeds,
#: and perfbench/tests/test_precision_control_zaya.py on 6: 20 readings),
#: and what this file gives against itself one precision below — computed
#: in bfloat16 THROUGHOUT (``dtype=jnp.bfloat16``: router MLP, softmax,
#: norms, residual stream too; 6 seeds) or with every matrix rounded to
#: float8 (``store=``; 6 seeds).
#:
#: ROUTING IS DISCONTINUOUS, top-1 most of all. Where this file's best and
#: second-best biased probability lie closer than the program's rounding of
#: the router's input moves them, the program sends the token to the OTHER
#: expert and the position's whole expert output in that layer is replaced
#: (times a probability near 1/2), not a sixth of it as under top-6. That is
#: no fault — a bf16 deployment of the published model flips the same
#: choices — and it is rarer than under top-6 of 128 (one boundary a
#: choice: 115-150 of 12288 (position, layer) choices a run have a margin
#: under ``MARGIN`` = 2**-9, 101-131 of 512 positions hold one) but each
#: flip moves more. So the differences have a body (median 0.014-0.026)
#: and a heavy tail (largest 0.43-1.45), and:
#:
#: - ``LP_MEDIAN_TOL``, the median over ALL positions, is the limit that
#:   tells a precision: the flips do not move it. Program 0.0141-0.0261 (20
#:   readings; the control's uniform random tokens read higher, 0.0193-
#:   0.0261, than the cell's sampled ones, 0.0141-0.0228), bfloat16
#:   throughout 0.0438-0.0553, float8 weights 0.223-0.267. 0.0338 is the
#:   geometric mean of the program's largest and the control's smallest:
#:   30 % of room above and below (4 standard deviations of the control's
#:   six program readings above their mean, 2.5 below the bfloat16 ones').
#: - ``LP_MEAN_TOL``, the mean over ALL positions (none left out), carries
#:   the tail, so it is wide: program 0.031-0.065, bfloat16 throughout
#:   0.084-0.106, float8 weights 0.282-0.324. 2**-3 leaves the program's
#:   largest 93 % of room and is under half the float8 reading; it does NOT
#:   tell the bfloat16 control (the median does): a limit between 0.065 and
#:   0.084 would leave either side 14 %.
#: - ``LP_MAX_TOL``, the largest over ALL positions, cannot tell a precision
#:   (program 0.43-1.45, bfloat16 0.85-1.85, float8 1.10-2.04): one flipped
#:   expert decides it. It stays as the guard against a gross fault — a
#:   wrong mask, position, cache slot or rolling state moves positions by
#:   several nats —, at 3.0, twice the program's largest of 20.
LP_MEDIAN_TOL = 0.0338
LP_MEAN_TOL = 2.0 ** -3
LP_MAX_TOL = 3.0
#: A (position, layer) choice of this file whose best and second-best biased
#: probability lie closer than ``MARGIN`` is counted as fragile in the
#: record: how much of the batch a rounding can re-route.
MARGIN = 2.0 ** -9
LOGIT_TOL = 5e-5  # CPU tests: the program in float32 differs by the order
# of its sums alone
HEAD_BLOCK = 128  # positions per head call
VOCAB_BLOCK = 32784  # rows of the head upcast at a time: 262272 / 8
EXPERT_GROUP = 4  # experts upcast at a time: 4 x 12.6 M x 4 B = 0.2 GB


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
    """x [T, N, r]; position t rotates the pair (2i, 2i+1) by t * theta **
    (-2i / r)."""
    t, _, r = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, r/2]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _unit(x):
    """x / |x| a head (``F.normalize``)."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(norm, 1e-12)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "theta", "rotary", "eps", "dtype", "store"))
def attention_part(h, w, *, n_head, n_kv, theta, rotary, eps, dtype, store):
    """Steps 1-7 on one sequence. h: [T, d]."""
    w = _cast({k: w[k] for k in (
        "ln1", "wq", "wk", "wv1", "wv2", "wo", "conv0_w", "conv0_b",
        "conv1_w", "conv1_b", "tau", "merge1")}, dtype, store)
    t = h.shape[0]
    heads, per_group = n_head + n_kv, n_head // n_kv
    hd = w["wq"].shape[1] // n_head
    x = _rms(h, w["ln1"], eps)
    qt, kt = x @ w["wq"], x @ w["wk"]
    qh, kh = qt.reshape(t, n_head, hd), kt.reshape(t, n_kv, hd)
    mq = (qh + jnp.repeat(kh, per_group, axis=1)) / 2
    mk = mq.reshape(t, n_kv, per_group, hd).mean(axis=2)
    # the two causal convolutions, rows before the first are zero
    p = jnp.concatenate([qt, kt], axis=-1)
    k0, k1 = w["conv0_w"].shape[0], w["conv1_w"].shape[0]
    ppad = jnp.pad(p, ((k0 - 1, 0), (0, 0)))
    c0 = w["conv0_b"] + sum(w["conv0_w"][u] * ppad[u:u + t] for u in range(k0))
    c0pad = jnp.pad(c0.reshape(t, heads, hd), ((k1 - 1, 0), (0, 0), (0, 0)))
    c1 = w["conv1_b"].reshape(heads, hd) + sum(
        jnp.einsum("tni,nio->tno", c0pad[u:u + t], w["conv1_w"][u])
        for u in range(k1))
    q = math.sqrt(hd) * _unit(c1[:, :n_head] + mq)
    k = w["tau"][:, None] * math.sqrt(hd) * _unit(c1[:, n_head:] + mk)
    rd = int(hd * rotary)
    q = jnp.concatenate([_rope(q[..., :rd], theta), q[..., rd:]], axis=-1)
    k = jnp.concatenate([_rope(k[..., :rd], theta), k[..., rd:]], axis=-1)
    # the value shift: the second half is the PREVIOUS token's
    shifted = jnp.pad(x @ w["wv2"], ((1, 0), (0, 0)))[:t]
    v = jnp.concatenate([(x @ w["wv1"]).reshape(t, n_kv // 2, hd),
                         shifted.reshape(t, n_kv // 2, hd)], axis=1)
    scores = jnp.einsum("tjgd,sjd->jgts",
                        q.reshape(t, n_kv, per_group, hd), k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("jgts,sjd->tjgd", probs, v).reshape(t, n_head * hd)
    m = w["merge1"]
    return (m[0] * h + m[1]) + (m[2] * (o @ w["wo"]) + m[3])


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


@functools.partial(jax.jit, static_argnames=("first", "eps", "dtype"))
def route_part(h, s_prev, w, *, first, eps, dtype):
    """Step 8: (x2, router state z, chosen expert [T], its probability [T],
    margin [T]: how far the best biased probability lies above the next)."""
    w = _cast({k: w[k] for k in (
        "ln2", "router_in", "router_in_b", "router_gamma", "router_norm",
        "router_w1", "router_b1", "router_w2", "router_b2", "router",
        "router_bias")}, dtype)
    x = _rms(h, w["ln2"], eps)
    z = x @ w["router_in"] + w["router_in_b"]
    if not first:
        z = z + w["router_gamma"] * s_prev
    u = _rms(z, w["router_norm"], eps)
    u = _gelu(u @ w["router_w1"] + w["router_b1"])
    u = _gelu(u @ w["router_w2"] + w["router_b2"])
    pr = jax.nn.softmax(u @ w["router"], axis=-1)
    top, order = jax.lax.top_k(pr + w["router_bias"], 2)
    choice = order[:, 0]
    weight = jnp.take_along_axis(pr, choice[:, None], axis=-1)[:, 0]
    return x, z, choice, weight, (top[:, 0] - top[:, 1]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("first", "dtype", "store"))
def expert_group_part(x, gate, up, down, choice, weight, *, first, dtype,
                      store):
    """sum over the experts ``first .. first + G`` of ``[choice == e] *
    weight * E_e(x)``: every expert on every row, masked."""
    gate, up, down = _cast((gate, up, down), dtype, store)
    out = jnp.zeros_like(x)
    for j in range(gate.shape[0]):
        coef = jnp.where(choice == first + j, weight, 0)
        out = out + coef[:, None] * (
            (jax.nn.silu(x @ gate[j]) * (x @ up[j])) @ down[j])
    return out


def expert_part(h, s_prev, w, *, first, eps, dtype, store):
    """Steps 8-9 on one sequence: (h_out, router state, margins [T])."""
    x, z, choice, weight, margin = route_part(
        h, s_prev, w, first=first, eps=eps, dtype=dtype)
    y = jnp.zeros_like(x)
    for e in range(0, w["w_gate"].shape[0], EXPERT_GROUP):
        g = slice(e, e + EXPERT_GROUP)
        y = y + expert_group_part(
            x, w["w_gate"][g], w["w_up"][g], w["w_down"][g], choice, weight,
            first=e, dtype=dtype, store=store)
    m = w["merge2"].astype(dtype)
    return (m[0] * h + m[1]) + (m[2] * y + m[3]), z, margin


def layers(params):
    """The per-layer weight trees in order, out of the stacked runs."""
    for run in params["runs"]:
        n = jax.tree_util.tree_leaves(run)[0].shape[0]
        for j in range(n):
            yield jax.tree_util.tree_map(lambda a, j=j: a[j], run)


def hidden_states(params, tokens, *, n_head, n_kv, theta, rotary, eps,
                  dtype=jnp.float32, store=None):
    """(hidden states [T, D] before the final norm, routing margins
    [layers, T]) for one unpadded sequence of token ids."""
    h = _cast(jnp.take(params["tok_emb"], jnp.asarray(tokens, jnp.int32),
                       axis=0), dtype, store)
    s, margins = None, []
    for i, w in enumerate(layers(params)):
        h = attention_part(h, w, n_head=n_head, n_kv=n_kv, theta=theta,
                           rotary=rotary, eps=eps, dtype=dtype, store=store)
        h, s, margin = expert_part(h, s, w, first=i == 0, eps=eps,
                                   dtype=dtype, store=store)
        margins.append(margin)
    return h, jnp.stack(margins)


@functools.partial(jax.jit, static_argnames=("dtype", "store"))
def _head_block(hn, emb, *, dtype, store=None):
    return (hn @ _cast(emb, dtype, store).T).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dtype", "store"))
def _head_block_lse(hn, emb, local, *, dtype, store=None):
    """(logsumexp over this block of the head's rows, the logit at row
    ``local`` of the block, clipped into it) for every position."""
    lg = _head_block(hn, emb, dtype=dtype, store=store)
    at = jnp.clip(local, 0, emb.shape[0] - 1)[:, None]
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
            _final_norm(params, h, hp["eps"], dtype), params["tok_emb"],
            dtype=dtype, store=hp.get("store")))


def token_logprobs(params, tokens, at, **hp):
    """(log p(tokens[t + 1] | tokens[:t + 1]) for every t in ``at``, the
    routing margins [layers, len(at)] at those positions) for one unpadded
    sequence ``tokens`` ([T] ints), as float32 numpy. ``dtype=jnp.bfloat16``
    computes ALL of it in bfloat16, ``store=jnp.float8_e4m3fn`` rounds every
    matrix to float8 first: what the limits must tell from this file's own
    answer. The head is taken ``VOCAB_BLOCK`` rows at a time (its float32
    copy whole is 2.1 GB at 262272 x 2048)."""
    dtype = hp.get("dtype", jnp.float32)
    tokens = np.asarray(tokens, np.int32)
    at = np.asarray(at)
    emb = params["tok_emb"]
    with jax.default_matmul_precision("highest"):
        h, margins = hidden_states(params, tokens, **hp)
        hn = _final_norm(params, h[jnp.asarray(at)], hp["eps"], dtype)
        target = tokens[at + 1]
        lses, chosen = [], np.zeros(at.size, np.float32)
        for first in range(0, emb.shape[0], VOCAB_BLOCK):
            block = emb[first:first + VOCAB_BLOCK]
            here = (target >= first) & (target < first + block.shape[0])
            lse, pick = zip(*(
                _head_block_lse(hn[s:s + HEAD_BLOCK], block,
                                jnp.asarray(target[s:s + HEAD_BLOCK] - first),
                                dtype=dtype, store=hp.get("store"))
                for s in range(0, at.size, HEAD_BLOCK)))
            lses.append(np.concatenate(lse))
            chosen = np.where(here, np.concatenate(pick), chosen)
    total = np.asarray(jax.nn.logsumexp(jnp.asarray(np.stack(lses)), axis=0))
    return (chosen - total).astype(np.float32), np.asarray(margins)[:, at]
