"""Plain reference for a DeepSeek-V3-class decoder (``model_type:
deepseek_v3``) without a query latent (``q_lora_rank`` null): pre-norm
residual layers ``h += mla(rms(h)); h += ffn(rms(h))``, a final RMSNorm and
an untied head. The FFN is a dense SwiGLU in the leading layers
(``first_k_dense_replace``) and the expert layer in every other.

- Latent attention: ``q = x Wq`` -> H x (nope | rope); ``x Wkv_a`` -> ``ckv``
  (kv_lora_rank) | ``k_rope`` (rope, ONE head shared by all H); ``c =
  rms(ckv)`` (``kv_a_layernorm``); RoPE over the rope dimensions of
  ``q_rope`` and ``k_rope``, no scaling of positions or of the softmax
  (``rope_scaling`` null); ``c Wkv_b`` -> H x (``k_nope`` | ``v``); ``k_h =
  [k_nope_h | k_rope]``; ``softmax(q_h k_h^T / sqrt(nope + rope))``, causal;
  the heads' outputs side by side through ``Wo``. EXPANDED form only: every
  position's keys and values are up-projected, nothing is absorbed, nothing
  is cached.
- Expert layer: ``s = sigmoid(x Wr)``; the choice is the top-k of ``s + b``
  (``e_score_correction_bias``, a frozen buffer; ``n_group`` and
  ``topk_group`` 1, so group limiting selects everything); the weights are
  ``s`` at the chosen experts WITHOUT ``b``, divided by their sum + 1e-20
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``out = sum_k w_k
  E_k(x) + S(x)``, every ``E`` a SwiGLU, ``S`` one SwiGLU of the shared
  experts' joint width. No token is dropped; no auxiliary loss.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
batching, no padding, no sort and no grouped matmul: one unpadded sequence
at a time, layer by layer (one jitted call a layer part, the float32 copy of
ONE layer's attention or of ONE group of ``EXPERT_GROUP`` experts alive at a
time), so that it fits beside the program on the chip. Imports nothing from
the program, and is never told the program's choice of experts. It is handed
the program's weight arrays, one stacked tree a run of equal layers
(``runs[r]``, leading axis = the run's layers, in layer order), under the
names ``tok_emb``, ``ln_f``, ``lm_head`` and per layer ``ln1 wq wkv_a
kv_norm wkv_b wo ln2`` plus ``w_gate w_up w_down`` (``[d, f]`` dense,
``[E, d, f]`` experts), ``router``, ``router_bias`` and ``ws_gate ws_up
ws_down`` (the shared expert).

Departures from the published description:

- ``rope_interleave`` true: the published code pairs dimensions (2i, 2i+1)
  of the stored rows, moves them to the half-split layout and rotates
  halves. Here dimension 2i is rotated with 2i+1 in place: the same pairs,
  the same angles; the order of the rotated columns differs by a permutation
  that queries and keys share, which no score sees. With seeded weights the
  pairing is a fixed permutation of columns of ``Wq`` / ``Wkv_a``; program
  and reference use this one.
- "A loop over the chosen experts a token" is written as a loop over the
  EXPERTS, each applied to every token and kept where the token chose it
  (``coef = sum_k [choice_k == e] w_k``): the same sum in the same float32,
  without gathering k expert matrices a token (6 x 4.7 M x 1024 tokens).
- The two shared experts are one SwiGLU 2 x ``moe_intermediate_size`` wide,
  as the published module builds them.

TOLERANCES, with their reasons (``LP_MEDIAN_TOL``, ``LP_MEAN_TOL``,
``LP_MAX_TOL``, ``MARGIN``), are at the constants below.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: The limits, on |log p(program) - log p(this file)| over 2 rows x 256
#: positions of log-probabilities of magnitude ~log(vocab) = 11.3, set from
#: two readings on a TPU v5e at published widths (PERF.md, PR 31, has the
#: runs): what the program gives (bf16 matmul inputs, f32 accumulation, an
#: f32 router; the cell's warm-up check, learn side and paged tier, and
#: perfbench/tests/test_precision_control_moe.py: 28 readings over 13 seeds),
#: and what this file gives against itself one precision below — computed in
#: bfloat16 THROUGHOUT (``dtype=jnp.bfloat16``: router, softmax, residual
#: stream too; 4 seeds) or with every matrix rounded to float8 (``store=``).
#:
#: ROUTING IS DISCONTINUOUS, and that shapes all three. Where the k-th and
#: (k+1)-th biased scores of this file lie closer than the program's
#: rounding of the hidden state moves them, the program chooses the other
#: expert and the position's log-probability moves by what one expert of k
#: contributes: up to ~1.2 here. That is no fault (a bf16 deployment of the
#: published model flips the same choices) and it is COMMON: of 3584
#: (position, layer) choices checked a run, 560-600 have a margin under
#: ``MARGIN`` = 2**-9 and 345-370 of 512 positions have at least one such
#: choice (the record counts both). So the differences have a body (median
#: 0.015) and a heavy tail of flipped positions (7-9 % over 0.25, largest
#: 0.59-1.19), and:
#:
#: - ``LP_MEDIAN_TOL``, the median over ALL positions, is the limit that
#:   tells a precision: the flips do not move it. Program 0.0144-0.0160
#:   (4 seeds of the control), bfloat16 throughout 0.0289-0.0367, float8
#:   weights 0.196-0.215. 0.0215 is the geometric mean of the program's
#:   largest and the control's smallest: 34 % of room above, 26 % below.
#: - ``LP_MEAN_TOL``, the mean over ALL positions (none left out), carries
#:   the tail, so it is wide: program 0.057-0.083 (28 readings), bfloat16
#:   throughout 0.101-0.109, float8 weights 0.252-0.267. 2**-3 leaves the
#:   program's largest 50 % of room and is half the float8 reading; it does
#:   NOT tell the bfloat16 control (the median does): the other GRPO cells'
#:   2**-4 would fail the program itself one run in five.
#: - ``LP_MAX_TOL``, the largest over ALL positions, cannot tell a precision
#:   at all (program 0.59-1.19, bfloat16 0.88-1.29, float8 1.20-1.48) and
#:   leaving the fragile positions out does not repair it: they are 70 % of
#:   all positions, and a firm position still flips where the rounding
#:   exceeds its margin (one of 12 runs read 0.89 on a firm position). It
#:   stays as the guard against a gross fault — a wrong mask, position or
#:   cache slot moves positions by several nats —, at 2.5, twice the
#:   program's largest of 28.
LP_MEDIAN_TOL = 0.0215
LP_MEAN_TOL = 2.0 ** -3
LP_MAX_TOL = 2.5
#: A (position, layer) choice of this file with a margin under ``MARGIN``
#: between its k-th and (k+1)-th biased score is counted as fragile in the
#: record: how much of the batch a rounding can re-route.
MARGIN = 2.0 ** -9
LOGIT_TOL = 2e-5  # CPU tests: the program in float32 differs by the order
# of its sums alone (measured ~1e-6 at the tests' tiny size)
HEAD_BLOCK = 128  # positions per head call: 128 x 128256 f32 logits = 66 MB
EXPERT_GROUP = 16  # experts upcast at a time: 16 x 4.7 M x 4 B = 0.3 GB


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
    """x [T, H, r]; position t rotates the pair (2i, 2i+1) by t * theta **
    (-2i / r)."""
    t, _, r = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, r/2]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "nope", "rope", "theta", "eps", "dtype", "store"))
def attention_part(h, w, *, n_head, nope, rope, theta, eps, dtype, store):
    """``h + mla(rms(h))`` on one sequence. h: [T, D]."""
    w = _cast({k: w[k] for k in ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b",
                                 "wo")}, dtype, store)
    t = h.shape[0]
    rank = w["wkv_b"].shape[0]
    x = _rms(h, w["ln1"], eps)
    q = (x @ w["wq"]).reshape(t, n_head, nope + rope)
    kv = x @ w["wkv_a"]
    c = _rms(kv[:, :rank], w["kv_norm"], eps)
    k_rope = _rope(kv[:, None, rank:], theta)  # [T, 1, rope]
    q_rope = _rope(q[..., nope:], theta)
    up = (c @ w["wkv_b"]).reshape(t, n_head, -1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rope, (t, n_head, rope))], -1)
    v = up[..., nope:]
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v).reshape(t, -1)
    return h + attn @ w["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "store"))
def dense_ffn_part(h, w, *, eps, dtype, store):
    w = _cast({k: w[k] for k in ("ln2", "w_gate", "w_up", "w_down")}, dtype,
              store)
    return h + _swiglu(_rms(h, w["ln2"], eps), w["w_gate"], w["w_up"],
                       w["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "norm_topk", "eps", "dtype"))
def route_part(h, w, *, top_k, scale, norm_topk, eps, dtype):
    """(normed input x, choice [T, k], weights [T, k], margin [T]): margin
    is the distance between the k-th and the (k+1)-th biased score, how far
    a rounding must move a score to change the choice."""
    x = _rms(h, w["ln2"].astype(dtype), eps)
    s = jax.nn.sigmoid(x @ w["router"].astype(dtype))
    bias = w["router_bias"].astype(dtype) if "router_bias" in w else 0.0
    top, choice = jax.lax.top_k(s + bias, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]).astype(jnp.float32)
    choice = choice[:, :top_k]
    wts = jnp.take_along_axis(s, choice, axis=-1)
    if norm_topk:
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    return x, choice, wts * scale, margin


@functools.partial(jax.jit, static_argnames=("first", "dtype", "store"))
def expert_group_part(x, gate, up, down, choice, wts, *, first, dtype, store):
    """sum over the experts ``first .. first + G`` of ``coef_e * E_e(x)``
    where ``coef_e[t] = sum_k [choice[t, k] == e] wts[t, k]``."""
    gate, up, down = _cast((gate, up, down), dtype, store)
    out = jnp.zeros_like(x)
    for j in range(gate.shape[0]):
        coef = jnp.sum(jnp.where(choice == first + j, wts, 0), axis=-1)
        out = out + coef[:, None] * _swiglu(x, gate[j], up[j], down[j])
    return out


@functools.partial(jax.jit, static_argnames=("dtype", "store"))
def shared_part(x, w, *, dtype, store):
    w = _cast({k: w[k] for k in ("ws_gate", "ws_up", "ws_down")}, dtype,
              store)
    return _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])


def expert_ffn(h, w, *, top_k, scale, norm_topk, eps, dtype, store):
    """``h + experts(rms(h))`` on one sequence; also the routing margins."""
    x, choice, wts, margin = route_part(
        h, w, top_k=top_k, scale=scale, norm_topk=norm_topk, eps=eps,
        dtype=dtype)
    out = jnp.zeros_like(x)
    for first in range(0, w["w_gate"].shape[0], EXPERT_GROUP):
        g = slice(first, first + EXPERT_GROUP)
        out = out + expert_group_part(
            x, w["w_gate"][g], w["w_up"][g], w["w_down"][g], choice, wts,
            first=first, dtype=dtype, store=store)
    if "ws_gate" in w:
        out = out + shared_part(x, w, dtype=dtype, store=store)
    return h + out, margin


def layers(params):
    """The per-layer weight trees in order, out of the stacked runs."""
    for run in params["runs"]:
        n = jax.tree_util.tree_leaves(run)[0].shape[0]
        for j in range(n):
            yield jax.tree_util.tree_map(lambda a, j=j: a[j], run)


def hidden_states(params, tokens, *, n_head, nope, rope, theta, eps, top_k,
                  scale, norm_topk=True, dtype=jnp.float32, store=None):
    """(hidden states [T, D] before the final norm, routing margins
    [expert layers, T]) for one unpadded sequence of token ids."""
    h = _cast(jnp.take(params["tok_emb"], jnp.asarray(tokens, jnp.int32),
                       axis=0), dtype, store)
    margins = []
    for w in layers(params):
        h = attention_part(h, w, n_head=n_head, nope=nope, rope=rope,
                           theta=theta, eps=eps, dtype=dtype, store=store)
        if "router" in w:
            h, margin = expert_ffn(h, w, top_k=top_k, scale=scale,
                                   norm_topk=norm_topk, eps=eps, dtype=dtype,
                                   store=store)
            margins.append(margin)
        else:
            h = dense_ffn_part(h, w, eps=eps, dtype=dtype, store=store)
    return h, jnp.stack(margins)


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "store"))
def _head_logits(h, ln_f, head, *, eps, dtype, store=None):
    return (_rms(h, ln_f.astype(dtype), eps) @ _cast(head, dtype, store)
            ).astype(jnp.float32)


def logits(params, tokens, **hp):
    """Logits [T, V] for one unpadded sequence (small sizes: tests)."""
    dtype = hp.get("dtype", jnp.float32)
    with jax.default_matmul_precision("highest"):
        h, _ = hidden_states(params, tokens, **hp)
        return np.asarray(_head_logits(
            h, params["ln_f"], params["lm_head"], eps=hp["eps"], dtype=dtype,
            store=hp.get("store")))


def token_logprobs(params, tokens, at, **hp):
    """(log p(tokens[t + 1] | tokens[:t + 1]) for every t in ``at``, the
    routing margins [expert layers, len(at)] at those positions) for one
    unpadded sequence ``tokens`` ([T] ints), as float32 numpy. ``dtype=
    jnp.bfloat16`` computes ALL of it in bfloat16, ``store=
    jnp.float8_e4m3fn`` rounds every matrix to float8 first: what the limits
    must tell from this file's own answer."""
    dtype = hp.get("dtype", jnp.float32)
    tokens = jnp.asarray(tokens, jnp.int32)
    at = np.asarray(at)
    with jax.default_matmul_precision("highest"):
        h, margins = hidden_states(params, tokens, **hp)
        out = []
        for s in range(0, at.size, HEAD_BLOCK):
            idx = jnp.asarray(at[s:s + HEAD_BLOCK])
            lg = _head_logits(h[idx], params["ln_f"], params["lm_head"],
                              eps=hp["eps"], dtype=dtype,
                              store=hp.get("store"))
            out.append(np.asarray(jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), tokens[idx + 1][:, None],
                axis=-1)[:, 0]))
    return np.concatenate(out), np.asarray(margins)[:, at]
