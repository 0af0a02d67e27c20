"""Plain reference for a Jamba-class hybrid decoder: pre-norm residual
layers ``h += mixer(rms(h)); h += mlp(rms(h))``, a final RMSNorm and a head
tied to the embedding. Layer ``i`` is an attention layer iff ``i % period ==
offset``; every other layer is a Mamba-1 layer. Every MLP is the dense
SwiGLU ``down(silu(gate(x)) * up(x))`` (``num_experts`` 1).

- Attention: ``n_head`` query heads, ``n_kv`` KV heads, no bias, causal
  softmax, NO positional encoding of any kind.
- Mamba: ``x, z = split(in_proj(u))``; ``x = silu(causal depthwise conv1d(x,
  k) + conv_b)``; ``dt, B, C = split(x_proj(x))``; Jamba's addition ``dt, B,
  C = rms(dt), rms(B), rms(C)`` with three learned scales; ``dt =
  softplus(dt_proj(dt) + dt_bias)``; ``A = -exp(A_log)``; for every position
  ``h_t = exp(dt_t * A) * h_{t-1} + (dt_t * B_t) * x_t`` on a state
  ``[d_inner, d_state]``, ``y_t = h_t . C_t + D * x_t``; ``out = out_proj(y
  * silu(z))``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; the recurrence is a plain
``lax.scan`` over positions. No kernel, no cache, no chunking, no batching,
no padding: one unpadded sequence at a time, computed layer by layer (one
jitted call a layer, the float32 copy of ONE layer's weights alive at a
time), so that it fits beside the program on the chip. Imports nothing from
the program. It is handed the program's weight arrays, one stacked tree a
run of equal layers (``runs[r]``, leading axis = the run's layers, in layer
order), under the names ``tok_emb``, ``ln_f`` and per layer ``ln1
ln2 w_gate w_up w_down`` plus ``wq wk wv wo`` (attention) or ``in_proj
conv_w conv_b x_proj dt_norm b_norm c_norm dt_proj dt_bias A_log D
out_proj`` (Mamba).

Departures from the published description:

- ``conv_w`` is ``[k, d_inner]`` (tap-major) where the published Conv1d
  weight is ``[d_inner, 1, k]``: a transpose of the same numbers; tap ``j``
  multiplies ``x[t - (k - 1) + j]`` in both.
- The published ``dt_proj`` is a Linear with bias whose bias is what is
  called ``dt_bias`` here, applied outside the matmul: the same sum.
- ``mamba_proj_bias`` false and ``mamba_conv_bias`` true, as published.
- Experts: the published model has one (``num_experts`` 1), so no router
  exists here.

TOLERANCES, with their reasons.

``LP_MEAN_TOL`` / ``LP_MAX_TOL``: on log-probabilities of magnitude
~log(vocab) = 10..12 computed by the program with bf16 matmul inputs, f32
accumulation and an f32 SSM state, over 2 rows x 256 positions. Set from
two readings on a TPU v5e at published widths (PERF.md, PR 27, has the
runs). What the program gives — the cell's own warm-up check, learn side
and paged tier, 34 seeds, and
``perfbench/tests/test_precision_control_hybrid.py``, 12 more, 80 readings:
mean 0.0303..0.0368 (0.0329 +- 0.0012), max 0.103..0.171. What this file
gives against itself one precision below (that test, through the runner's
``reference_check``, 12 seeds, and 2 earlier ones): with the SSM state and
``exp(dt * A)`` rounded to bfloat16 at every position mean 0.0368..0.132,
max 0.315..1.449; with the stored matrices rounded to float8 mean
0.80..0.91, max 2.98..4.85. A rounded state's error sits in few positions
(its max is 7..12 times its mean, the program's 3..5 times), so the max
limit is the one that tells it on every seed: 0.25 is 1.46 times the
program's largest and 0.79 of the control's smallest. The mean limit,
0.045, is 1.22 times the program's largest (10 standard deviations above
the readings' mean) and under the control on 13 seeds of 14: the odd one
read 0.0368, what the program's own rounding reached once, so no mean
limit tells that seed. Both were 2**-4 and 4 * 2**-4 = 0.25, one and four
bf16 ulps at this magnitude, while the control had two seeds; the max
stayed because every later check draws new seeds and the largest of 512
positions has a long tail.

``LOGIT_TOL`` / ``SCAN_TOL`` / ``SCAN_GRAD_TOL``: for the CPU tests, where
the program runs in float32 too (``dtype=float32``) and differs from this
file only by the order of its sums. At the tests' tiny size logits are of
magnitude ~0.3 and log-probabilities ~4.6: both agree to 2e-5 (measured
2e-7 .. 5e-7); the scan's outputs, of magnitude ~1, to 1e-4 and its
gradients to 1e-3 of the largest gradient (measured ~1e-7). An SSM state or
a discretisation ``exp(dt * A)`` kept in bfloat16 loses 8 bits at every one
of T steps: it moves the scan's outputs by ~1e-2, a hundred times
``SCAN_TOL``, and the tiny model's logits by ~1e-4, six times ``LOGIT_TOL``
(``bf16_state=True`` below is that variant, for the tests that show both).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LP_MEAN_TOL = 0.045
LP_MAX_TOL = 0.25
LOGIT_TOL = 2e-5
SCAN_TOL = 1e-4
SCAN_GRAD_TOL = 1e-3
HEAD_BLOCK = 128  # positions per head call: 128 x 65536 f32 logits = 34 MB


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _mlp(h, w, eps):
    x = _rms(h, w["ln2"], eps)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "eps"))
def attention_block(h, w, *, n_head, n_kv, eps):
    """One attention layer on one sequence. h: [T, D] float32."""
    w = _f32(w)
    t, d = h.shape
    hd = w["wq"].shape[1] // n_head
    x = _rms(h, w["ln1"], eps)
    q = (x @ w["wq"]).reshape(t, n_head, hd)
    k = (x @ w["wk"]).reshape(t, n_kv, hd)
    v = (x @ w["wv"]).reshape(t, n_kv, hd)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v).reshape(t, n_head * hd)
    return _mlp(h + attn @ w["wo"], w, eps)


def selective_scan(x, dt, A, Bm, Cm, D, h0=None, bf16_state=False):
    """The recurrence on one sequence, position by position. x, dt: [T, Di];
    A: [Di, N]; Bm, Cm: [T, N]; D: [Di]; h0: [Di, N] or None (zeros).
    Returns (y [T, Di], h_T [Di, N])."""
    # reduce_precision, not a cast there and back: XLA may drop such a pair
    # (xla_allow_excess_precision), and did on the chip
    lossy = ((lambda a: jax.lax.reduce_precision(a, 8, 7))
             if bf16_state else (lambda a: a))
    h = jnp.zeros(A.shape, jnp.float32) if h0 is None else h0

    def step(h, s):
        x_t, dt_t, b_t, c_t = s
        h = lossy(lossy(jnp.exp(dt_t[:, None] * A)) * h
                  + (dt_t * x_t)[:, None] * b_t[None, :])
        return h, h @ c_t + D * x_t

    h, y = jax.lax.scan(step, h, (x, dt, Bm, Cm))
    return y, h


@functools.partial(jax.jit, static_argnames=("eps", "bf16_state"))
def mamba_block(h, w, *, eps, bf16_state=False):
    """One Mamba layer on one sequence. h: [T, D] float32."""
    w = _f32(w)
    t = h.shape[0]
    k, di = w["conv_w"].shape
    n = w["A_log"].shape[1]
    r = w["dt_proj"].shape[0]
    u = _rms(h, w["ln1"], eps)
    xz = u @ w["in_proj"]
    x, z = xz[:, :di], xz[:, di:]
    xpad = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), x])
    x = jax.nn.silu(
        sum(w["conv_w"][j] * xpad[j:j + t] for j in range(k)) + w["conv_b"])
    dbc = x @ w["x_proj"]
    dt = _rms(dbc[:, :r], w["dt_norm"], eps)
    bm = _rms(dbc[:, r:r + n], w["b_norm"], eps)
    cm = _rms(dbc[:, r + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(dt @ w["dt_proj"] + w["dt_bias"])
    y, _ = selective_scan(x, dt, -jnp.exp(w["A_log"]), bm, cm, w["D"],
                          bf16_state=bf16_state)
    return _mlp(h + (y * jax.nn.silu(z)) @ w["out_proj"], w, eps)


def layers(params):
    """The per-layer weight trees in order, out of the stacked runs."""
    for run in params["runs"]:
        n = jax.tree_util.tree_leaves(run)[0].shape[0]
        for j in range(n):
            yield jax.tree_util.tree_map(lambda a, j=j: a[j], run)


def hidden_states(params, tokens, *, n_head, n_kv, eps, bf16_state=False):
    """Hidden states [T, D] before the final norm, for one unpadded
    sequence of token ids."""
    h = jnp.take(params["tok_emb"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    for w in layers(params):
        if "wq" in w:
            h = attention_block(h, w, n_head=n_head, n_kv=n_kv, eps=eps)
        else:
            h = mamba_block(h, w, eps=eps, bf16_state=bf16_state)
    return h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_logits(h, ln_f, tok_emb, *, eps):
    return _rms(h, ln_f.astype(jnp.float32), eps) @ tok_emb.astype(jnp.float32).T


def logits(params, tokens, *, n_head, n_kv, eps, bf16_state=False):
    """Logits [T, V] for one unpadded sequence (small sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, n_head=n_head, n_kv=n_kv, eps=eps,
                          bf16_state=bf16_state)
        return np.asarray(_head_logits(h, params["ln_f"], params["tok_emb"],
                                       eps=eps))


def token_logprobs(params, tokens, at, *, n_head, n_kv, eps,
                   bf16_state=False):
    """log p(tokens[t + 1] | tokens[:t + 1]) for every t in ``at``, for one
    unpadded sequence ``tokens`` ([T] ints). Returns float32 numpy.
    ``bf16_state`` is the lossy variant of ``selective_scan`` above: what
    the limits must tell from this file's own answer."""
    tokens = jnp.asarray(tokens, jnp.int32)
    at = np.asarray(at)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, n_head=n_head, n_kv=n_kv, eps=eps,
                          bf16_state=bf16_state)
        out = []
        for s in range(0, at.size, HEAD_BLOCK):
            idx = jnp.asarray(at[s:s + HEAD_BLOCK])
            lg = _head_logits(h[idx], params["ln_f"], params["tok_emb"],
                              eps=eps)
            out.append(np.asarray(jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), tokens[idx + 1][:, None],
                axis=-1)[:, 0]))
    return np.concatenate(out)
