"""Plain reference for the dense GQA decoder block (Qwen2-class): RMSNorm,
biased q/k/v projections, RoPE, grouped-query causal softmax attention,
SwiGLU, final norm and an untied head. Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")`` (a TPU otherwise
multiplies float32 in bf16 passes). No kernel, no cache, no batching, no
padding: one unpadded sequence at a time. Imports nothing from the program;
it is handed the same weight arrays under the names ``tok_emb``, ``blocks``
(per layer ``ln1 wq wk wv wo ln2 w_gate w_up w_down`` and, if biased,
``bq bk bv``), ``ln_f``, ``lm_head``.

Departure from the published description: RoPE rotates interleaved pairs
``(x[2i], x[2i+1])`` as the program does, where the published code rotates
the two halves ``(x[i], x[i + hd/2])``. The two are the same function up to
a fixed permutation of the columns of wq and wk; with weights made from a
seed there is no published checkpoint whose column order could be broken.

Each layer is one jitted call of one program (the layers share shapes), and
the head is applied to blocks of positions, so a check of a few hundred
positions at a 152064-wide head stays under a gigabyte.

TOLERANCE, on log-probabilities of magnitude ~log(vocab) = 8..16 computed by
the program with bf16 matmul inputs and f32 accumulation: one bf16 ulp at
that magnitude is 2**-4. The mean absolute difference must stay within one
ulp and the worst position within 4. (Kernels on against kernels off, both
bf16, measured mean 0.016 / max 0.078 on the chip in PR 21.) A dropped bias,
norm or rotation, a wrong mask or position, or 8-bit weights move the
log-probabilities by tenths to units and fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LP_MEAN_TOL = 2.0 ** -4
LP_MAX_TOL = 4 * 2.0 ** -4
HEAD_BLOCK = 128  # positions per head call: 128 x 152064 f32 logits = 78 MB


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [T, H, hd], positions 0..T-1, interleaved pairs."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "theta", "eps"))
def block(h, w, *, n_head, n_kv, theta, eps):
    """One decoder block on one sequence. h: [T, D] float32."""
    t, d = h.shape
    hd = d // n_head
    x = _rms(h, w["ln1"], eps)
    q, k, v = x @ w["wq"], x @ w["wk"], x @ w["wv"]
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(t, n_head, hd), theta)
    k = _rope(k.reshape(t, n_kv, hd), theta)
    v = v.reshape(t, n_kv, hd)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v).reshape(t, d)
    h = h + attn @ w["wo"]
    x = _rms(h, w["ln2"], eps)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_logprobs(h, ln_f, head, targets, *, eps):
    logits = _rms(h, ln_f, eps) @ head
    return jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]


def token_logprobs(params, tokens, at, *, n_head, n_kv, theta, eps):
    """log p(tokens[t + 1] | tokens[:t + 1]) for every t in ``at``, for one
    unpadded sequence ``tokens`` ([T] ints). Returns float32 numpy."""
    tokens = jnp.asarray(tokens, jnp.int32)
    at = np.asarray(at)
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["tok_emb"], tokens, axis=0).astype(jnp.float32)
        for i in range(len(params["blocks"])):
            h = block(h, params["blocks"][str(i)], n_head=n_head, n_kv=n_kv,
                      theta=theta, eps=eps)
        out = []
        for s in range(0, at.size, HEAD_BLOCK):
            idx = jnp.asarray(at[s:s + HEAD_BLOCK])
            out.append(np.asarray(_head_logprobs(
                h[idx], params["ln_f"], params["lm_head"], tokens[idx + 1],
                eps=eps)))
    return np.concatenate(out)
