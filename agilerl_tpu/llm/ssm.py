"""The state-space mixer of a hybrid stack: Mamba-1 as Jamba runs it.

    x, z   = split(in_proj(u))                        d -> 2 * d_inner
    x      = silu(causal_depthwise_conv1d(x, k) + conv_b)
    dt,B,C = split(x_proj(x))                         d_inner -> R + N + N
    dt,B,C = rms(dt), rms(B), rms(C)                  (Jamba's addition)
    dt     = softplus(dt_proj(dt) + dt_bias)          R -> d_inner
    y      = selective_scan(x, dt, -exp(A_log), B, C, D)
    out    = out_proj(y * silu(z))                    d_inner -> d

Three entry shapes, one set of maths (``mixer``):

- no state (training / log-probabilities): the whole sequence through
  ``ops.selective_scan`` from a zero state;
- state and ``T > 1`` (prefill): the first ``T - 1`` positions through the
  scan, the last through the one-token step, so that the state BEFORE the
  last token exists — the serving tier snapshots it, because a prefix-cache
  hit re-enters the last prompt token (docs/serving.md);
- state and ``T == 1`` (decode): the one-token step.

State of a layer: the conv window (the last ``k - 1`` inputs of the conv,
in the compute dtype) and the SSM state ``[d_state, d_inner]`` in float32
(``d_inner`` on the lanes: see ops/selective_scan.py). The discretisation
``exp(dt * A)`` and the state never leave float32.

Pads: a pad position's conv input is zeroed and its ``dt`` is zero, so the
left padding the system uses is exact (zeros are what a sequence starts
from). The one-token step leaves window and state as they are where
``mask == 0`` (a finished or free slot). Pads in the MIDDLE of a sequence
would still shift a zero into the full-sequence conv: not a layout this
system builds.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from agilerl_tpu.llm.model import GPTConfig, _maybe_lora, _normal, _rms
from agilerl_tpu.ops.selective_scan import selective_scan

CONV_SCOPE = "ssm/conv"
STEP_SCOPE = "ssm/step"

#: the published dt initialisation (Mamba-1): dt drawn log-uniform here
DT_MIN, DT_MAX = 1e-3, 0.1


def mamba_lora_dims(config: GPTConfig) -> Dict[str, Tuple[int, int]]:
    """(in, out) of the mixer's projections that take a LoRA adapter."""
    d, di = config.d_model, config.mamba_d_inner
    r, n = config.mamba_rank, config.mamba_d_state
    return {"in_proj": (d, 2 * di), "x_proj": (di, r + 2 * n),
            "out_proj": (di, d)}


def init_mamba_mixer(key: jax.Array, config: GPTConfig, out_std: float) -> Dict:
    """Matrices normal(0, 0.02) like the rest of the model; the conv as a
    depthwise Conv1d is drawn by default (uniform +-1/sqrt(k), bias too);
    ``A_log`` S4D-real (log 1..N on every channel), ``D`` ones, ``dt_bias``
    the inverse softplus of a log-uniform dt in [1e-3, 0.1] — the published
    initialisation, under which the state neither dies nor blows up."""
    d, di = config.d_model, config.mamba_d_inner
    r, n, k = config.mamba_rank, config.mamba_d_state, config.mamba_d_conv
    ks = jax.random.split(key, 7)
    bound = 1.0 / math.sqrt(k)
    dt = jnp.exp(jax.random.uniform(ks[6], (di,), jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    return {
        "in_proj": _normal(ks[0], (d, 2 * di), 0.02),
        "conv_w": jax.random.uniform(ks[1], (k, di), jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(ks[2], (di,), jnp.float32, -bound, bound),
        "x_proj": _normal(ks[3], (di, r + 2 * n), 0.02),
        "dt_norm": jnp.ones((r,), jnp.float32),
        "b_norm": jnp.ones((n,), jnp.float32),
        "c_norm": jnp.ones((n,), jnp.float32),
        "dt_proj": _normal(ks[4], (r, di), 0.02),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (di, n)),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _normal(ks[5], (di, d), out_std),
    }


def init_state(config: GPTConfig, n_layers: int, batch: int):
    """(conv [n, B, k-1, d_inner] compute dtype, ssm [n, B, N, d_inner] f32)"""
    di = config.mamba_d_inner
    return (jnp.zeros((n_layers, batch, config.mamba_d_conv - 1, di),
                      config.dtype),
            jnp.zeros((n_layers, batch, config.mamba_d_state, di),
                      jnp.float32))


def _proj_f32(x, w, lora_layer, name, scale, dtype):
    """``_maybe_lora`` with a float32 result: what feeds the discretisation
    is multiplied in the compute dtype and accumulated and kept in f32."""
    f32 = jnp.float32
    x = x.astype(dtype)
    y = jnp.dot(x, w.astype(dtype), preferred_element_type=f32)
    if lora_layer is not None and name in lora_layer:
        a = lora_layer[name]["A"].astype(dtype)
        b = lora_layer[name]["B"].astype(dtype)
        y = y + jnp.dot(x @ a, b, preferred_element_type=f32) * scale
    return y


def _conv_taps(window_at, w, b):
    """sum_j w[j] * window_at(j) + b in float32, taps in one fixed order:
    the full-sequence conv and the one-token step add the same numbers in
    the same order."""
    acc = b.astype(jnp.float32)
    for j in range(w.shape[0]):
        acc = acc + w[j].astype(jnp.float32) * window_at(j).astype(jnp.float32)
    return acc


def _ssm_inputs(config, blk, xc, lora_layer, lora_scale):
    """dt (after softplus), B, C in float32 from the conv's output."""
    r, n = config.mamba_rank, config.mamba_d_state
    dbc = _proj_f32(xc, blk["x_proj"], lora_layer, "x_proj", lora_scale,
                    config.dtype)
    dt_r = _rms(dbc[..., :r], blk["dt_norm"], config.rms_eps)
    bm = _rms(dbc[..., r:r + n], blk["b_norm"], config.rms_eps)
    cm = _rms(dbc[..., r + n:], blk["c_norm"], config.rms_eps)
    dt = _proj_f32(dt_r, blk["dt_proj"], None, "dt_proj", lora_scale,
                   config.dtype)
    return jax.nn.softplus(dt + blk["dt_bias"].astype(jnp.float32)), bm, cm


def _step(a_t, d_skip, ssm, xc_t, dt_t, b_t, c_t, m_t):
    """One position of the recurrence. ssm [B, N, D]; xc_t, dt_t [B, D];
    b_t, c_t [B, N]; m_t [B]. Returns (y_t [B, D] float32, new ssm)."""
    xc32 = xc_t.astype(jnp.float32)
    dtm = dt_t * m_t[:, None]
    h = (jnp.exp(dtm[:, None, :] * a_t) * ssm
         + b_t[:, :, None] * (dtm * xc32)[:, None, :])
    return jnp.sum(h * c_t[:, :, None], axis=1) + d_skip * xc32, h


def mixer(
    config: GPTConfig,
    blk: Dict,
    u: jax.Array,                 # [B, T, d] the block's normed input
    mask: jax.Array,              # [B, T] 1 = real position
    state: Optional[Tuple[jax.Array, jax.Array]] = None,  # (conv, ssm)
    lora_layer: Optional[Dict] = None,
    lora_scale: float = 2.0,
):
    """Returns (out [B, T, d], new_state, prev_state): ``new_state`` after
    the last position, ``prev_state`` before it (``T > 1`` with a state
    only; else None)."""
    B, T, _ = u.shape
    dtype = u.dtype
    f32 = jnp.float32
    di, k = config.mamba_d_inner, config.mamba_d_conv
    m = mask.astype(f32)
    xz = _maybe_lora(u, blk["in_proj"], lora_layer, "in_proj", lora_scale,
                     dtype)
    x = xz[..., :di] * mask.astype(dtype)[..., None]
    z = xz[..., di:]
    a = -jnp.exp(blk["A_log"].astype(f32))  # [D, N]; the step reads a.T
    d_skip = blk["D"].astype(f32)
    new_state = prev_state = None

    if state is not None and T == 1:
        conv, ssm = state
        with jax.named_scope(STEP_SCOPE):
            window = jnp.concatenate([conv, x], axis=1)  # [B, k, di]
            xc = jax.nn.silu(_conv_taps(
                lambda j: window[:, j], blk["conv_w"], blk["conv_b"])
            ).astype(dtype)
            keep = mask[:, 0].astype(bool)
            new_conv = jnp.where(keep[:, None, None], window[:, 1:], conv)
        dt, bm, cm = _ssm_inputs(config, blk, xc[:, None], lora_layer,
                                 lora_scale)
        with jax.named_scope(STEP_SCOPE):
            y, h = _step(a.T, d_skip, ssm, xc, dt[:, 0], bm[:, 0], cm[:, 0],
                         m[:, 0])
        y = y[:, None].astype(dtype)
        new_state = (new_conv, h)
    else:
        with jax.named_scope(CONV_SCOPE):
            left = (state[0] if state is not None
                    else jnp.zeros((B, k - 1, di), dtype))
            xpad = jnp.concatenate([left, x], axis=1)  # [B, k-1+T, di]
            xc = jax.nn.silu(_conv_taps(
                lambda j: xpad[:, j:j + T], blk["conv_w"], blk["conv_b"])
            ).astype(dtype)
        dt, bm, cm = _ssm_inputs(config, blk, xc, lora_layer, lora_scale)
        if state is None:
            y, _ = selective_scan(xc, dt, a, bm, cm, d_skip, m)
        else:
            y_head, h_prev = selective_scan(
                xc[:, :-1], dt[:, :-1], a, bm[:, :-1], cm[:, :-1], d_skip,
                m[:, :-1], state[1])
            with jax.named_scope(STEP_SCOPE):
                y_last, h = _step(a.T, d_skip, h_prev, xc[:, -1], dt[:, -1],
                                  bm[:, -1], cm[:, -1], m[:, -1])
            y = jnp.concatenate([y_head, y_last[:, None].astype(dtype)],
                                axis=1)
            new_state = (xpad[:, T:], h)
            prev_state = (xpad[:, T - 1:T + k - 2], h_prev)
    out = _maybe_lora(y * jax.nn.silu(z), blk["out_proj"], lora_layer,
                      "out_proj", lora_scale, dtype)
    return out, new_state, prev_state
