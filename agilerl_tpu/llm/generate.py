"""In-tree jitted generation loop — the vLLM replacement
(parity target: agilerl/algorithms/core/base.py:3101 _configure_vllm +
_generate_with_vllm_colocate:2799 + weight hot-swap _move_model_to_vllm:2772.
None of that machinery exists here: training and sampling share one sharded
param tree, the KV cache is a device pytree, and decode is a lax.scan).

Left-padded ragged prompts; per-row RoPE positions; EOS early-stop via done
masking (shapes stay static so XLA compiles once per (B, P, max_new_tokens)).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.llm import model as M
from agilerl_tpu.observability.timeline import device_scope

#: head + sampler of a paged decode step (docs/observability.md, "Device scopes")
HEAD_SCOPE = "decode/head"


def left_pad(
    sequences, pad_id: int, max_len: Optional[int] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Host helper: list of 1D token arrays -> (tokens [B, P], mask [B, P])."""
    max_len = max_len or max(len(s) for s in sequences)
    B = len(sequences)
    toks = np.full((B, max_len), pad_id, np.int32)
    mask = np.zeros((B, max_len), np.int32)
    for i, s in enumerate(sequences):
        s = np.asarray(s, np.int32)[-max_len:]
        toks[i, max_len - len(s):] = s
        mask[i, max_len - len(s):] = 1
    return toks, mask


def _filter_logits(logits, temperature, top_k, top_p):
    """Temperature + top-k + nucleus filtering — ONE home shared by the
    batch-key sampler below and the per-row-key sampler the continuous
    decode path uses, so the two recipes cannot drift."""
    # temperature applies BEFORE the nucleus filter (reference order,
    # sampling_utils.py:107 process_logits): top_p is order-sensitive —
    # a hotter distribution admits more tokens into the nucleus
    logits = logits / temperature
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e9, logits)
    if top_p is not None:
        # nucleus sampling (parity: sampling_utils.py:92 top_p_logits):
        # keep the smallest logit set whose probability mass reaches top_p.
        # Sorted-descending cumulative mass EXCLUSIVE of the current token,
        # so the token that crosses the threshold stays includable.
        sort_idx = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1) - probs
        drop_sorted = cum >= top_p
        drop = jnp.zeros_like(drop_sorted).at[
            jnp.arange(logits.shape[0])[:, None], sort_idx
        ].set(drop_sorted)
        logits = jnp.where(drop, -1e9, logits)
    return logits


def _sample_token(logits, key, temperature, top_k, top_p=None):
    if temperature == 0.0:
        # greedy: filters can't change the argmax
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(
        key, _filter_logits(logits, temperature, top_k, top_p), axis=-1)


def _sample_token_per_row(logits, keys, temperature, top_k, top_p=None):
    """Per-row-key sampling for continuous batching: every slot carries its
    own RNG stream, so the admission order and slot placement of OTHER
    requests cannot change a request's samples."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    filtered = _filter_logits(logits, temperature, top_k, top_p)
    return jax.vmap(lambda k, l: jax.random.categorical(k, l))(keys, filtered)


def _suppress_eos(logits, step, eos_id, min_new_tokens):
    """EOS logit floor for the first min_new_tokens sampled tokens
    (parity: vllm/HF min_output_tokens). step: [] (batch-aligned decode),
    [B] (per-slot step indices in the continuous path), or [B, T]
    (per-candidate indices in the speculative verify window — logits then
    [B, T, V])."""
    if eos_id is None or not min_new_tokens:
        return logits
    lt = jnp.asarray(step) < min_new_tokens
    if lt.ndim:
        lt = lt[..., None]
    return jnp.where(
        lt & (jnp.arange(logits.shape[-1]) == eos_id),
        -1e9, logits,
    )


def prefill_head(config, params, prompt, prompt_mask, caches, key, *,
                 lora, lora_scale, temperature, top_k, top_p, eos_id,
                 pad_id, min_new_tokens, row_valid=None,
                 return_logits=False, keep_prev_state=False):
    """Prompt forward + first sampled token. Returns the decode carry and
    the first (token, emit_mask) pair. row_valid marks real rows (bucket
    padding rows are born done); None means every row is real.
    return_logits=True appends the raw last-position logits [B, V] to the
    return (the serving tier's behavior-logprob capture hook).
    keep_prev_state=True leaves the state of layers that keep one (a hybrid
    stack's recurrent state, a CCA stack's rolling state) from BEFORE the
    last prompt token in the returned cache (the continuous tier
    snapshots it for prefix-cache hits); otherwise it is dropped, so the
    decode loops carry the current state alone.

    SHARED between generate() and llm/serving.BucketedGenerator so the two
    paths cannot drift (review finding)."""
    B = prompt.shape[0]
    positions = jnp.maximum(jnp.cumsum(prompt_mask, axis=-1) - 1, 0)
    hidden, caches = M.forward(
        config, params, prompt, attention_mask=prompt_mask,
        positions=positions, cache=caches, lora=lora, lora_scale=lora_scale,
    )
    if not keep_prev_state and caches.prev_state is not None:
        caches = caches._replace(prev_state=None)
    last_logits = M.logits_fn(config, params, hidden[:, -1:, :])[:, 0, :]
    pos = prompt_mask.sum(axis=-1)
    key, k0 = jax.random.split(key)
    tok0 = _sample_token(
        _suppress_eos(last_logits, 0, eos_id, min_new_tokens), k0,
        temperature, top_k, top_p,
    )
    if row_valid is None:
        row_valid = jnp.ones((B,), bool)
    tok0 = jnp.where(row_valid, tok0, pad_id)
    done0 = ~row_valid
    if eos_id is not None:
        done0 = done0 | (tok0 == eos_id)
    carry = (caches, tok0, row_valid, pos, done0, key)
    if return_logits:
        return carry, (tok0, row_valid), last_logits
    return carry, (tok0, row_valid)


def decode_step(config, params, carry, i, *, lora, lora_scale, temperature,
                top_k, top_p, eos_id, pad_id, min_new_tokens):
    """One decode step: advance with the previous token, sample the next.
    `i` is the ABSOLUTE sampled-token index (drives min_new_tokens).

    SHARED between generate()'s scan and the bucketed decode chunks."""
    caches, prev_tok, prev_valid, pos, done, key = carry
    hidden, caches = M.forward(
        config, params, prev_tok[:, None],
        attention_mask=prev_valid.astype(jnp.int32)[:, None],
        positions=pos[:, None], cache=caches, lora=lora,
        lora_scale=lora_scale,
    )
    logits = M.logits_fn(config, params, hidden[:, -1:, :])[:, 0, :]
    pos = pos + prev_valid.astype(pos.dtype)
    key, k_s = jax.random.split(key)
    tok = _sample_token(
        _suppress_eos(logits, i, eos_id, min_new_tokens), k_s,
        temperature, top_k, top_p,
    )
    if eos_id is not None:
        tok = jnp.where(done, pad_id, tok)
    emit = jnp.logical_not(done)
    if eos_id is not None:
        done = jnp.logical_or(done, tok == eos_id)
    return (caches, tok, emit, pos, done, key), (tok, emit)


@functools.partial(
    jax.jit,
    static_argnames=("config", "max_new_tokens", "temperature", "top_k",
                     "top_p", "eos_id", "pad_id", "lora_scale",
                     "min_new_tokens"),
)
def generate(
    config: M.GPTConfig,
    params,
    prompt: jax.Array,  # [B, P] left-padded
    prompt_mask: jax.Array,  # [B, P]
    key: jax.Array,
    max_new_tokens: int = 64,
    lora=None,
    lora_scale: float = 2.0,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    min_new_tokens: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (completions [B, max_new_tokens], completion_mask [B, max_new_tokens]).

    completion_mask covers tokens up to and including the first EOS.
    min_new_tokens (parity: vllm/HF min_output_tokens) suppresses EOS for
    the first N sampled tokens so completions have a length floor."""
    B, P = prompt.shape
    caches = M.init_caches(config, B, P + max_new_tokens)
    knobs = dict(
        lora=lora, lora_scale=lora_scale, temperature=temperature,
        top_k=top_k, top_p=top_p, eos_id=eos_id, pad_id=pad_id,
        min_new_tokens=min_new_tokens,
    )
    # first token comes straight from the prefill logits; each scan step then
    # advances the model with the PREVIOUS token and samples the next — exactly
    # max_new_tokens - 1 decode forwards, none wasted on logits never sampled
    carry, (tok0, mask0) = prefill_head(
        config, params, prompt, prompt_mask, caches, key, **knobs
    )

    def step(carry, i):
        return decode_step(config, params, carry, i, **knobs)

    _, (tokens, masks) = jax.lax.scan(
        step, carry, jnp.arange(1, max_new_tokens)
    )
    tokens = jnp.concatenate([tok0[None], tokens], axis=0)
    masks = jnp.concatenate([mask0[None], masks], axis=0)
    return tokens.T, masks.T.astype(jnp.int32)  # [B, N]


# --------------------------------------------------------------------------- #
# Continuous (in-flight) batching decode step over a paged slot pool — the
# iteration-level-scheduling role of Orca (Yu et al., OSDI 2022) under XLA's
# compile-once model. The host scheduler (llm/serving.ContinuousGenerator)
# admits/releases slots BETWEEN decode chunks; this step is the per-token
# body, the paged twin of decode_step above: same sampling order, same
# done/emit discipline, but per-slot cache depths, RoPE positions, step
# indices, and RNG streams.
# --------------------------------------------------------------------------- #


def paged_decode_step(config, params, carry, *, lora, lora_scale, temperature,
                      top_k, top_p, eos_id, pad_id, min_new_tokens,
                      capture_lp=False):
    """One decode step for every slot in the pool.

    carry:
      cache        PagedKVCache — the shared physical block pool
      block_tables [slots, max_blocks] int32 (free slots: all-zero -> writes
                   land in the reserved garbage block 0)
      slot_mask    [slots, S] int32 logical-slot validity
      lengths      [slots] int32 cache fill (incl. left-pad; the write slot)
      prev_tok     [slots] previous sampled token (enters the cache now)
      prev_ok      [slots] bool — prev_tok is a real emission (mirrors the
                   dense decode_step's prev_valid/emit)
      pos          [slots] int32 RoPE position (count of real tokens)
      step_idx     [slots] int32 absolute sampled-token index (min_new_tokens)
      done         [slots] bool (free slots are parked done=True)
      keys         [slots, 2] per-slot PRNG keys

    Returns (carry', (tok, emit)) — with capture_lp=True, (carry', (tok,
    emit, lp)) where lp is log p(tok) under the RAW logits (temperature
    1.0, no EOS floor: exactly the model.token_logprobs convention, so the
    GRPO flywheel can consume decode-captured behavior logprobs without a
    second forward). Greedy outputs are bit-identical to
    decode_step for a slot whose blocks hold what the dense cache holds (the
    serving equivalence tests pin this). Over a dropless expert stack the
    ys end with one more member: the distinct experts this step touched,
    summed over the expert layers (a float32 scalar)."""
    (cache, block_tables, slot_mask, lengths, prev_tok, prev_ok, pos,
     step_idx, done, keys) = carry
    n_slots = prev_tok.shape[0]
    S = slot_mask.shape[1]
    # the previous token's slot becomes visible exactly as in the dense path
    # (forward writes attention_mask=prev_valid at cache.length); released
    # slots' lengths may run past S — clamp, their mask rows are all-zero
    # and prev_ok is 0 so the write is a masked no-op
    slot_mask = slot_mask.at[
        jnp.arange(n_slots), jnp.minimum(lengths, S - 1)
    ].set(prev_ok.astype(slot_mask.dtype))
    hidden, new, *aux = M.forward_paged(
        config, params, prev_tok[:, None], pos, lengths, cache, block_tables,
        slot_mask, lora=lora, lora_scale=lora_scale,
        **({"return_aux": True} if config.is_dropless else {}),
    )
    experts_hit = tuple(a[1] for a in aux)
    # (new_k, new_v), and the new per-slot state too where layers keep one
    cache = M.paged_scatter_tokens(cache, block_tables, lengths, *new)
    pos = pos + prev_ok.astype(pos.dtype)
    split = jax.vmap(jax.random.split)(keys)  # [slots, 2, 2]
    keys, k_s = split[:, 0], split[:, 1]
    with device_scope(HEAD_SCOPE):
        logits = M.logits_fn(config, params, hidden)[:, 0, :]
        tok = _sample_token_per_row(
            _suppress_eos(logits, step_idx, eos_id, min_new_tokens), k_s,
            temperature, top_k, top_p,
        )
        tok = jnp.where(done, pad_id, tok)
    emit = jnp.logical_not(done)
    if eos_id is not None:
        done = jnp.logical_or(done, tok == eos_id)
    lengths = lengths + 1
    step_idx = step_idx + 1
    carry = (cache, block_tables, slot_mask, lengths, tok, emit, pos,
             step_idx, done, keys)
    if capture_lp:
        with device_scope(HEAD_SCOPE):
            lsm = jax.nn.log_softmax(logits, axis=-1)
            lp = jnp.take_along_axis(lsm, tok[:, None], axis=-1)[:, 0]
        return carry, (tok, emit, lp) + experts_hit
    return carry, (tok, emit) + experts_hit
