"""What the two GRPO runners share: the program's ``GPTConfig`` from a
configuration file's published keys, the base made on the device from the
seed, the agent, and the comparison with the plain reference."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from perfbench.reference import dense_gqa_f32 as ref

CHECK_ROWS = 2  # rows of the warm-up batch compared with the reference
CHECK_POSITIONS = 256  # seeded sample of completion positions in each


def gpt_config(config: Dict[str, Any]) -> M.GPTConfig:
    """The configuration file's published keys, under the program's names."""
    extra = dict(config.get("gpt_config", {}))
    extra["dtype"] = jnp.dtype(extra.get("dtype", "bfloat16")).type
    for key in ("flash_shard_axes", "fused_loss_shard_axes"):
        if key in extra:  # JSON lists -> the tuples the program compares
            extra[key] = tuple(tuple(a) if isinstance(a, list) else a
                               for a in extra[key])
    if config["hidden_act"] != "silu":
        raise ValueError("the program's FFN is SwiGLU (silu) only")
    return M.GPTConfig(
        vocab_size=int(config["vocab_size"]),
        n_layer=int(config["num_hidden_layers"]),
        n_head=int(config["num_attention_heads"]),
        n_kv_head=int(config["num_key_value_heads"]),
        d_model=int(config["hidden_size"]),
        d_ff=int(config["intermediate_size"]),
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        qkv_bias=bool(config["attention_bias"]), **extra)


def _init(key, cfg: M.GPTConfig):
    """``M.init_params``' own draw, with q/k/v biases drawn too (it leaves
    them zero, and a zero bias checks nothing)."""
    k_params, k_bias = jax.random.split(key)
    params = M.init_params(k_params, cfg)
    if cfg.qkv_bias:
        for i, blk in params["blocks"].items():
            for j, name in enumerate(("bq", "bk", "bv")):
                k = jax.random.fold_in(jax.random.fold_in(k_bias, int(i)), j)
                blk[name] = 0.02 * jax.random.normal(
                    k, blk[name].shape, jnp.float32)
    return params


def make_base(cfg: M.GPTConfig, seed: int, shardings=None):
    """The frozen base, made on the device in one jitted call from the seed,
    in the type it is served in (f32 masters): no host copy. With
    ``shardings`` (a function from the tree of shapes to a tree of
    shardings) it is made already sharded."""
    key = jax.random.PRNGKey(seed)
    out = None
    if shardings is not None:
        out = shardings(jax.eval_shape(lambda k: _init(k, cfg), key))
    return jax.jit(lambda k: _init(k, cfg), out_shardings=out)(key)


def make_agent(cfg: M.GPTConfig, base, seed: int, config: Dict[str, Any],
               tok, *, group_size: int, rows: int, new_tokens: int) -> GRPO:
    agent_args = dict(config["agent"])
    agent_args["lora_targets"] = tuple(agent_args["lora_targets"])
    return GRPO(config=cfg, base_params=base, pad_token_id=tok.pad_token_id,
                eos_token_id=tok.eos_token_id, group_size=group_size,
                batch_size=rows, max_output_tokens=new_tokens, seed=seed,
                **agent_args)


def flat(tree) -> np.ndarray:
    return np.concatenate(
        [np.ravel(x) for x in jax.tree_util.tree_leaves(jax.device_get(tree))])


def reference_check(cfg: M.GPTConfig, base, ids: np.ndarray,
                    action_masks: np.ndarray, pad_id: int, seed: int,
                    program_lp: np.ndarray, what: str,
                    rollout_lp: Optional[np.ndarray] = None):
    """The program's token log-probabilities against the plain reference on
    ``CHECK_ROWS`` rows, a seeded sample of ``CHECK_POSITIONS`` completion
    positions each. ``program_lp`` is ``[rows, T-1]`` in ``token_logprobs``'
    layout; ``rollout_lp`` (``[rows, new_tokens]``), where given, holds what
    the paged tier assigned to its own sampled tokens. The adapters must
    still be at their zero initialisation (the reference has none).
    Returns (problems, a record for the progress line)."""
    rng = np.random.default_rng([seed, 7])
    problems, record = [], {}
    diffs = {"learn": [], "rollout": []}
    magnitude = []
    for row in range(min(CHECK_ROWS, ids.shape[0])):
        # the reference sees the bare sequence, without the left padding, so
        # the program's masks and positions are part of what is checked. It
        # is filled up on the right to the batch's length (attention is
        # causal: what follows a position cannot change it), so that the
        # reference's programs have one shape whatever the seed's prompt
        real = np.flatnonzero(ids[row] != pad_id)
        first = int(real[0]) if real.size else 0
        tokens = np.concatenate([ids[row, first:], np.full(first, 2, ids.dtype)])
        cols = np.flatnonzero(action_masks[row] > 0)
        cols = rng.choice(cols, size=min(CHECK_POSITIONS, cols.size),
                          replace=False)
        cols.sort()
        want = ref.token_logprobs(
            base, tokens, cols - first, n_head=cfg.n_head, n_kv=cfg.kv_heads,
            theta=cfg.rope_theta, eps=cfg.rms_eps)
        magnitude.append(np.abs(want))
        diffs["learn"].append(np.abs(program_lp[row, cols] - want))
        if rollout_lp is not None:
            n_new = rollout_lp.shape[1]
            comp_cols = cols - (ids.shape[1] - 1 - n_new)
            diffs["rollout"].append(np.abs(rollout_lp[row, comp_cols] - want))
    record["ref_lp_mean_abs"] = float(np.concatenate(magnitude).mean())
    for name, parts in diffs.items():
        if not parts:
            continue
        d = np.concatenate(parts)
        record[f"{name}_lp_mean_abs_diff"] = float(d.mean())
        record[f"{name}_lp_max_abs_diff"] = float(d.max())
        if not np.isfinite(d).all() or d.mean() > ref.LP_MEAN_TOL \
                or d.max() > ref.LP_MAX_TOL:
            problems.append(
                f"{what}: {name} log-probabilities against the reference: "
                f"mean {d.mean():.4f} (tolerance {ref.LP_MEAN_TOL}), max "
                f"{d.max():.4f} (tolerance {ref.LP_MAX_TOL})")
    return problems, record
