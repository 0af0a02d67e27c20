"""Operations and bytes a DeepSeek-V3-class stack needs, from shapes:
``counts.py``'s functions for latent attention (no query latent), leading
dense layers and expert layers of which a token uses ``num_experts_per_tok``
routed experts and the shared one. Kept with the benchmark so that no PR
that claims a gain can change the denominator. Everything counts the
PUBLISHED mathematics: keys and queries ``qk_nope + qk_rope`` wide, values
``v_head_dim`` wide, whatever a kernel pads; the expanded attention of the
learn programs, not the absorbed form's wider products.

A configuration here is a file under ``perfbench/configs/`` with the
published ``deepseek_v3`` key names.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence


def _dims(model: Dict[str, Any]):
    return {
        "d": int(model["hidden_size"]), "nh": int(model["num_attention_heads"]),
        "nope": int(model["qk_nope_head_dim"]),
        "rope": int(model["qk_rope_head_dim"]), "dv": int(model["v_head_dim"]),
        "rank": int(model["kv_lora_rank"]), "ff": int(model["intermediate_size"]),
        "fe": int(model["moe_intermediate_size"]),
        "fs": int(model["n_shared_experts"]) * int(model["moe_intermediate_size"]),
        "experts": int(model["n_routed_experts"]),
        "k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
        "layers": int(model["num_hidden_layers"]),
        "dense": int(model["first_k_dense_replace"]),
    }


def layer_counts(model) -> Dict[str, int]:
    m = _dims(model)
    return {"dense": m["dense"], "expert": m["layers"] - m["dense"]}


def _mla_shapes(model):
    m = _dims(model)
    return {"wq": (m["d"], m["nh"] * (m["nope"] + m["rope"])),
            "wkv_a": (m["d"], m["rank"] + m["rope"]),
            "wkv_b": (m["rank"], m["nh"] * (m["nope"] + m["dv"])),
            "wo": (m["nh"] * m["dv"], m["d"])}


def mla_params(model) -> int:
    return sum(a * b for a, b in _mla_shapes(model).values())


def expert_params(model) -> int:
    """One routed expert: a SwiGLU hidden -> moe_intermediate -> hidden."""
    m = _dims(model)
    return 3 * m["d"] * m["fe"]


def layer_active_params(model) -> Dict[str, int]:
    """Weights of one layer of each kind that ONE token is multiplied by:
    the attention projections, and the dense SwiGLU, or the router, the
    token's ``k`` routed experts and the shared expert."""
    m = _dims(model)
    attn = mla_params(model)
    return {"dense": attn + 3 * m["d"] * m["ff"],
            "expert": attn + m["d"] * m["experts"]
            + m["k"] * expert_params(model) + 3 * m["d"] * m["fs"]}


def active_matmul_params(model) -> int:
    """N active: every weight a token is multiplied by, the untied head
    included, the embedding lookup not."""
    m = _dims(model)
    per, n = layer_active_params(model), layer_counts(model)
    return (n["dense"] * per["dense"] + n["expert"] * per["expert"]
            + m["d"] * m["vocab"])


def attention_forward_flops(model, lengths: Sequence[int]) -> float:
    """Causal softmax attention of all layers over rows of the given real
    lengths: QK^T over ``nope + rope``, PV over ``v_head_dim``, half of the
    T x T square."""
    m = _dims(model)
    width = (m["nope"] + m["rope"]) + m["dv"]
    return float(m["layers"] * sum(
        2 * m["nh"] * width * t * t / 2 for t in lengths))


def lora_forward_flops_per_token(model, rank: int,
                                 targets: Sequence[str]) -> float:
    shapes = _mla_shapes(model)
    return float(_dims(model)["layers"] * sum(
        2 * (shapes[t][0] * rank + rank * shapes[t][1]) for t in targets))


def grpo_learn_flops(model, lengths: Sequence[int], rank: int,
                     targets: Sequence[str], nograd_passes: int = 2) -> float:
    """``counts.grpo_learn_flops`` for this stack, ACTIVE parameters only:
    per no-grad pass 2 N_active a token, attention by the rows' real
    lengths and the adapters; the update 4 N_active a token (the frozen base
    forms no weight gradients) and three times the forward of attention and
    adapters. Remat's second forward is not counted."""
    tokens = float(sum(lengths))
    n = active_matmul_params(model)
    extra = (attention_forward_flops(model, lengths)
             + lora_forward_flops_per_token(model, rank, targets) * tokens)
    return nograd_passes * (2 * n * tokens + extra) + 4 * n * tokens + 3 * extra


def grouped_matmul_forward_flops(model, rows: float) -> float:
    """The routed experts' three grouped matmuls of all expert layers for
    ``rows`` token positions, forward: every position, a pad too, goes
    through its ``k`` experts."""
    m = _dims(model)
    return float(layer_counts(model)["expert"] * rows * m["k"]
                 * 2 * expert_params(model))


def learn_grouped_matmul_flops(model, rows: float, nograd_passes: int = 2,
                               remat: bool = True) -> float:
    """What the learn programs EXECUTE in grouped matmuls for one
    ``GRPO.learn`` call of ``rows`` positions: the no-grad passes' forwards,
    the update's forward, remat's second forward, and the backward with
    respect to the rows (three grouped matmuls of the forward's size; the
    frozen experts take no weight gradient)."""
    return (nograd_passes + 2 + (1 if remat else 0)) \
        * grouped_matmul_forward_flops(model, rows)


def latent_bytes_per_token(model, kv_bytes: int = 2) -> int:
    """What the cache keeps of a token across all layers."""
    m = _dims(model)
    return m["layers"] * (m["rank"] + m["rope"]) * kv_bytes


def decode_step_bytes(model, live_tokens: float, experts_hit: float,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """The least bytes one decode step over all slots has to move: every
    weight outside the routed experts once in the stored dtype (attention,
    the dense layers' SwiGLU, the shared experts, the head; the router's
    matrix is float32), the routed experts the step's rows really touched
    (``experts_hit``: distinct experts summed over the expert layers), and
    the live latent cache (``live_tokens`` summed over slots)."""
    m = _dims(model)
    n = layer_counts(model)
    fixed = (m["layers"] * mla_params(model)
             + n["dense"] * 3 * m["d"] * m["ff"]
             + n["expert"] * 3 * m["d"] * m["fs"]
             + m["d"] * m["vocab"]) * weight_bytes \
        + n["expert"] * m["d"] * m["experts"] * 4
    return float(fixed + experts_hit * expert_params(model) * weight_bytes
                 + live_tokens * latent_bytes_per_token(model, kv_bytes))
