"""Operations and bytes a ZAYA1-class stack needs, from shapes:
``counts.py``'s functions for compressed convolutional attention (CCA), a
router MLP and experts of which a token uses ``num_experts_per_tok`` (one).
Kept with the benchmark so that no PR that claims a gain can change the
denominator. Everything counts the PUBLISHED mathematics: queries
``num_attention_heads x head_dim`` wide, keys and values
``num_key_value_heads x head_dim``, both convolutions, the router's
down-projection and its two hidden layers, the tied head once.

A configuration here is a file under ``perfbench/configs/`` with the
published ``zaya`` key names.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence


def _dims(model: Dict[str, Any]):
    hd = int(model["head_dim"])
    return {
        "d": int(model["hidden_size"]), "hd": hd,
        "dq": int(model["num_attention_heads"]) * hd,
        "dk": int(model["num_key_value_heads"]) * hd,
        "nh": int(model["num_attention_heads"]),
        "k0": int(model["cca_time0"]), "k1": int(model["cca_time1"]),
        "r": int(model["router_hidden_size"]),
        "fe": int(model["moe_intermediate_size"]),
        "experts": int(model["num_experts"]),
        "k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
        "layers": int(model["num_hidden_layers"]),
    }


def _cca_shapes(model):
    m = _dims(model)
    return {"wq": (m["d"], m["dq"]), "wk": (m["d"], m["dk"]),
            "wv1": (m["d"], m["dk"] // 2), "wv2": (m["d"], m["dk"] // 2),
            "wo": (m["dq"], m["d"])}


def cca_params(model) -> int:
    """The five projections of the attention sublayer."""
    return sum(a * b for a, b in _cca_shapes(model).values())


def conv_params(model) -> Dict[str, int]:
    """Multiply-adds a token of the two sequence convolutions = their
    weights: the depthwise taps, and a head's channels among themselves."""
    m = _dims(model)
    c = m["dq"] + m["dk"]
    return {"conv0": m["k0"] * c, "conv1": m["k1"] * c * m["hd"]}


def router_params(model) -> int:
    """Down-projection, two hidden layers, the output over the experts."""
    m = _dims(model)
    return m["d"] * m["r"] + 2 * m["r"] * m["r"] + m["r"] * m["experts"]


def expert_params(model) -> int:
    """One expert: a SwiGLU hidden -> moe_intermediate -> hidden."""
    m = _dims(model)
    return 3 * m["d"] * m["fe"]


def layer_params(model) -> int:
    """Every weight of a layer: all experts, biases and vectors too."""
    m = _dims(model)
    c = m["dq"] + m["dk"]
    vectors = (2 * m["d"]  # the two norms
               + 2 * c + m["dk"] // m["hd"]  # conv biases, tau
               + 8 * m["d"]  # the two merges
               + 5 * m["r"] + m["experts"])  # router biases, gamma, norm, bias
    return (cca_params(model) + sum(conv_params(model).values())
            + router_params(model) + m["experts"] * expert_params(model)
            + vectors)


def layer_active_params(model) -> int:
    """Weights of one layer that ONE token is multiplied by."""
    return (cca_params(model) + sum(conv_params(model).values())
            + router_params(model) + _dims(model)["k"] * expert_params(model))


def active_matmul_params(model) -> int:
    """N active: every weight a token is multiplied by, the tied head
    included, the embedding lookup not."""
    m = _dims(model)
    return m["layers"] * layer_active_params(model) + m["d"] * m["vocab"]


def attention_forward_flops(model, lengths: Sequence[int]) -> float:
    """Causal softmax attention of all layers over rows of the given real
    lengths: QK^T and PV over ``head_dim``, half of the T x T square."""
    m = _dims(model)
    return float(m["layers"] * sum(
        2 * m["nh"] * 2 * m["hd"] * t * t / 2 for t in lengths))


def lora_forward_flops_per_token(model, rank: int,
                                 targets: Sequence[str]) -> float:
    shapes = _cca_shapes(model)
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
    """The experts' three grouped matmuls of all layers for ``rows`` token
    positions, forward: every position, a pad too, goes through its ``k``
    experts."""
    m = _dims(model)
    return float(m["layers"] * rows * m["k"] * 2 * expert_params(model))


def learn_grouped_matmul_flops(model, rows: float, nograd_passes: int = 2,
                               remat: bool = True) -> float:
    """What the learn programs EXECUTE in grouped matmuls for one
    ``GRPO.learn`` call of ``rows`` positions: the no-grad passes' forwards,
    the update's forward, remat's second forward, and the backward with
    respect to the rows (three grouped matmuls of the forward's size; the
    frozen experts take no weight gradient)."""
    return (nograd_passes + 2 + (1 if remat else 0)) \
        * grouped_matmul_forward_flops(model, rows)


def kv_bytes_per_token(model, kv_bytes: int = 2) -> int:
    """What the paged pool keeps of a token across all layers: K and V."""
    m = _dims(model)
    return m["layers"] * 2 * m["dk"] * kv_bytes


def rolling_state_bytes_per_slot(model, state_bytes: int = 2) -> int:
    """The rolling state of one sequence across all layers: the last
    ``k0 - 1`` rows of the convolutions' input, the last ``k1 - 1`` of the
    first convolution's output, the previous token's value half."""
    m = _dims(model)
    c = m["dq"] + m["dk"]
    return m["layers"] * ((m["k0"] - 1 + m["k1"] - 1) * c + m["dk"] // 2) \
        * state_bytes


def decode_step_bytes(model, live_tokens: float, experts_hit: float,
                      slots: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """The least bytes one decode step over all slots has to move: every
    weight outside the experts once (projections and the head-wise
    convolution in the stored dtype, the depthwise taps and the router in
    float32, the tied head), the experts the step's rows really touched
    (``experts_hit``: distinct experts summed over the layers), the live
    K/V (``live_tokens`` summed over slots) and every slot's rolling state
    read and written."""
    m = _dims(model)
    conv = conv_params(model)
    fixed = (m["layers"] * ((cca_params(model) + conv["conv1"]) * weight_bytes
                            + (conv["conv0"] + router_params(model)) * 4)
             + m["d"] * m["vocab"] * weight_bytes)
    return float(fixed + experts_hit * expert_params(model) * weight_bytes
                 + live_tokens * kv_bytes_per_token(model, kv_bytes)
                 + 2 * slots * rolling_state_bytes_per_slot(model, kv_bytes))
