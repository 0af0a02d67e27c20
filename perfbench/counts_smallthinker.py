"""Operations and bytes a SmallThinker-class stack needs, from shapes:
``counts.py``'s functions for grouped-query attention layers that are
GLOBAL or WINDOWED by ``sliding_window_layout`` and ReGLU experts of which a
token uses ``moe_num_active_primary_experts``. Kept with the benchmark so
that no PR that claims a gain can change the denominator. Everything counts
the PUBLISHED mathematics: queries ``num_attention_heads x head_dim`` wide,
keys and values ``num_key_value_heads x head_dim``, the router's one matrix,
the untied head once; attention by the query-key PAIRS a layer's mask
allows (``live_pairs``), never the square.

A configuration here is a file under ``perfbench/configs/`` with the
published ``smallthinker`` key names; its two layout lists may be longer
than ``num_hidden_layers`` (kept whole as published): the first
``num_hidden_layers`` entries are the stack.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple


def _dims(model: Dict[str, Any]):
    hd = int(model["head_dim"])
    layers = int(model["num_hidden_layers"])
    return {
        "d": int(model["hidden_size"]), "hd": hd,
        "dq": int(model["num_attention_heads"]) * hd,
        "dk": int(model["num_key_value_heads"]) * hd,
        "nh": int(model["num_attention_heads"]),
        "fe": int(model["moe_ffn_hidden_size"]),
        "experts": int(model["moe_num_primary_experts"]),
        "k": int(model["moe_num_active_primary_experts"]),
        "vocab": int(model["vocab_size"]),
        "window": int(model["sliding_window_size"]),
        "layers": layers,
        "window_layers": sum(
            int(x) for x in model["sliding_window_layout"][:layers]),
    }


def _attention_shapes(model):
    m = _dims(model)
    return {"wq": (m["d"], m["dq"]), "wk": (m["d"], m["dk"]),
            "wv": (m["d"], m["dk"]), "wo": (m["dq"], m["d"])}


def attention_params(model) -> int:
    """The four projections of an attention block (either kind)."""
    return sum(a * b for a, b in _attention_shapes(model).values())


def router_params(model) -> int:
    m = _dims(model)
    return m["d"] * m["experts"]


def expert_params(model) -> int:
    """One expert: a ReGLU hidden -> moe_ffn_hidden -> hidden."""
    m = _dims(model)
    return 3 * m["d"] * m["fe"]


def layer_params(model) -> int:
    """Every matrix of a layer: the projections, the router, ALL experts."""
    return (attention_params(model) + router_params(model)
            + _dims(model)["experts"] * expert_params(model))


def embedding_and_head_params(model) -> int:
    m = _dims(model)
    return 2 * m["vocab"] * m["d"]


def layer_active_params(model) -> int:
    """Weights of one layer that ONE token is multiplied by."""
    return (attention_params(model) + router_params(model)
            + _dims(model)["k"] * expert_params(model))


def active_matmul_params(model) -> int:
    """N active: every weight a token is multiplied by, the untied head
    included, the embedding lookup not."""
    m = _dims(model)
    return m["layers"] * layer_active_params(model) + m["d"] * m["vocab"]


def live_pairs(t: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask allows in a sequence of ``t``: the
    causal half ``t (t + 1) / 2``, or under a ``window`` ``sum_i min(i + 1,
    window)``."""
    if not window or t <= window:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def attention_forward_flops(model, lengths: Sequence[int]) -> float:
    """Softmax attention of all layers over rows of the given real lengths,
    by live pairs: QK^T and PV over ``head_dim`` (4 FLOPs a pair, a head and
    a dimension), global layers the causal half, window layers their band."""
    m = _dims(model)
    per_pair = 4 * m["nh"] * m["hd"]
    return float(sum(
        per_pair * ((m["layers"] - m["window_layers"]) * live_pairs(t)
                    + m["window_layers"] * live_pairs(t, m["window"]))
        for t in lengths))


def lora_forward_flops_per_token(model, rank: int,
                                 targets: Sequence[str]) -> float:
    shapes = _attention_shapes(model)
    return float(_dims(model)["layers"] * sum(
        2 * (shapes[t][0] * rank + rank * shapes[t][1]) for t in targets))


def grpo_learn_flops(model, lengths: Sequence[int], rank: int,
                     targets: Sequence[str], nograd_passes: int = 1) -> float:
    """``counts.grpo_learn_flops`` for this stack, ACTIVE parameters only:
    per no-grad pass 2 N_active a token, attention by live pairs over the
    rows' real lengths and the adapters; the update 4 N_active a token (the
    frozen base forms no weight gradients) and three times the forward of
    attention and adapters. Remat's second forward is not counted. ONE
    no-grad pass: a ``GRPO.learn`` call of one optimizer step runs the
    reference's alone (the ratio's anchor is the update's own
    log-probabilities, PR 34)."""
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


def learn_grouped_matmul_flops(model, rows: float, nograd_passes: int = 1,
                               remat: bool = True) -> float:
    """What the learn programs EXECUTE in grouped matmuls for one
    ``GRPO.learn`` call of ``rows`` positions: the reference pass's forward,
    the update's forward, remat's second forward, and the backward with
    respect to the rows (three grouped matmuls of the forward's size; the
    frozen experts take no weight gradient) — 4 forwards' worth."""
    return (nograd_passes + 2 + (1 if remat else 0)) \
        * grouped_matmul_forward_flops(model, rows)


#: FLOPs of one execution of a flash kernel a live pair, in units of a head
#: dimension's multiply-add pairs: (matmuls over d, matmuls over dv) —
#: ``_kernels.KERNEL_FLOPS``'s counts
_FLASH_MATMULS = {"flash_fwd": (1, 1), "flash_dq": (2, 1), "flash_dkv": (2, 2)}


def flash_execution_flops(model, stem: str, shape: Tuple[int, ...]) -> float:
    """FLOPs one execution of a flash kernel is credited with, from the
    kernel's name and its first result's shape ``[BH, Tp, .]``: 2 a
    multiply-add x the matmuls it forms x ``head_dim`` x the pairs the mask
    allows — a ``*_win`` execution its band (``sliding_window_size``), any
    other the causal half."""
    m = _dims(model)
    windowed = stem.endswith("_win")
    qk, v = _FLASH_MATMULS[stem[:-4] if windowed else stem]
    bh, tp = shape[:2]
    pairs = live_pairs(tp, m["window"] if windowed else 0)
    return 2.0 * bh * pairs * (qk + v) * m["hd"]
