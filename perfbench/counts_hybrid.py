"""Operations and bytes a hybrid stack (state-space layers beside attention
layers) needs, from shapes: ``counts.py``'s functions for a configuration
whose layers are of two kinds. Kept with the benchmark so that no PR that
claims a gain can change the denominator.

A configuration here is a file under ``perfbench/configs/`` with Jamba's
published key names (``attn_layer_period``, ``mamba_d_state``, ...).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence


def _dims(model: Dict[str, Any]):
    d = int(model["hidden_size"])
    nh = int(model["num_attention_heads"])
    return {
        "d": d, "nh": nh, "nkv": int(model["num_key_value_heads"]),
        "hd": d // nh, "ff": int(model["intermediate_size"]),
        "vocab": int(model["vocab_size"]),
        "layers": int(model["num_hidden_layers"]),
        "di": int(model["mamba_expand"]) * d,
        "n": int(model["mamba_d_state"]), "k": int(model["mamba_d_conv"]),
        "r": int(model["mamba_dt_rank"]),
    }


def layer_counts(model) -> Dict[str, int]:
    """How many layers of each kind: layer i is an attention layer iff
    ``i % attn_layer_period == attn_layer_offset``."""
    period = int(model["attn_layer_period"])
    offset = int(model["attn_layer_offset"])
    attn = sum(i % period == offset
               for i in range(int(model["num_hidden_layers"])))
    return {"attn": attn, "mamba": int(model["num_hidden_layers"]) - attn}


def _lora_shapes(model):
    m = _dims(model)
    d, ff, di = m["d"], m["ff"], m["di"]
    ffn = {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    return {
        "attn": {"wq": (d, m["nh"] * m["hd"]), "wk": (d, m["nkv"] * m["hd"]),
                 "wv": (d, m["nkv"] * m["hd"]), "wo": (m["nh"] * m["hd"], d),
                 **ffn},
        "mamba": {"in_proj": (d, 2 * di), "x_proj": (di, m["r"] + 2 * m["n"]),
                  "out_proj": (di, d), **ffn},
    }


def layer_matmul_params(model) -> Dict[str, int]:
    """Weights of one layer of each kind that a token is multiplied by: the
    mixer's projections and the SwiGLU. The conv's taps, ``A_log``, ``D``
    and the norm scales are no matmuls."""
    m = _dims(model)
    d, ff, di = m["d"], m["ff"], m["di"]
    mlp = 3 * d * ff
    attn = d * m["nh"] * m["hd"] + 2 * d * m["nkv"] * m["hd"] \
        + m["nh"] * m["hd"] * d
    mamba = d * 2 * di + di * (m["r"] + 2 * m["n"]) + m["r"] * di + di * d
    return {"attn": attn + mlp, "mamba": mamba + mlp}


def matmul_params(model) -> int:
    """N: every weight a token is multiplied by, the tied head included,
    the embedding lookup not."""
    m = _dims(model)
    per, n = layer_matmul_params(model), layer_counts(model)
    return (n["attn"] * per["attn"] + n["mamba"] * per["mamba"]
            + m["d"] * m["vocab"])


def attention_forward_flops(model, lengths: Sequence[int]) -> float:
    """Causal softmax attention of the attention layers alone over rows of
    the given real lengths: QK^T and PV, half of the T x T square."""
    m = _dims(model)
    return float(layer_counts(model)["attn"] * sum(
        2 * 2 * m["nh"] * m["hd"] * t * t / 2 for t in lengths))


def selective_scan_flops(model, tokens: float) -> float:
    """Forward elementwise work of the recurrence in all state-space layers:
    a state element takes 7 operations a position (``dt * A``, its ``exp``
    counted as one, ``* h``, ``dtx * B``, the add, ``* C`` and the add of
    the reduction), a channel 3 more (``dt * x``, ``D * x``, its add)."""
    m = _dims(model)
    return float(layer_counts(model)["mamba"] * tokens
                 * (7 * m["di"] * m["n"] + 3 * m["di"]))


def selective_scan_bytes(model, tokens: float, act_bytes: int = 2) -> float:
    """The least bytes the forward scan of all state-space layers moves for
    ``tokens`` positions: ``x`` in and ``y`` out in the compute dtype, ``dt``
    in float32, ``B`` and ``C`` in float32. The state lives on the chip."""
    m = _dims(model)
    return float(layer_counts(model)["mamba"] * tokens
                 * (m["di"] * (2 * act_bytes + 4) + 2 * m["n"] * 4))


def lora_forward_flops_per_token(model, rank: int,
                                 targets: Sequence[str]) -> float:
    """A target counts in the layers whose kind has that projection."""
    shapes, n = _lora_shapes(model), layer_counts(model)
    return float(sum(
        n[kind] * 2 * (s[t][0] * rank + rank * s[t][1])
        for kind, s in shapes.items() for t in targets if t in s))


def grpo_learn_flops(model, lengths: Sequence[int], rank: int,
                     targets: Sequence[str], nograd_passes: int = 2) -> float:
    """``counts.grpo_learn_flops`` for the hybrid stack: per no-grad pass 2N
    a token, attention by the rows' real lengths, the scan's elementwise
    work and the adapters; the update 4N a token (the frozen base forms no
    weight gradients) and three times the forward of attention, scan and
    adapters. Remat's second forward is not counted."""
    tokens = float(sum(lengths))
    n = matmul_params(model)
    extra = (attention_forward_flops(model, lengths)
             + selective_scan_flops(model, tokens)
             + lora_forward_flops_per_token(model, rank, targets) * tokens)
    return nograd_passes * (2 * n * tokens + extra) + 4 * n * tokens + 3 * extra


def state_bytes_per_slot(model, act_bytes: int = 2) -> float:
    """Recurrent state of one sequence: per state-space layer the SSM state
    in float32 and the conv window in the compute dtype."""
    m = _dims(model)
    return float(layer_counts(model)["mamba"] * (
        m["di"] * m["n"] * 4 + (m["k"] - 1) * m["di"] * act_bytes))


def decode_step_bytes(model, live_kv_tokens: float, slots: int,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """The least bytes one decode step over all slots has to move: every
    matmul weight and the head once in the stored dtype, the live keys and
    values of the attention layers (``live_kv_tokens`` summed over slots),
    and every slot's recurrent state read and written once."""
    m = _dims(model)
    weights = matmul_params(model) * weight_bytes
    kv = layer_counts(model)["attn"] * 2 * m["nkv"] * m["hd"] \
        * live_kv_tokens * kv_bytes
    return float(weights + kv + 2 * slots * state_bytes_per_slot(model, kv_bytes))
