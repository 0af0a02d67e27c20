"""Operations and bytes the algorithm needs, from shapes. Kept with the
benchmark so that no PR that claims a gain can change the denominator.

A configuration here is the ``model`` group of a file under
``perfbench/configs/`` with the published key names (``hidden_size``,
``num_hidden_layers``, ...).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence


def _dims(model: Dict[str, Any]):
    d = int(model["hidden_size"])
    nh = int(model["num_attention_heads"])
    nkv = int(model["num_key_value_heads"])
    hd = d // nh
    return (d, nh, nkv, hd, int(model["intermediate_size"]),
            int(model["vocab_size"]), int(model["num_hidden_layers"]))


def layer_matmul_params(model) -> int:
    """Weights of one block that a token is multiplied by."""
    d, nh, nkv, hd, ff, _, _ = _dims(model)
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff


def matmul_params(model) -> int:
    """N: every weight a token is multiplied by, the head included, the
    embedding table not (a lookup is no matmul)."""
    d, _, _, _, _, vocab, layers = _dims(model)
    return layers * layer_matmul_params(model) + d * vocab


def attention_forward_flops(model, lengths: Sequence[int]) -> float:
    """Causal softmax attention over rows of the given real lengths: QK^T
    and PV, 2 FLOPs a multiply-add, half of the T x T square."""
    _, nh, _, hd, _, _, layers = _dims(model)
    return float(layers * sum(2 * 2 * nh * hd * t * t / 2 for t in lengths))


def lora_forward_flops_per_token(model, rank: int,
                                 targets: Sequence[str]) -> float:
    d, nh, nkv, hd, ff, _, layers = _dims(model)
    shapes = {"wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
              "wo": (nh * hd, d), "w_gate": (d, ff), "w_up": (d, ff),
              "w_down": (ff, d)}
    return float(layers * sum(
        2 * (shapes[t][0] * rank + rank * shapes[t][1]) for t in targets))


def grpo_learn_flops(model, lengths: Sequence[int], rank: int,
                     targets: Sequence[str], nograd_passes: int = 2) -> float:
    """Useful FLOPs of one ``GRPO.learn`` call with a frozen base and LoRA
    adapters: ``nograd_passes`` forward passes (2N a token, attention by the
    rows' real lengths) and one update (forward, and backward through the
    activations only: 4N a token; the base's weight gradients are never
    formed). Attention's backward is twice its forward; the adapters do
    forward, activation gradient and weight gradient, 3x their forward.
    Remat's second forward is not counted: it is the price of memory, not
    work the algorithm asks for."""
    tokens = float(sum(lengths))
    n = matmul_params(model)
    attn = attention_forward_flops(model, lengths)
    lora = lora_forward_flops_per_token(model, rank, targets) * tokens
    nograd = nograd_passes * (2 * n * tokens + attn + lora)
    update = 4 * n * tokens + 3 * attn + 3 * lora
    return nograd + update


def decode_step_bytes(model, live_kv_tokens: int,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """The least bytes one decode step over all slots has to read: every
    block's weights and the head once, in the compute dtype, and the live
    keys and values of every slot (``live_kv_tokens`` summed over slots)."""
    d, _, nkv, hd, _, vocab, layers = _dims(model)
    weights = (layers * layer_matmul_params(model) + d * vocab) * weight_bytes
    kv = layers * 2 * nkv * hd * live_kv_tokens * kv_bytes
    return float(weights + kv)


def stored_weight_bytes(model, bytes_per_weight: int = 4) -> float:
    """Bytes of the same weights as the program stores them (f32 masters)."""
    return float(matmul_params(model) * bytes_per_weight)
