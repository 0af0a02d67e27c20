"""Decoder-only transformer (Llama-class: RMSNorm, RoPE, SwiGLU, GQA) as pure
init/apply over dict params, with LoRA adapter subtrees and a fixed-size KV
cache for jitted decoding.

This is the in-tree replacement for the reference's HF-model + PEFT + vLLM stack
(agilerl/algorithms/core/base.py:1894 LLMAlgorithm — LoRA adapters :2041,
adapter-swap reference policy :2755, vLLM colocate generation :3101): training
and sampling share ONE sharded param tree, so there is no weight hot-swap and no
external engine. bfloat16 compute on the MXU; float32 params/logits.

Sharding contract (see parallel/mesh.py): attention/MLP kernels are annotated
with logical axes ("embed", "heads"/"mlp") so GSPMD shards them over ("fsdp",
"tp") mesh axes with no code change here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu.observability.timeline import device_scope

Params = Any

#: the two parts of a layer that ``forward_paged`` names (docs/observability.md,
#: "Device scopes"); attention has ``paged/attend`` and the mixers their own
PROJ_SCOPE = "decode/proj"
FFN_SCOPE = "decode/ffn"


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int
    n_layer: int = 4
    n_head: int = 4
    n_kv_head: Optional[int] = None  # grouped-query attention; None -> n_head
    d_model: int = 256
    d_ff: Optional[int] = None  # None -> 4 * d_model (SwiGLU sized 2/3)
    max_seq_len: int = 1024
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    qkv_bias: bool = False  # Qwen2-style attention biases
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False  # jax.checkpoint each block (HBM <-> FLOPs trade)
    # Roll every run of structurally equal layers into ONE lax.scan on every
    # path (learn and cached): compile time ~constant in n_layer (an unrolled
    # 12-layer learn step compiled >15 min). False: each layer called directly.
    scan_layers: bool = True
    use_flash_attention: bool = False  # Pallas kernel on the non-cached path
    # ((batch axes...), (head axes...)): the flash kernel in a shard_map over
    # the active mesh (Mosaic kernels are not GSPMD-partitioned). None = plain.
    flash_shard_axes: Any = None
    # (batch axes...) for the fused lm-head loss: rows shard, the head stays
    # replicated (fsdp meshes; on tp pods the chunked XLA loss is the route).
    fused_loss_shard_axes: Any = None
    # Mixture-of-Experts (beyond reference parity — completes the ep axis of
    # the dp/fsdp/tp/sp/ep strategy menu, SURVEY.md §2.8):
    n_experts: int = 0  # 0 = dense FFN everywhere
    expert_top_k: int = 2
    # a capacity states the Switch-style bucketed dispatch, which drops what
    # overflows a bucket; None = the dropless dispatch (llm/moe.py)
    capacity_factor: Optional[float] = 1.25
    moe_every: int = 1  # layer i is MoE iff (i + 1) % moe_every == 0
    router_aux_weight: float = 0.01
    # Positional encoding of the attention layers: rotary, or none at all
    # (a hybrid stack's state-space layers carry position).
    rope: bool = True
    # Hybrid stack: layer i is ATTENTION iff i % attn_layer_period ==
    # attn_layer_offset, else a Mamba-1 layer (llm/ssm.py); period 1 = uniform.
    attn_layer_period: int = 1
    attn_layer_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None  # None -> ceil(d_model / 16)
    # Latent attention (llm/mla.py) in every attention layer iff
    # kv_lora_rank > 0: a cache keeps the latent alone (rotary on qk_rope_dim).
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # Expert stacks beyond "every moe_every-th layer": the first
    # n_dense_layers layers keep a dense FFN (d_ff wide) whatever moe_every
    # says; the experts are d_ff_expert wide (None -> d_ff); the dropless
    # dispatch's router scores by softmax or sigmoid, may add a frozen
    # selection bias to the scores for the CHOICE only, renormalises the
    # chosen weights (norm_topk) and scales them (routed_scale); an
    # always-on shared expert d_ff_shared wide runs beside the routed ones.
    n_dense_layers: int = 0
    d_ff_expert: Optional[int] = None
    d_ff_shared: int = 0
    router_score: str = "softmax"
    router_bias: bool = False
    norm_topk: bool = True
    routed_scale: float = 1.0
    # The head size where the model states one (None -> d_model // n_head):
    # attention may run narrower or wider than the residual stream.
    head_size: Optional[int] = None
    # Compressed convolutional attention (llm/cca.py) in every attention
    # layer iff cca_time0 > 0: the kernel sizes of the depthwise and of the
    # head-wise causal convolution over the query/key latents. Such a layer
    # keeps a rolling per-sequence state beside its K/V. What follows is
    # read by a CCA stack's layers alone: the share of a head that rotary
    # turns; a router that is an MLP router_hidden wide over a state carried
    # from layer to layer (llm/moe.mlp_logits; 0 = x @ router); a learned
    # per-channel scale and bias on both arms of every residual merge.
    cca_time0: int = 0
    cca_time1: int = 0
    rotary_share: float = 1.0
    router_hidden: int = 0
    scaled_merge: bool = False
    # Window and full attention mixed, and positions by layer. A layer i
    # with window_layout[i] (every layer where the layout is None) sees the
    # last sliding_window positions only: the flash kernels, the cached and
    # the paged attention skip what lies behind the window. rope_layout[i]
    # says whether layer i's q and k get rotary positions (None: ``rope`` in
    # every layer). The two lists are two published keys and stay two
    # fields. Layers of one run that differ in either are scanned a PERIOD
    # of the pattern at a time (_run_layers), their kinds static in the body.
    sliding_window: int = 0
    window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    # The experts' gate activation ("silu": SwiGLU; "relu": ReGLU) and what
    # the router reads: the FFN's normed input, or ("attn") the normed input
    # of the layer's ATTENTION block — a router placed before attention,
    # whose logits ride past it. Read by the dropless dispatch alone.
    expert_act: str = "silu"
    router_input: str = "ffn"
    # Single-block layers named by a pattern (NemotronH's
    # hybrid_override_pattern, llm/nemotron_h.py): layer i is x + block(
    # rmsnorm(x)), block "M" a Mamba-2 mixer (llm/ssm2.py: mamba_heads of
    # mamba_head_dim, mamba_groups groups of B/C, state mamba_d_state, conv
    # mamba_d_conv, SSD chunks of mamba_chunk), "E" the dropless expert FFN
    # (experts in a moe_latent-wide latent; this chip holds experts_held =
    # (first id, count) of the router's n_experts), "*" attention.
    layer_pattern: Optional[str] = None
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_groups: int = 1
    mamba_chunk: int = 128
    moe_latent: int = 0
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        routed = (self.router_score != "softmax" or self.router_bias
                  or not self.norm_topk or self.routed_scale != 1.0
                  or self.d_ff_shared)
        if self.n_experts and self.capacity_factor is not None and routed:
            raise ValueError(
                "the capacity dispatch (moe.moe_ffn) scores by softmax, "
                "renormalises and has no shared expert, selection bias or "
                "scale; state capacity_factor=None for the dropless dispatch")
        if self.is_mla and (self.is_hybrid or not self.rope or self.qkv_bias):
            raise ValueError(
                "latent attention is built for a stack of attention layers "
                "with rotary positions and no projection bias")
        if self.is_cca and (
                self.is_mla or self.is_hybrid or self.qkv_bias or not self.rope
                or self.cca_time1 < 1 or self.kv_heads % 2
                or (self.n_experts and self.capacity_factor is not None)):
            raise ValueError(
                "compressed convolutional attention is built for a stack of "
                "attention layers with rotary positions, an even number of "
                "key heads, two convolutions, no projection bias, no latent "
                "cache, no state-space layers and the dropless dispatch")
        if not self.is_cca and (self.rotary_share != 1.0 or self.router_hidden
                                or self.scaled_merge):
            raise ValueError(
                "rotary_share, router_hidden and scaled_merge are read by a "
                "CCA stack's layers alone (cca_time0 > 0)")
        if self.router_hidden and not self.is_dropless:
            raise ValueError(
                "a router MLP scores for the dropless dispatch: state "
                "n_experts and capacity_factor=None")
        for name in ("window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout is not None and (not isinstance(layout, tuple)
                                       or len(layout) != self.n_layer):
                raise ValueError(
                    f"{name} is a tuple with one entry a layer "
                    f"({self.n_layer}), got {layout!r}")
        if self.window_layout is not None and self.sliding_window <= 0:
            raise ValueError("window_layout names window layers: state "
                             "sliding_window, their window")
        _check_pattern(self)
        if self.varies and (self.is_mla or self.is_cca or self.is_hybrid):
            raise ValueError(
                "a sliding window and positions by layer (sliding_window, "
                "window_layout, rope_layout) are built for a stack of "
                "grouped-query attention layers: no latent cache, no CCA, "
                "no state-space layers")
        if self.expert_act not in ("silu", "relu", "relu2") \
                or self.router_input not in ("ffn", "attn"):
            raise ValueError(
                f"expert_act {self.expert_act!r} is 'silu' or 'relu', "
                f"router_input {self.router_input!r} 'ffn' or 'attn'")
        if (self.expert_act != "silu" or self.router_input != "ffn") and (
                not self.is_dropless or self.router_hidden or self.is_mla
                or self.is_hybrid):
            raise ValueError(
                "ReGLU experts and a router that reads the attention "
                "block's input are the dropless dispatch's (n_experts, "
                "capacity_factor=None), over grouped-query attention "
                "layers and a linear router")

    def is_moe_layer(self, i: int) -> bool:
        return (self.n_experts > 0 and i >= self.n_dense_layers
                and (i + 1) % self.moe_every == 0
                and not (self.layer_pattern and self.layer_pattern[i] != "E"))

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_cca(self) -> bool:
        return self.cca_time0 > 0

    @property
    def state_kind(self) -> Optional[str]:
        """The kind of layer that keeps a fixed-size state a sequence beside
        (or in place of) paged K/V: the state-space layers of a hybrid
        stack, the attention layers of a CCA stack; None = no such layer."""
        if self.is_hybrid:
            return "mamba"
        if self.layer_pattern is not None:
            return "mamba2"
        return "attn" if self.is_cca else None

    @property
    def is_dropless(self) -> bool:
        return self.n_experts > 0 and self.capacity_factor is None

    @property
    def n_moe_layers(self) -> int:
        return sum(self.is_moe_layer(i) for i in range(self.n_layer))

    @property
    def stores_runs(self) -> bool:
        """Weights are kept one stacked tree a RUN of equal layers
        (``params["runs"]``), the layout the programs scan, where stacking
        per-layer trees inside every program would cost a copy of the base:
        hybrid stacks, latent attention, CCA, dense-then-expert stacks and
        stacks whose layers have variants (there a run of period P > 1 is P
        trees, one a position in the period: ``stack_run``)."""
        return (self.is_hybrid or self.is_mla or self.is_cca
                or self.n_dense_layers > 0 or self.varies
                or self.layer_pattern is not None)

    @property
    def ff_expert(self) -> int:
        return self.d_ff_expert or self.ff_dim

    @property
    def is_hybrid(self) -> bool:
        return self.attn_layer_period > 1

    def layer_kind(self, i: int) -> str:
        if self.layer_pattern is not None:
            return PATTERN_KINDS[self.layer_pattern[i]]
        if i % self.attn_layer_period == self.attn_layer_offset:
            return "attn"
        return "mamba"

    @property
    def varies(self) -> bool:
        """The stack states attention by layer (a window, positions by
        layer): its layers have VARIANTS (``layer_variant``)."""
        return self.sliding_window > 0 or self.rope_layout is not None

    def layer_variant(self, i: int) -> Tuple[int, bool]:
        """(window of layer i's attention, 0 = full; whether its q and k
        get rotary positions): what a layer's program is static in beyond
        its kind."""
        windowed = self.sliding_window > 0 and (
            self.window_layout is None or bool(self.window_layout[i]))
        rope = self.rope if self.rope_layout is None \
            else bool(self.rope_layout[i])
        return (self.sliding_window if windowed else 0, rope)

    @property
    def variants(self):
        """The distinct ``layer_variant``s of the stack."""
        return {self.layer_variant(i) for i in range(self.n_layer)}

    @property
    def n_window_layers(self) -> int:
        return sum(self.layer_variant(i)[0] > 0 for i in range(self.n_layer))

    def run_period(self, first: int, n: int) -> int:
        """The shortest period of the variants' pattern over the run of
        ``n`` layers from ``first`` that divides n: 1 for equal layers, 4
        for [full, win, win, win] x m, n where the pattern has none. One
        ``lax.scan`` body holds one period."""
        variants = [self.layer_variant(first + j) for j in range(n)]
        return next(p for p in range(1, n + 1) if n % p == 0 and all(
            variants[j] == variants[j % p] for j in range(n)))

    def layer_runs(self):
        """[(kind, first layer, number of layers)]: the maximal runs of
        consecutive structurally equal layers — one kind of mixer AND one
        kind of FFN, so an interleaved dense / expert stack (moe_every > 1)
        is runs of one layer. A layer's VARIANT (window, positions) does not
        end a run: a run whose variants repeat is scanned a period at a
        time (``run_period``)."""
        runs, last = [], None
        for i in range(self.n_layer):
            kind = self.layer_kind(i)
            structure = (kind, self.is_moe_layer(i))
            if structure == last:
                runs[-1][2] += 1
            else:
                runs.append([kind, i, 1])
            last = structure
        return [tuple(r) for r in runs]

    def n_layers_of(self, kind: str) -> int:
        return sum(self.layer_kind(i) == kind for i in range(self.n_layer))

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.d_model // 16)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_head

    @property
    def ff_dim(self) -> int:
        return self.d_ff or int(8 * self.d_model / 3 + 127) // 128 * 128


class KVCache(NamedTuple):
    """All layers' KV cache, stacked on a leading layer axis.

    ``length``/``mask`` are layer-invariant (every layer appends the same
    tokens at the same slots), so they are stored ONCE — which is also what
    lets the cached forward roll the layer stack into ``lax.scan`` with
    (k[i], v[i]) as scan xs/ys: decode/prefill compile time is constant in
    depth, like the non-cached paths (window-2 finding: the unrolled
    12-layer cached prefill was the repo's last depth-linear program)."""

    # latent attention (GPTConfig.is_mla): k is [L, B, S, latent width],
    # what llm/mla.py keeps of a token, and v is None
    k: jax.Array  # [L, B, S, KV, hd]
    v: jax.Array  # [L, B, S, KV, hd]
    length: jax.Array  # [] int32 — filled slots
    mask: jax.Array  # [B, S] int32 — 1 where the slot holds a REAL token
    # (left-padded prompts leave dead slots that must stay masked forever)
    # Hybrid stacks only (k/v then hold the ATTENTION layers alone): the
    # state-space layers' recurrent state after the last token, and — where
    # a multi-token forward just ran — before it (llm/ssm.py):
    # one (conv [n, B, k-1, d_inner], ssm [n, B, d_state, d_inner] float32)
    # a run of n state-space layers — a run's scan reads and writes its own
    # arrays whole, with no slicing or joining of one big array.
    # CCA stacks (k/v hold every layer, as ever): the attention layers'
    # rolling state (llm/cca.py), one (p window, c0 window, previous value)
    # a run of layers, under the same two names
    state: Any = None
    prev_state: Any = None


def _token_shape(config: GPTConfig):
    """(heads, width) of what a cache keeps of one token in one attention
    layer, and whether values have an array of their own."""
    if config.is_mla:
        # no head axis: [.., block_size, width] tiles whole, where a
        # second-minor axis of 1 would be padded to a tile's 16 rows
        return (config.kv_lora_rank + config.qk_rope_dim,), False
    return (config.kv_heads, config.head_dim), True


def _init_state(config: GPTConfig, batch: int):
    """Zero state of the layers that keep one (``config.state_kind``): one
    entry a run of such layers, ``batch`` sequences each; None for a stack
    without them."""
    if config.state_kind is None:
        return None
    from agilerl_tpu.llm import cca, ssm, ssm2
    mixer = {"mamba": ssm, "attn": cca, "mamba2": ssm2}[config.state_kind]
    return tuple(mixer.init_state(config, n, batch)
                 for kind, _, n in config.layer_runs()
                 if kind == config.state_kind)


def init_kv_cache(config: GPTConfig, batch: int, max_len: Optional[int] = None) -> KVCache:
    s = max_len or config.max_seq_len
    per_token, has_v = _token_shape(config)
    shape = (config.n_layers_of("attn"), batch, s, *per_token)
    return KVCache(
        k=jnp.zeros(shape, config.dtype),
        v=jnp.zeros(shape, config.dtype) if has_v else None,
        length=jnp.zeros((), jnp.int32),
        mask=jnp.zeros((batch, s), jnp.int32),
        state=_init_state(config, batch),
    )


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.float32)


def init_block(key: jax.Array, config: GPTConfig, i: int) -> Params:
    """Layer ``i``'s weights from its own key (``init_params`` hands layer i
    the key ``split(key, n_layer + 3)[i + 1]``): what makes a base run by
    run, or layer by layer, draw the numbers ``init_params`` draws."""
    d, hd = config.d_model, config.head_dim
    nh, nkv, f = config.n_head, config.kv_heads, config.ff_dim
    std = 0.02
    out_std = std / math.sqrt(2 * config.n_layer)
    if config.layer_pattern is not None:
        return _pattern().init_block(key, config, i, out_std)
    ks = jax.random.split(key, 8)
    if config.layer_kind(i) == "mamba":
        from agilerl_tpu.llm import ssm

        blk = {"ln1": jnp.ones((d,), jnp.float32),
               **ssm.init_mamba_mixer(ks[0], config, out_std),
               "ln2": jnp.ones((d,), jnp.float32)}
    elif config.is_mla:
        from agilerl_tpu.llm import mla

        blk = {"ln1": jnp.ones((d,), jnp.float32),
               **mla.init_mla_mixer(ks[0], config, out_std),
               "ln2": jnp.ones((d,), jnp.float32)}
    elif config.is_cca:
        from agilerl_tpu.llm import cca

        blk = {"ln1": jnp.ones((d,), jnp.float32),
               **cca.init_cca_mixer(ks[0], config, out_std),
               "ln2": jnp.ones((d,), jnp.float32)}
    else:
        blk = {
            "ln1": jnp.ones((d,), jnp.float32),
            "wq": _normal(ks[0], (d, nh * hd), std),
            "wk": _normal(ks[1], (d, nkv * hd), std),
            "wv": _normal(ks[2], (d, nkv * hd), std),
            "wo": _normal(ks[3], (nh * hd, d), out_std),
            "ln2": jnp.ones((d,), jnp.float32),
        }
    if config.is_moe_layer(i):
        E, f = config.n_experts, config.ff_expert
        if config.router_hidden:
            from agilerl_tpu.llm import moe

            blk.update(moe.init_router_mlp(ks[7], d, config.router_hidden, E))
        else:
            blk["router"] = _normal(ks[7], (d, E), std)
        blk["w_gate"] = _normal(ks[4], (E, d, f), std)
        blk["w_up"] = _normal(ks[5], (E, d, f), std)
        blk["w_down"] = _normal(ks[6], (E, f, d), out_std)
        extra = jax.random.split(jax.random.fold_in(key, 8), 4)
        if config.router_bias:
            # drawn, not zero: a zero bias moves no choice and checks nothing
            blk["router_bias"] = _normal(extra[0], (E,), std)
        if config.d_ff_shared:
            fs = config.d_ff_shared
            blk["ws_gate"] = _normal(extra[1], (d, fs), std)
            blk["ws_up"] = _normal(extra[2], (d, fs), std)
            blk["ws_down"] = _normal(extra[3], (fs, d), out_std)
    else:
        blk["w_gate"] = _normal(ks[4], (d, f), std)
        blk["w_up"] = _normal(ks[5], (d, f), std)
        blk["w_down"] = _normal(ks[6], (f, d), out_std)
    if config.qkv_bias and "wq" in blk:
        blk["bq"] = jnp.zeros((nh * hd,), jnp.float32)
        blk["bk"] = jnp.zeros((nkv * hd,), jnp.float32)
        blk["bv"] = jnp.zeros((nkv * hd,), jnp.float32)
    if config.scaled_merge:
        # rows: residual scale, residual bias, sublayer scale, sublayer
        # bias. Scales drawn around 1 and biases around 0, not AT them: a
        # unit scale or a zero bias checks nothing. The biases are small
        # beside the embedding's 0.02: they add up over 4 arms a layer, and
        # a stream they dominate routes every token alike
        for j, name in enumerate(("merge1", "merge2")):
            noise = _normal(jax.random.fold_in(key, 9 + j), (4, d), 1.0)
            blk[name] = (noise * jnp.asarray([0.05, 0.002, 0.05, 0.002])[:, None]
                         + jnp.asarray([1.0, 0.0, 1.0, 0.0])[:, None])
    return blk


def stack_run(blocks, period: int = 1):
    """A run's per-layer trees in the layout its scan reads: one tree
    stacked over the layers — or, where the run's variants repeat with a
    ``period`` > 1, a list of ``period`` trees, tree p stacked over the
    layers at position p of every period (layer ``j * period + p`` of the
    run is row j of tree p), so that the period scan slices each as xs."""
    stack = lambda *xs: jnp.stack(xs)  # noqa: E731
    if period == 1:
        return jax.tree_util.tree_map(stack, *blocks)
    return [jax.tree_util.tree_map(stack, *blocks[p::period])
            for p in range(period)]


def run_layer(run, j: int):
    """Layer j's tree out of a run stored as ``stack_run`` stores it."""
    if isinstance(run, (list, tuple)):
        run, j = run[j % len(run)], j // len(run)
    return jax.tree_util.tree_map(lambda a: a[j], run)


def run_length(run) -> int:
    """Layers in a run stored as ``stack_run`` stores it."""
    trees = run if isinstance(run, (list, tuple)) else [run]
    return sum(jax.tree_util.tree_leaves(t)[0].shape[0] for t in trees)


def init_params(key: jax.Array, config: GPTConfig) -> Params:
    """A uniform stack keeps one tree a layer (``params["blocks"][str(i)]``).
    A hybrid stack's weights (and those of every ``config.stores_runs``
    stack) are stored in the layout its programs scan: one
    stacked tree a RUN of equal layers (``params["runs"][r]``, leading axis =
    the run's layers, ``config.layer_runs()``'s order) — stacking per-layer
    trees inside a program would cost a copy of the base in every one. A
    run whose layers' variants repeat with a period is ``stack_run``'s list
    of one tree a position in the period."""
    d = config.d_model
    std = 0.02
    keys = jax.random.split(key, config.n_layer + 3)
    blocks = [init_block(keys[i + 1], config, i)
              for i in range(config.n_layer)]
    params: Dict = {
        "tok_emb": _normal(keys[0], (config.vocab_size, d), std),
        "ln_f": jnp.ones((d,), jnp.float32),
    }
    if config.stores_runs:
        params["runs"] = [
            stack_run(blocks[first:first + n], config.run_period(first, n))
            for _, first, n in config.layer_runs()]
    else:
        params["blocks"] = {str(i): blk for i, blk in enumerate(blocks)}
    if not config.tie_embeddings:
        params["lm_head"] = _normal(keys[-1], (d, config.vocab_size), std)
    return params


# --------------------------------------------------------------------------- #
# LoRA
# --------------------------------------------------------------------------- #

LORA_TARGETS = ("wq", "wk", "wv", "wo")


def init_lora(
    key: jax.Array, config: GPTConfig, rank: int = 8, targets: Tuple[str, ...] = ("wq", "wv")
) -> Params:
    """LoRA adapter subtree mirroring blocks (parity: the reference's auto LoRA
    config, core/base.py:2041). B is zero-init so the adapter starts as a no-op."""
    if config.layer_pattern is not None:
        return _pattern().init_lora(key, config, rank, targets)
    d, hd = config.d_model, config.head_dim
    dims = {
        "wq": (d, config.n_head * hd),
        "wk": (d, config.kv_heads * hd),
        "wv": (d, config.kv_heads * hd),
        "wo": (config.n_head * hd, d),
        "w_gate": (d, config.ff_dim),
        "w_up": (d, config.ff_dim),
        "w_down": (config.ff_dim, d),
    }
    ffn_names = ("w_gate", "w_up", "w_down")
    attention = LORA_TARGETS
    if config.is_mla:
        # latent attention has its own projections (no wk / wv) and, in the
        # stacks that exist, expert layers: no FFN target names anything
        from agilerl_tpu.llm import mla

        dims = mla.mla_dims(config)
        attention = tuple(dims)
    if config.is_cca:
        from agilerl_tpu.llm import cca

        dims = cca.cca_dims(config)
        attention = tuple(dims)
    if config.n_experts > 0 and any(t in ffn_names for t in targets):
        # MoE FFN weights are expert-stacked [E, ...]; the dense-shaped
        # adapters below would silently never be consulted by the MoE branch
        # of forward (review finding) — refuse loudly instead.
        raise ValueError(
            "LoRA on FFN projections is not supported for MoE layers; "
            f"restrict targets to attention projections {attention}"
        )
    target_ids = {name: idx for idx, name in enumerate(sorted(dims))}
    by_kind = {"attn": dims}
    if config.is_hybrid:
        # a state-space layer takes adapters on its own projections (and on
        # the FFN both kinds share); an attention target names nothing in it
        from agilerl_tpu.llm import ssm

        mamba_dims = ssm.mamba_lora_dims(config)
        target_ids.update({name: len(dims) + idx
                           for idx, name in enumerate(sorted(mamba_dims))})
        by_kind["mamba"] = {**mamba_dims, **{n: dims[n] for n in ffn_names}}
    unknown = [t for t in targets if t not in target_ids]
    if unknown:
        raise ValueError(
            f"LoRA targets {unknown} name no projection of this stack; it "
            f"has {sorted(target_ids)}")
    lora: Dict = {"blocks": {}}
    for i in range(config.n_layer):
        k = jax.random.fold_in(key, i)
        layer = {}
        layer_dims = by_kind[config.layer_kind(i)]
        for t in targets:
            if t not in layer_dims:
                continue
            # fixed per-name fold (NOT hash(): salted per process, which would
            # desync adapter init across hosts — review finding)
            ka = jax.random.fold_in(k, target_ids[t])
            din, dout = layer_dims[t]
            layer[t] = {
                "A": _normal(ka, (din, rank), 0.02),
                "B": jnp.zeros((rank, dout), jnp.float32),
            }
        lora["blocks"][str(i)] = layer
    return lora


def _maybe_lora(x, w, lora_layer, name, scale, dtype):
    y = x @ w.astype(dtype)
    if lora_layer is not None and name in lora_layer:
        a = lora_layer[name]["A"].astype(dtype)
        b = lora_layer[name]["B"].astype(dtype)
        y = y + ((x @ a) @ b) * scale
    return y


def merge_lora(params: Params, lora: Params, scale: float = 2.0) -> Params:
    """Fold the adapter into the base weights (used for export; training never
    needs it — parity contrast: the reference must merge before every vLLM
    weight swap, core/base.py:2772)."""
    out = jax.tree_util.tree_map(lambda x: x, params)
    if "runs" in params:
        # a hybrid stack: layer i is row i - first of its run's stacked tree
        # (of the tree at its position in the period where the run has one)
        first = 0
        for run in out["runs"]:
            trees = run if isinstance(run, (list, tuple)) else [run]
            n = run_length(run)
            for j in range(n):
                tree, row = trees[j % len(trees)], j // len(trees)
                for t, ab in lora["blocks"].get(str(first + j), {}).items():
                    tree[t] = tree[t].at[row].add((ab["A"] @ ab["B"]) * scale)
            first += n
        return out
    for i, layer in lora["blocks"].items():
        for t, ab in layer.items():
            out["blocks"][i][t] = params["blocks"][i][t] + (ab["A"] @ ab["B"]) * scale
    return out


# --------------------------------------------------------------------------- #
# Apply
# --------------------------------------------------------------------------- #


def _rms(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, T, H, hd]; positions: [B, T]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, hd/2]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _qkv_rope(config: GPTConfig, blk, x, positions, lora_layer, lora_scale,
              rope=None):
    """Shared q/k/v projection + bias + head split + RoPE. ONE home for the
    projection maths so the dense cached path (forward) and the paged decode
    path (forward_paged) cannot drift — the paged serving tier's greedy
    bit-parity guarantee rests on both paths running these exact ops.
    ``rope``: this layer's (``GPTConfig.layer_variant``); None = the
    stack's."""
    B, T = x.shape[:2]
    dtype = x.dtype
    q = _maybe_lora(x, blk["wq"], lora_layer, "wq", lora_scale, dtype)
    k = _maybe_lora(x, blk["wk"], lora_layer, "wk", lora_scale, dtype)
    v = _maybe_lora(x, blk["wv"], lora_layer, "wv", lora_scale, dtype)
    if config.qkv_bias:
        q = q + blk["bq"].astype(dtype)
        k = k + blk["bk"].astype(dtype)
        v = v + blk["bv"].astype(dtype)
    q = q.reshape(B, T, config.n_head, config.head_dim)
    k = k.reshape(B, T, config.kv_heads, config.head_dim)
    v = v.reshape(B, T, config.kv_heads, config.head_dim)
    if config.rope if rope is None else rope:
        q = _rope(q, positions, config.rope_theta)
        k = _rope(k, positions, config.rope_theta)
    return q, k, v


#: a learn-path attention block of a stack whose layers have variants, by
#: kind (docs/observability.md): norm, projections, attention, ``wo``, merge
WINDOW_SCOPE = "attn/win"
FULL_SCOPE = "attn/full"


def _attn_scope(config: GPTConfig, window: int, cached: bool):
    """The scope of a learn-path attention block where the stack mixes
    kinds of attention; no scope on any other stack or cached forward."""
    if not config.varies or cached:
        return contextlib.nullcontext()
    return jax.named_scope(WINDOW_SCOPE if window else FULL_SCOPE)


def _early_router_logits(config: GPTConfig, blk, h):
    """A router placed before attention (``router_input="attn"``): its
    logits from the attention block's NORMED input, in float32 from the
    residual stream ``h`` [B, T, d] on — the norm is taken again here
    without the rounding to the stream's type that ``_rms`` ends with, so
    a choice of experts turns on the stream's own rounding alone (in the
    first layer, whose input is a stored embedding, on none) —, under
    ``moe/score``: what rides past attention to ``_block_ffn``. None on
    any other stack or layer."""
    if config.router_input != "attn" or "router" not in blk:
        return None
    from agilerl_tpu.llm import moe

    with jax.named_scope(moe.SCORE_SCOPE):
        x = h.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + config.rms_eps) \
            * blk["ln1"].astype(jnp.float32)
        return moe.linear_logits(x.reshape(-1, config.d_model), blk["router"])


def _merge(blk, name, h, y):
    """The residual merge ``h + y``; where the block has the leaf ``name``
    (``GPTConfig.scaled_merge``: rows residual scale, residual bias,
    sublayer scale, sublayer bias) ``(s_r h + b_r) + (s_y y + b_y)``, in
    float32."""
    if name not in blk:
        return h + y
    m = blk[name].astype(jnp.float32)
    return ((m[0] * h + m[1]) + (m[2] * y + m[3])).astype(h.dtype)


def _stream(config: GPTConfig, stream):
    """(h, router state) of what the layer loop carries: the residual
    stream alone, or with ``GPTConfig.router_hidden`` the pair."""
    return stream if config.router_hidden else (stream, None)


def _restream(config: GPTConfig, h, s):
    """The inverse of ``_stream``."""
    return (h, s) if config.router_hidden else h


def _stream_in(config: GPTConfig, h):
    """What enters the first layer: the router state starts at zero, so the
    first layer's mix with "the previous layer's" adds nothing."""
    return _restream(config, h, jnp.zeros(
        (*h.shape[:2], config.router_hidden), jnp.float32))


def _block_ffn(config: GPTConfig, blk, stream, lora_layer, lora_scale,
               logits=None):
    """Post-attention half of a block: RMSNorm + (MoE | SwiGLU) FFN with the
    residual merge. ``stream`` is what ``_run_layers`` carries (``_stream``);
    returns (stream out, aux). Shared between forward's block_fn
    and forward_paged (same no-drift contract as _qkv_rope). ``logits``:
    the router's where it reads the attention block's input
    (``_early_router_logits``)."""
    h, s = _stream(config, stream)
    B, T = h.shape[:2]
    dtype = h.dtype
    x = _rms(h, blk["ln2"], config.rms_eps)
    if "router" in blk and config.is_dropless:
        from agilerl_tpu.llm import moe

        x2d = x.reshape(B * T, config.d_model)
        if config.router_input == "attn" and logits is None:
            raise ValueError(
                "this stack's router reads the attention block's input: "
                "the caller carries its logits past attention "
                "(_early_router_logits)")
        if config.router_hidden:
            logits, s = moe.mlp_logits(x2d, blk, s.reshape(B * T, -1),
                                       config.rms_eps)
            s = s.reshape(B, T, -1)
        out2d, load = moe.dropless_ffn(
            x2d, blk, top_k=config.expert_top_k, score=config.router_score,
            norm_topk=config.norm_topk, scale=config.routed_scale,
            logits=logits, act=config.expert_act)
        # what rides the aux channel of a dropless layer is its load, as
        # [fullest expert's rows over the mean, experts with any row]: no
        # auxiliary loss exists here (the router is frozen with the base)
        aux = jnp.stack([load.max() / jnp.maximum(load.mean(), 1.0),
                         (load > 0).sum()]).astype(jnp.float32)
        h = _merge(blk, "merge2", h, out2d.reshape(B, T, config.d_model))
        return _restream(config, h, s), aux
    if "router" in blk:
        from agilerl_tpu.llm.moe import moe_ffn

        out2d, aux = moe_ffn(
            x.reshape(B * T, config.d_model),
            blk["router"], blk["w_gate"], blk["w_up"], blk["w_down"],
            top_k=config.expert_top_k,
            capacity_factor=config.capacity_factor,
        )
        return h + out2d.reshape(B, T, config.d_model), aux
    gate = _maybe_lora(x, blk["w_gate"], lora_layer, "w_gate", lora_scale, dtype)
    up = _maybe_lora(x, blk["w_up"], lora_layer, "w_up", lora_scale, dtype)
    down = _maybe_lora(
        jax.nn.silu(gate) * up, blk["w_down"], lora_layer, "w_down", lora_scale, dtype
    )
    return (_restream(config, _merge(blk, "merge2", h, down), s),
            jnp.zeros(_aux_shape(config), jnp.float32))


def _aux_shape(config: GPTConfig):
    """A layer's aux: the capacity dispatch's balance loss (a scalar), or a
    dropless layer's [load max over mean, experts hit(, held pairs)]."""
    return (3 if config.layer_pattern else 2,) if config.is_dropless else ()


def _split_by_runs(config: GPTConfig, kind: str, tree):
    """A tree stacked over all layers of ``kind`` -> one slice a run."""
    out, off = [], 0
    for k, _, n in config.layer_runs():
        if k == kind:
            out.append(jax.tree_util.tree_map(
                lambda a, o=off, m=n: a[o:o + m], tree))
            off += n
    return out


def _join_runs(trees):
    """The inverse of _split_by_runs: one tree stacked over all layers."""
    return jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *trees)


def _same_structure(trees) -> bool:
    def sig(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return treedef, tuple((x.shape, x.dtype) for x in leaves)

    first = sig(trees[0])
    return all(sig(t) == first for t in trees[1:])


def _scans(config: GPTConfig, steps: int, uniform_lora: bool) -> bool:
    """Whether a run is ONE ``lax.scan`` (over its layers, or over its
    periods): more than one step, ``scan_layers``, and adapters of one
    structure from step to step. Otherwise the layers are called directly."""
    return steps > 1 and config.scan_layers and uniform_lora


def _hold_experts(config: GPTConfig, h, w):
    """(a scanned run's stacked weights ``w`` without the experts' three
    matrices, those three held whole — or ``w`` and {}).

    Where a call's rows cannot touch every expert (a decode step: 8 rows x
    6 choices of 128 experts), a dropless expert run's scan does not slice
    the experts' weights: a slice of [n, E, d, f] handed to the grouped
    matmul is a COPY of all E experts a layer (1.2 GB a decode step at 128
    experts of 2048 x 768, three times what the step's rows read). They
    stay whole, closed over, and step j addresses its experts as the groups
    [j E, (j + 1) E) of one matmul grouped over n E
    (moe.dropless_experts, ``expert_layer``). A call with more rows than
    experts reads a layer's slice whole anyway, and its backward wants the
    slice in a layout of its own."""
    B, T = jax.tree_util.tree_leaves(h)[0].shape[:2]
    few_rows = B * T * config.expert_top_k < config.n_experts
    if not (few_rows and config.is_dropless and "router" in w):
        return w, {}
    held = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    return {k: v for k, v in w.items() if k not in held}, held


def _run_layers(config: GPTConfig, params: Params, lora, h, fns, xs):
    """THE layer loop, of every kind of stack. It walks
    ``config.layer_runs()``: a run of structurally equal layers is one
    ``lax.scan`` over its stacked weights (one body a run). A run of one,
    adapters that differ from layer to layer, and ``scan_layers=False`` call
    the layers directly. Weights are stored stacked a run
    (``params["runs"][r]``) or one tree a layer (``params["blocks"]``).

    ``h`` is what the loop carries from layer to layer, a TREE: the residual
    stream, or with a router state (``_stream``) the pair — so a second
    stream rides the scan on every path.

    fns[kind](h, blk, x_i, lora_i) -> (h, y_i, aux); xs[kind]: None, or a
    list with one entry a run of that kind, each a tree stacked over the
    run's layers. Returns (h, {kind: [ys of each run]}, aux).

    Where the stack's layers have variants (``GPTConfig.varies``: a window,
    positions by layer) ``fns[kind]`` is a dict from the variant to such a
    function, and a run whose variants repeat with a period P > 1 is ONE
    scan over its n / P periods (``_run_periods``): the body holds the P
    layers of a period, each with its variant static."""
    stack = lambda *a: jnp.stack(a)  # noqa: E731

    def layer(t, j):
        """Layer j of a run's tree, stored one tree a layer or stacked."""
        if isinstance(t, list):
            return t[j]
        return jax.tree_util.tree_map(lambda a: a[j], t)

    seen = {kind: 0 for kind in fns}
    ys = {kind: [] for kind in fns}
    aux = jnp.zeros(_aux_shape(config), jnp.float32)
    for r, (kind, first, n) in enumerate(config.layer_runs()):
        layers = range(first, first + n)
        w = (params["runs"][r] if "runs" in params
             else [params["blocks"][str(i)] for i in layers])
        lo = [None] * n
        if lora is not None:
            lo = [lora["blocks"].get(str(i)) for i in layers]
        x_run = None if xs.get(kind) is None else xs[kind][seen[kind]]
        seen[kind] += 1
        fn = fns[kind]
        if isinstance(fn, dict):
            period = config.run_period(first, n)
            if period > 1:
                h, y, aux = _run_periods(
                    config, [fn[config.layer_variant(first + p)]
                             for p in range(period)], w, lo, x_run, h, aux)
                ys[kind].append(y)
                continue
            fn = fn[config.layer_variant(first)]
        uniform_lora = _same_structure(lo)
        scan = _scans(config, n, uniform_lora)
        if scan and isinstance(w, list):
            w = jax.tree_util.tree_map(stack, *w)
        if uniform_lora:
            # also where the run is not scanned: layer() then picks rows,
            # which is what a hybrid stack's runs of one have always lowered
            lo = jax.tree_util.tree_map(stack, *lo)
        held = {}
        if scan:
            w, held = _hold_experts(config, h, w)

            def body(carry, x, fn=fn, held=held):
                h, aux = carry
                if held:
                    blk, *x, j = x
                    x = ({**blk, **held, "expert_layer": j}, *x)
                hn, y, a = fn(h, *x)
                return (hn, aux + a), y

            xs_run = (w, x_run, lo) + ((jnp.arange(n),) if held else ())
            (h, aux), y = jax.lax.scan(body, (h, aux), xs_run)
        else:
            outs = []
            for j in range(n):
                h, y, a = fn(h, layer(w, j), layer(x_run, j), layer(lo, j))
                aux = aux + a
                outs.append(y)
            y = jax.tree_util.tree_map(stack, *outs)
        ys[kind].append(y)
    return h, ys, aux


def _run_periods(config: GPTConfig, fns, w, lo, x_run, h, aux):
    """A run whose layers' variants repeat with period ``P = len(fns)``:
    one ``lax.scan`` over its n / P periods whose body calls the P layers
    of a period one after the other, layer p with ``fns[p]`` — its variant
    static, as the kernels want their window. ``w``: the run's weights as
    ``stack_run`` stores them, tree p stacked over the periods and sliced
    by the scan as xs; ``lo``: the n layers' adapters; ``x_run``: the run's
    per-layer inputs stacked over its n layers in layer order (a cache),
    closed over, each layer taking its own row — the slice of ONE layer
    that a scan over layers takes, where a period's rows as xs would be a
    slice P layers large. Returns (h, ys stacked over the n layers, aux).

    A run of one period, adapters that differ between periods and
    ``scan_layers=False`` call the layers directly (``_scans``). A dropless
    expert run whose rows cannot touch every expert holds each position's
    experts whole (``_hold_experts``)."""
    stack = lambda *a: jnp.stack(a)  # noqa: E731
    P, n = len(fns), len(lo)
    m = n // P
    if not isinstance(w, list) or len(w) != P:
        raise ValueError(
            f"a run of period {P} stores {P} trees, one a position in the "
            "period (model.stack_run)")
    uniform_lora = all(_same_structure(lo[p::P]) for p in range(P))
    row = lambda t, j: jax.tree_util.tree_map(lambda a: a[j], t)  # noqa: E731

    def layer_x(i):  # layer i's row of the run's inputs; i may be traced
        if x_run is None:
            return None
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            x_run)

    if not _scans(config, m, uniform_lora):
        outs = []
        for i in range(n):
            h, y, a = fns[i % P](h, row(w[i % P], i // P), layer_x(i), lo[i])
            aux = aux + a
            outs.append(y)
        return h, jax.tree_util.tree_map(stack, *outs), aux

    lo = [jax.tree_util.tree_map(stack, *lo[p::P]) for p in range(P)]
    w, held = map(list, zip(*(_hold_experts(config, h, t) for t in w)))

    def body(carry, x):
        h, aux = carry
        blks, los, j = x
        ys = []
        for p in range(P):
            blk = blks[p]
            if held[p]:
                blk = {**blk, **held[p], "expert_layer": j}
            h, y, a = fns[p](h, blk, layer_x(j * P + p), los[p])
            aux = aux + a
            ys.append(y)
        return (h, aux), jax.tree_util.tree_map(stack, *ys)

    (h, aux), y = jax.lax.scan(body, (h, aux), (w, lo, jnp.arange(m)))
    # [m, P, ...] -> the run's n layers in layer order
    return h, jax.tree_util.tree_map(
        lambda a: a.reshape(n, *a.shape[2:]), y), aux


def forward(
    config: GPTConfig,
    params: Params,
    tokens: jax.Array,  # [B, T]
    attention_mask: Optional[jax.Array] = None,  # [B, T] 1=valid
    positions: Optional[jax.Array] = None,  # [B, T]
    cache: Optional[KVCache] = None,  # stacked over layers (leading axis L)
    lora: Optional[Params] = None,
    lora_scale: float = 2.0,
    flash: Optional[bool] = None,  # override config.use_flash_attention
    return_aux: bool = False,  # also return the MoE router load-balance loss
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Returns (hidden [B, T, D] float32, new cache); a cache's tokens go at
    cache.length (left-pad ragged prompts). ``return_aux``: the summed aux."""
    if config.layer_pattern is not None:
        return _pattern().forward(config, params, tokens, attention_mask,
                                  cache, lora, lora_scale, flash, return_aux)
    B, T = tokens.shape
    dtype = config.dtype
    if attention_mask is None:
        attention_mask = jnp.ones((B, T), jnp.int32)
    if positions is None:
        positions = jnp.cumsum(attention_mask, axis=-1) - 1
        positions = jnp.maximum(positions, 0)

    use_flash = config.use_flash_attention if flash is None else flash
    h = jnp.take(params["tok_emb"], tokens, axis=0).astype(dtype)

    # length/mask are layer-invariant: computed ONCE for the whole stack
    if cache is not None:
        start = cache.length
        cache_mask = jax.lax.dynamic_update_slice(
            cache.mask, attention_mask.astype(jnp.int32), (0, start)
        )
    else:
        start = cache_mask = None

    def block_fn(stream, blk, layer_kv, lora_layer, variant=None):
        """layer_kv: (k_cache [B,S,KV,hd], v_cache [B,S,KV,hd]) or None;
        over a CCA stack a third member, the layer's rolling state.
        ``variant``: the layer's (window, rotary) where the stack's layers
        have variants (``GPTConfig.layer_variant``)."""
        h, router_state = _stream(config, stream)
        window, rope = (0, None) if variant is None else variant
        logits = _early_router_logits(config, blk, h)
        with _attn_scope(config, window, layer_kv is not None):
            h, new_kv = attend(h, blk, layer_kv, lora_layer, window, rope)
        if config.is_mla:
            h, aux = _block_ffn(config, blk, h, lora_layer, lora_scale)
            return h, new_kv, aux
        stream, aux = _block_ffn(
            config, blk, _restream(config, h, router_state), lora_layer,
            lora_scale, logits)
        return stream, new_kv, aux

    def attend(h, blk, layer_kv, lora_layer, window, rope):
        """The attention half of a block: (h with the merged attention
        output, the new tokens' K/V or None)."""
        x = _rms(h, blk["ln1"], config.rms_eps)
        if config.is_mla:
            from agilerl_tpu.llm import mla

            q_nope, q_rope, lat = mla.project(
                config, blk, x, positions, lora_layer, lora_scale)
            if layer_kv is not None:
                # the same pre-update discipline as below, on the one array
                # a latent cache has; every cached forward is absorbed
                slab = jax.lax.dynamic_update_slice(
                    layer_kv[0], lat, (0, start, 0))
                new_kv = (lat, None)
                attn = mla.attend_absorbed(
                    config, blk, q_nope, q_rope, slab, cache_mask, start,
                    lora_layer, lora_scale)
            else:
                new_kv = None
                attn = mla.attend_expanded(
                    config, blk, q_nope, q_rope, lat, attention_mask,
                    lora_layer, lora_scale, use_flash)
            attn = _maybe_lora(attn, blk["wo"], lora_layer, "wo", lora_scale,
                               dtype)
            return h + attn, new_kv
        states = ()
        if config.is_cca:
            from agilerl_tpu.llm import cca

            q, k, v, *states = cca.qkv(
                config, blk, x, positions, attention_mask,
                None if layer_kv is None else layer_kv[2], lora_layer,
                lora_scale)
        else:
            q, k, v = _qkv_rope(config, blk, x, positions, lora_layer,
                                lora_scale, rope)

        if layer_kv is not None:
            # layer_kv = this layer's PRE-update (k_slab, v_slab). Attention
            # sees the locally-updated slab; the function returns only the
            # NEW tokens' post-rope projections — the caller bulk-writes
            # them into the stacked cache ONCE after the layer loop
            # (returning full updated slabs as scan ys forced a cache-sized
            # copy per step: +11 GiB temp at 7B decode-chunk dims, and a
            # cache-as-carry variant made XLA double-buffer the carry).
            ck = jax.lax.dynamic_update_slice(
                layer_kv[0], k, (0, start, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                layer_kv[1], v, (0, start, 0, 0))
            new_kv = (k, v, *states)
            # flash-decode: online-softmax over KV chunks bounded by the LIVE
            # cache length — never reads the dead cache tail, never
            # materializes GQA-repeated K/V (ops/decode_attention.py)
            from agilerl_tpu.ops.decode_attention import chunked_cached_attention

            attn = chunked_cached_attention(q, ck, cv, cache_mask, start,
                                            window=window)
            attn = attn.reshape(B, T, config.n_head * config.head_dim)
        else:
            new_kv = None
            # causal within the block + padding mask
            t_ids = jnp.arange(T)
            mask = (t_ids[None, None, :] <= t_ids[None, :, None])  # [1, T, S=T]
            if window:  # and no further behind the query than the window
                mask = jnp.logical_and(
                    mask, t_ids[None, :, None] - t_ids[None, None, :] < window)
            mask = jnp.logical_and(mask, attention_mask[:, None, :].astype(bool))
            # GQA: repeat kv heads
            rep = config.n_head // config.kv_heads
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)

            qh = jnp.moveaxis(q, 2, 1)  # [B, H, T, d]
            kh = jnp.moveaxis(k, 2, 1)
            vh = jnp.moveaxis(v, 2, 1)
            if use_flash:
                # Pallas flash attention (causal + padding mask, custom VJP so
                # it also serves training losses). No block sizes are passed:
                # ops.flash_attention_vjp.flash_plan chooses each kernel's
                # tile from (T, head widths, dtype) — one tile of the whole
                # sequence at T 1024 or 1152, 1024 x 1024 from 2048 on — among the
                # multiples of 128 that divide round_up(T, 128), so the
                # padded extent never passes the next multiple of 128.
                from agilerl_tpu.ops.flash_attention_vjp import (
                    flash_attention_diff,
                )

                smesh = _flash_mesh(config)
                if smesh is not None:
                    from jax import shard_map
                    from jax.sharding import PartitionSpec as P

                    bax, hax = config.flash_shard_axes
                    bspec = _axes_in_mesh(bax, smesh)
                    hspec = _axes_in_mesh(hax, smesh)
                    qspec = P(bspec, hspec, None, None)
                    attn = shard_map(
                        lambda qq, kk, vv, mm: flash_attention_diff(
                            qq, kk, vv, mm, True, spmd=False, window=window),
                        mesh=smesh,
                        in_specs=(qspec, qspec, qspec, P(bspec, None)),
                        out_specs=qspec,
                        check_vma=False,
                    )(qh, kh, vh, attention_mask)
                else:
                    attn = flash_attention_diff(qh, kh, vh, attention_mask,
                                                True, window=window)
            else:
                scores = jnp.einsum("bhtd,bhsd->bhts", qh, kh).astype(jnp.float32)
                scores = scores / math.sqrt(config.head_dim)
                scores = jnp.where(mask[:, None, :, :], scores, -1e9)
                probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
                attn = jnp.einsum("bhts,bhsd->bhtd", probs, vh)
            attn = jnp.moveaxis(attn, 1, 2).reshape(
                B, T, config.n_head * config.head_dim
            )
        attn = _maybe_lora(attn, blk["wo"], lora_layer, "wo", lora_scale, dtype)
        return _merge(blk, "merge1", h, attn), new_kv

    fn = jax.checkpoint(block_fn, static_argnums=()) if config.remat else block_fn
    fns = {"attn": fn}
    if config.varies:  # one function a variant, its window and rotary static
        wrap = jax.checkpoint if config.remat else (lambda f: f)
        fns["attn"] = {
            v: wrap(functools.partial(block_fn, variant=v))
            for v in config.variants}
    if config.is_hybrid:
        from agilerl_tpu.llm import ssm

        def mamba_fn(h, blk, layer_state, lora_layer):
            """layer_state: (conv, ssm) of this layer or None."""
            x = _rms(h, blk["ln1"], config.rms_eps)
            out, new_s, prev_s = ssm.mixer(
                config, blk, x, attention_mask, layer_state, lora_layer,
                lora_scale)
            h, aux = _block_ffn(config, blk, h + out, lora_layer, lora_scale)
            return h, (None if layer_state is None else (new_s, prev_s)), aux

        fns["mamba"] = jax.checkpoint(mamba_fn) if config.remat else mamba_fn
    # cached: the pre-update slabs (and recurrent states) ride as read-only
    # xs, the new tokens' k/v come back as small ys
    xs = {}
    if cache is not None:
        xs["attn"] = _split_by_runs(config, "attn", (cache.k, cache.v))
        if config.is_hybrid:
            xs["mamba"] = list(cache.state)
        if config.is_cca:
            xs["attn"] = [kv + (s,) for kv, s in zip(xs["attn"], cache.state)]
    stream, ys, aux_total = _run_layers(
        config, params, lora, _stream_in(config, h), fns, xs)
    h, _ = _stream(config, stream)

    new_caches: Optional[KVCache] = None
    if cache is not None:
        # ONE bulk write of the new tokens ([L, B, T, KV, hd]) into the
        # (aliasable) cache buffers
        new_k, new_v = _join_runs([y[:2] for y in ys["attn"]])
        at = (0, 0, start) + (0,) * (cache.k.ndim - 3)
        new_caches = KVCache(
            jax.lax.dynamic_update_slice(cache.k, new_k, at),
            None if cache.v is None else jax.lax.dynamic_update_slice(
                cache.v, new_v, at),
            start + T, cache_mask,
        )
        if config.state_kind is not None:
            # (new state, state before the last token) of each run's layers:
            # a state-space run's ys whole, the tail of a CCA run's; a
            # one-token forward has no "before the last token" of its own
            kept = [y[-2:] for y in ys[config.state_kind]]
            new_caches = new_caches._replace(
                state=tuple(y[0] for y in kept),
                prev_state=(tuple(y[1] for y in kept) if T > 1
                            else cache.prev_state))

    h = _rms(h, params["ln_f"], config.rms_eps).astype(jnp.float32)
    if return_aux:
        return h, new_caches, aux_total
    return h, new_caches


def load_stats(config: GPTConfig, aux: jax.Array) -> Tuple[jax.Array, ...]:
    """A dropless expert stack's load statistics from ``forward``'s summed
    aux: the fullest expert's rows over the mean, averaged over the expert
    layers, and a pattern stack's routed pairs that fell on the experts the
    chip holds."""
    load = aux[0] / config.n_moe_layers
    return (load, aux[2]) if config.layer_pattern is not None else (load,)


def _shard_mesh(axes):
    """The active mesh for a kernel shard_map wrap, or None. Reads the
    `with mesh:` trace-time context (the pattern every sharded program in
    this repo uses for lowering) and falls back to the abstract mesh."""
    if axes is None:
        return None
    from jax._src import mesh as _mesh_lib

    m = _mesh_lib.thread_resources.env.physical_mesh
    if m is not None and not m.empty:
        return m
    am = jax.sharding.get_abstract_mesh()
    if am is not None and am.axis_names:
        return am
    return None


def _flash_mesh(config: GPTConfig):
    return _shard_mesh(config.flash_shard_axes)


def _axes_in_mesh(axes, mesh):
    """Filter requested mesh-axis names to those present (and >1) in the
    mesh; returns None (replicated) when nothing survives."""
    if axes is None:
        return None
    axes = axes if isinstance(axes, tuple) else (axes,)
    kept = tuple(a for a in axes
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    return kept if kept else None


def block_apply_dense(
    config: GPTConfig,
    blk: Params,
    h: jax.Array,  # [B, T, d]
    attention_mask: jax.Array,  # [B, T]
    positions: jax.Array,  # [B, T]
) -> jax.Array:
    """One dense transformer block as a standalone pure function — the
    staging-friendly core used by parallel/pipeline.py's GPipe stages (no
    cache, no LoRA, no MoE routing). Kept NEXT TO block_fn above so the
    attention math has one home; tests/test_parallel/test_pipeline.py pins
    parity between the two paths (incl. qkv_bias)."""
    B, T, _ = h.shape
    dtype = h.dtype
    x = _rms(h, blk["ln1"], config.rms_eps)
    q, k, v = x @ blk["wq"].astype(dtype), x @ blk["wk"].astype(dtype), x @ blk["wv"].astype(dtype)
    if config.qkv_bias:
        q = q + blk["bq"].astype(dtype)
        k = k + blk["bk"].astype(dtype)
        v = v + blk["bv"].astype(dtype)
    q = q.reshape(B, T, config.n_head, config.head_dim)
    k = k.reshape(B, T, config.kv_heads, config.head_dim)
    v = v.reshape(B, T, config.kv_heads, config.head_dim)
    q = _rope(q, positions, config.rope_theta)
    k = _rope(k, positions, config.rope_theta)
    rep = config.n_head // config.kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qh, kh, vh = (jnp.moveaxis(a, 2, 1) for a in (q, k, v))
    scores = jnp.einsum("bhtd,bhsd->bhts", qh, kh).astype(jnp.float32)
    scores = scores / math.sqrt(config.head_dim)
    t_ids = jnp.arange(T)
    causal = t_ids[None, None, :] <= t_ids[None, :, None]
    full_mask = jnp.logical_and(causal, attention_mask[:, None, :].astype(bool))
    scores = jnp.where(full_mask[:, None, :, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    attn = jnp.einsum("bhts,bhsd->bhtd", probs, vh)
    attn = jnp.moveaxis(attn, 1, 2).reshape(B, T, config.n_head * config.head_dim)
    h = h + attn @ blk["wo"].astype(dtype)
    x = _rms(h, blk["ln2"], config.rms_eps)
    gate = x @ blk["w_gate"].astype(dtype)
    up = x @ blk["w_up"].astype(dtype)
    return h + (jax.nn.silu(gate) * up) @ blk["w_down"].astype(dtype)


def logits_fn(config: GPTConfig, params: Params, hidden: jax.Array) -> jax.Array:
    """hidden [B, T, D] -> logits [B, T, V] (float32)."""
    head = params["tok_emb"].T if config.tie_embeddings else params["lm_head"]
    return hidden @ head.astype(jnp.float32)


def apply(
    config: GPTConfig,
    params: Params,
    tokens: jax.Array,
    **kw,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Full forward to logits. With return_aux=True also returns the MoE
    router load-balance loss: (logits, caches, aux)."""
    if kw.get("return_aux"):
        hidden, caches, aux = forward(config, params, tokens, **kw)
        return logits_fn(config, params, hidden), caches, aux
    hidden, caches = forward(config, params, tokens, **kw)
    return logits_fn(config, params, hidden), caches


def init_caches(config: GPTConfig, batch: int, max_len: Optional[int] = None) -> KVCache:
    """One stacked cache for the whole layer stack (leading axis = layer)."""
    return init_kv_cache(config, batch, max_len)


# --------------------------------------------------------------------------- #
# Paged KV cache (vLLM PagedAttention role, Kwon et al. SOSP 2023, redesigned
# for XLA's compile-once model): ONE physical block pool shared by every
# in-flight sequence + per-slot int32 block tables. A finished sequence's
# blocks return to the host free list immediately; heterogeneous lengths
# never strand HBM on a dense [B, P_max + N] allocation. The serving tier
# (llm/serving.ContinuousGenerator) owns the tables/free-list on the host;
# the device only ever sees gathers/scatters through them.
# --------------------------------------------------------------------------- #


class PagedKVCache(NamedTuple):
    """Physical KV block pool, stacked over layers.

    Block 0 is reserved as a garbage sink: free slots in the decode program
    point their whole block table at it, so masked writes always have a
    legal destination and no compiled program ever branches on occupancy."""

    # latent attention: k is [L, n_blocks, block_size, latent width] and v
    # is None — one array, 576 values a token a layer where the expanded
    # keys and values of 32 heads would be 10240
    k: jax.Array  # [L, n_blocks, block_size, KV, hd]
    v: jax.Array  # [L, n_blocks, block_size, KV, hd]
    # Hybrid stacks only (k/v then hold the attention layers alone) — the
    # second cache kind, per SLOT and not per block: a state-space layer's
    # state is one fixed-size record a sequence, whatever its length.
    # state: one (conv [n, slots, k-1, d_inner], ssm [n, slots, d_state,
    # d_inner] f32) a run of state-space layers; snap: the same with
    # snapshot entries in place of slots (the state BEFORE a prompt's last
    # token, what a prefix-cache hit resumes from; the last entry is a sink
    # for prefills whose snapshot nobody keeps)
    state: Any = None
    snap: Any = None

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]


def init_paged_cache(config: GPTConfig, n_blocks: int, block_size: int,
                     slots: Optional[int] = None,
                     snapshots: int = 0) -> PagedKVCache:
    per_token, has_v = _token_shape(config)
    shape = (config.n_layers_of("attn"), n_blocks, block_size, *per_token)
    state = snap = None
    if config.state_kind is not None:
        if slots is None:
            raise ValueError("the paged cache of a stack whose layers keep "
                             "state holds it per slot: pass slots=")
        state = _init_state(config, slots)
        snap = _init_state(config, snapshots + 1)
    return PagedKVCache(k=jnp.zeros(shape, config.dtype),
                        v=jnp.zeros(shape, config.dtype) if has_v else None,
                        state=state, snap=snap)


def paged_block_bytes(cache: PagedKVCache) -> int:
    """Bytes one physical block holds across all attention layers."""
    return int(sum(x.size // x.shape[1] * x.dtype.itemsize
                   for x in (cache.k, cache.v) if x is not None))


def state_cache_bytes(cache: PagedKVCache) -> int:
    """Bytes of the recurrent-state cache, slots and snapshots."""
    return int(sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves((cache.state, cache.snap))))


def paged_write_index(block_tables: jax.Array, write_pos: jax.Array,
                      block_size: int) -> jax.Array:
    """Flat pool index [B] for each slot's write position. Positions past the
    table (possible only for released slots whose lengths keep advancing)
    clamp into the last table entry — released slots' tables are all-zero,
    so the write lands in the reserved garbage block."""
    mb = block_tables.shape[1]
    bidx = jnp.minimum(write_pos // block_size, mb - 1)
    phys = jnp.take_along_axis(block_tables, bidx[:, None], axis=1)[:, 0]
    return phys * block_size + write_pos % block_size


def paged_scatter_tokens(cache: PagedKVCache, block_tables: jax.Array,
                         write_pos: jax.Array, new_k: jax.Array,
                         new_v: jax.Array, new_state=None) -> PagedKVCache:
    """ONE bulk write of the step's new tokens into the pool across all
    layers (mirrors forward's single dynamic_update_slice after the layer
    scan). new_k/new_v: [L, B, KV, hd]; write_pos: [B] logical slot index.
    ``new_state`` (hybrid stacks: forward_paged's third result) replaces the
    per-slot recurrent state whole — rows are slots."""
    L, nb, bs, *tok = cache.k.shape  # tok: (KV, hd), or (width,) latent
    idx = paged_write_index(block_tables, write_pos, bs)
    flat_k = cache.k.reshape(L, nb * bs, *tok).at[:, idx].set(new_k)
    if cache.v is None:  # a latent cache has no V array
        cache = cache._replace(k=flat_k.reshape(L, nb, bs, *tok))
    else:
        flat_v = cache.v.reshape(L, nb * bs, *tok).at[:, idx].set(new_v)
        cache = cache._replace(k=flat_k.reshape(L, nb, bs, *tok),
                               v=flat_v.reshape(L, nb, bs, *tok))
    return cache if new_state is None else cache._replace(state=new_state)


def paged_scatter_multi(cache: PagedKVCache, block_tables: jax.Array,
                        write_pos: jax.Array, new_k: jax.Array,
                        new_v: jax.Array) -> PagedKVCache:
    """Bulk write of a multi-token verify window into the pool.

    new_k/new_v: [L, B, T, KV, hd]; write_pos: [B, T] logical slot indices
    (lengths + arange(T) in the speculative verify step). Unlike the
    single-token path, positions at or past the logical extent S are
    REDIRECTED to the reserved garbage block 0 instead of clamping into the
    last table entry: a full-table slot speculating near its budget must
    never corrupt its own (possibly shared) final block. Rejected-draft
    positions inside the extent are written as-is — they sit past the
    slot's accepted length, are invisible to every mask, and are rewritten
    before the sequence ever reaches them."""
    L, nb, bs, KV, hd = cache.k.shape
    B, T = write_pos.shape
    mb = block_tables.shape[1]
    bidx = jnp.minimum(write_pos // bs, mb - 1)
    phys = jnp.take_along_axis(block_tables, bidx, axis=1)
    phys = jnp.where(write_pos < mb * bs, phys, 0)
    idx = (phys * bs + write_pos % bs).reshape(-1)
    flat_k = cache.k.reshape(L, nb * bs, KV, hd).at[:, idx].set(
        new_k.reshape(L, B * T, KV, hd))
    flat_v = cache.v.reshape(L, nb * bs, KV, hd).at[:, idx].set(
        new_v.reshape(L, B * T, KV, hd))
    return cache._replace(k=flat_k.reshape(L, nb, bs, KV, hd),
                          v=flat_v.reshape(L, nb, bs, KV, hd))


def paged_scatter_prompt(cache: PagedKVCache, block_ids: jax.Array,
                         k_prompt: jax.Array, v_prompt: jax.Array) -> PagedKVCache:
    """Write one request's prefilled prompt KV ([L, Pb, KV, hd], Pb a whole
    number of blocks) into its assigned physical blocks ([Pb // bs])."""
    L, _, bs, *tok = cache.k.shape
    nb_p = k_prompt.shape[1] // bs

    def put(pool, prompt):
        if pool is None:
            return None
        return pool.at[:, block_ids].set(prompt.reshape(L, nb_p, bs, *tok))

    return cache._replace(k=put(cache.k, k_prompt), v=put(cache.v, v_prompt))


def paged_write_state(cache: PagedKVCache, slot, snap, state,
                      prev_state) -> PagedKVCache:
    """After a prefill of ONE request (states with a batch of 1): the state
    after the prompt into slot ``slot``, the state before the prompt's last
    token into snapshot entry ``snap``."""
    put = lambda dst, i, src: dst.at[:, i].set(src[:, 0])  # noqa: E731
    return cache._replace(
        state=jax.tree_util.tree_map(
            lambda d, s: put(d, slot, s), cache.state, tuple(state)),
        snap=jax.tree_util.tree_map(
            lambda d, s: put(d, snap, s), cache.snap, tuple(prev_state)))


def paged_copy_block(cache: PagedKVCache, src, dst, snap=None,
                     slot=None) -> PagedKVCache:
    """Copy one physical block (prefix-cache hit: the last prompt block is
    duplicated into a private block so the first decode write cannot touch
    the shared original). With ``snap`` and ``slot`` (hybrid stacks) the
    same program also restores snapshot entry ``snap`` into slot ``slot``:
    the recurrent state the re-entering last prompt token starts from."""
    cache = cache._replace(
        k=cache.k.at[:, dst].set(cache.k[:, src]),
        v=None if cache.v is None else cache.v.at[:, dst].set(cache.v[:, src]))
    if snap is None:
        return cache
    return cache._replace(state=jax.tree_util.tree_map(
        lambda d, s: d.at[:, slot].set(s[:, snap]), cache.state, cache.snap))


def forward_paged(
    config: GPTConfig,
    params: Params,
    tokens: jax.Array,       # [B, T] the current token(s) per slot
    positions: jax.Array,    # [B] or [B, T] RoPE position(s)
    write_pos: jax.Array,    # [B] or [B, T] logical cache slot(s) for K/V
    cache: PagedKVCache,
    block_tables: jax.Array,  # [B, max_blocks] int32
    slot_mask: jax.Array,    # [B, S] 1 where the LOGICAL slot holds a real
    # token — including the current token at write_pos (caller pre-sets it)
    lora: Optional[Params] = None,
    lora_scale: float = 2.0,
    return_aux: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decode forward over the slot pool: returns (hidden [B, T, D]
    float32, (new_k, new_v)) — the caller scatters the new KV into the pool
    (paged_scatter_tokens / paged_scatter_multi) exactly once. Over a hybrid
    stack the second result has a third member, the new per-slot recurrent
    state, which ``paged_scatter_tokens(cache, tables, pos, *new)`` puts in
    place of the old. Over a latent cache ``new_k`` is the new latent and
    ``new_v`` None. ``return_aux`` (dropless stacks): + ``forward``'s aux.

    Per-slot `write_pos`: slots are admitted at different times, so there
    is no shared scalar cache length. Attention reads the pool as it was BEFORE
    this call, through the block table and one live chunk at a time
    (ops/decode_attention.chunked_paged_attention), with this call's new
    K/V put into the chunk they fall in — the same pre-update discipline
    as forward's block_fn, and no array of a slot's whole extent
    (max_blocks * block_size) is built in any layer. Greedy outputs equal
    the dense path's bit for bit: the SAME code (_qkv_rope/_block_ffn).

    T == 1 is the per-token decode step (positions/write_pos [B]; new KV
    [L, B, KV, hd]). T > 1 is the speculative verify window (positions and
    write_pos [B, T], consecutive per row with write_pos[:, 0] = lengths;
    new KV [L, B, T, KV, hd]). All T candidate K/Vs go into their chunks
    (one past the extent goes nowhere), and visibility is the SAME rule
    both ways: query t attends to logical slots <= write_pos[:, 0] + t that
    slot_mask marks valid, so candidate j sees exactly the prefix plus
    candidates < j."""
    if config.layer_pattern is not None:
        return _pattern().forward_paged(
            config, params, tokens, write_pos, cache, block_tables,
            slot_mask, lora, lora_scale, return_aux)
    B, T = tokens.shape
    dtype = config.dtype
    h = jnp.take(params["tok_emb"], tokens, axis=0).astype(dtype)
    pos2d = positions if positions.ndim == 2 else positions[:, None]
    wp_start = write_pos[:, 0] if write_pos.ndim == 2 else write_pos

    # the parts of a layer are named here (PERF.md section 3), at the paged
    # path's call sites, so that the learn programs, which share _qkv_rope /
    # _block_ffn, keep their text
    def mla_block_fn(h, blk, layer_kv, lora_layer):
        from agilerl_tpu.llm import mla

        with device_scope(PROJ_SCOPE):
            x = _rms(h, blk["ln1"], config.rms_eps)
            q_nope, q_rope, lat = mla.project(config, blk, x, pos2d,
                                              lora_layer, lora_scale)
        attn = mla.attend_absorbed(
            config, blk, q_nope, q_rope,
            (layer_kv[0], block_tables, lat, write_pos), slot_mask,
            wp_start, lora_layer, lora_scale)
        with device_scope(PROJ_SCOPE):
            attn = _maybe_lora(attn, blk["wo"], lora_layer, "wo", lora_scale,
                               dtype)
            h = h + attn
        with device_scope(FFN_SCOPE):
            h, aux = _block_ffn(config, blk, h, lora_layer, lora_scale)
        new_kv = (lat if write_pos.ndim == 2 else lat[:, 0], None)
        return h, new_kv, (aux if config.is_dropless else 0.0)

    if config.state_kind is not None:
        # the second cache kind: rows ARE slots, so a layer that keeps state
        # reads and writes its slot's record in place — no table, no gather
        if T > 1:
            raise NotImplementedError(
                "a multi-token paged forward (speculative verify) over "
                "layers that keep per-slot state (a hybrid stack's "
                "recurrent state, a CCA stack's rolling state) needs that "
                "state rolled back for rejected drafts; not implemented")
        S = slot_mask.shape[1]
        tok_mask = jnp.take_along_axis(
            slot_mask, jnp.minimum(wp_start, S - 1)[:, None], axis=1)

    def block_fn(stream, blk, layer_kv, lora_layer, variant=None):
        h, router_state = _stream(config, stream)
        window, rope = (0, None) if variant is None else variant
        states = ()
        logits = _early_router_logits(config, blk, h)
        with device_scope(PROJ_SCOPE):
            x = _rms(h, blk["ln1"], config.rms_eps)
            if config.is_cca:
                from agilerl_tpu.llm import cca

                q, k, v, new_state, _ = cca.qkv(
                    config, blk, x, pos2d, tok_mask, layer_kv[2], lora_layer,
                    lora_scale)
                states = (new_state,)
            else:
                q, k, v = _qkv_rope(config, blk, x, pos2d, lora_layer,
                                    lora_scale, rope)
        from agilerl_tpu.ops.decode_attention import chunked_paged_attention

        attn = chunked_paged_attention(q, layer_kv[0], layer_kv[1],
                                       block_tables, k, v, write_pos,
                                       slot_mask, wp_start, window=window)
        attn = attn.reshape(B, T, config.n_head * config.head_dim)
        with device_scope(PROJ_SCOPE):
            attn = _maybe_lora(attn, blk["wo"], lora_layer, "wo", lora_scale,
                               dtype)
            h = _merge(blk, "merge1", h, attn)
        with device_scope(FFN_SCOPE):
            stream, aux = _block_ffn(
                config, blk, _restream(config, h, router_state), lora_layer,
                lora_scale, logits)
        new_kv = (k, v) if write_pos.ndim == 2 else (k[:, 0], v[:, 0])
        return stream, new_kv + states, (aux if config.is_dropless else 0.0)

    fns = {"attn": mla_block_fn if config.is_mla else block_fn}
    if config.varies:
        fns["attn"] = {
            v: functools.partial(block_fn, variant=v)
            for v in config.variants}
    if config.is_hybrid:
        from agilerl_tpu.llm import ssm

        def mamba_fn(h, blk, layer_state, lora_layer):
            x = _rms(h, blk["ln1"], config.rms_eps)
            out, new_s, _ = ssm.mixer(config, blk, x, tok_mask, layer_state,
                                      lora_layer, lora_scale)
            with device_scope(FFN_SCOPE):
                h, _ = _block_ffn(config, blk, h + out, lora_layer, lora_scale)
            return h, new_s, 0.0

        fns["mamba"] = mamba_fn
    xs = {"attn": _split_by_runs(config, "attn", (cache.k, cache.v))}
    if config.is_hybrid:
        xs["mamba"] = list(cache.state)
    if config.is_cca:
        xs["attn"] = [kv + (s,) for kv, s in zip(xs["attn"], cache.state)]
    stream, ys, aux = _run_layers(config, params, lora,
                                  _stream_in(config, h), fns, xs)
    h, _ = _stream(config, stream)
    h = _rms(h, params["ln_f"], config.rms_eps).astype(jnp.float32)
    new = _join_runs([y[:2] for y in ys["attn"]])
    if config.is_hybrid:
        new += (tuple(ys["mamba"]),)
    if config.is_cca:
        new += (tuple(y[2] for y in ys["attn"]),)
    if return_aux:
        return h, new, aux
    return h, new


# --------------------------------------------------------------------------- #
# Chunked log-probs (parity: _get_logprobs / _memory_efficient_logits,
# core/base.py:2670,2937 — row-chunked log-softmax to avoid materialising
# [B, T, V] float32 logits; the Pallas fused kernel in ops/fused_loss.py goes
# further and never materialises the chunk either)
# --------------------------------------------------------------------------- #


def token_logprobs(
    config: GPTConfig,
    params: Params,
    tokens: jax.Array,  # [B, T]
    attention_mask: Optional[jax.Array] = None,
    lora: Optional[Params] = None,
    lora_scale: float = 2.0,
    temperature: float = 1.0,
    chunk_size: int = 128,
    use_pallas: bool = False,
    flash: Optional[bool] = None,
    return_aux: bool = False,
) -> jax.Array:
    """log p(tokens[:, t] | tokens[:, <t]) for t>=1, shape [B, T-1]; with
    ``return_aux`` (log-probabilities, the layers' summed aux as ``forward``
    returns it).

    use_pallas=True routes the lm-head+log-softmax through the fused Pallas
    kernel (ops/fused_loss.py, the Liger replacement). The kernel carries a
    custom VJP that recomputes per vocab chunk, so this path serves BOTH the
    no-grad logprob passes and the differentiable GRPO/DPO training losses
    (Liger parity: its fused losses are differentiable, ref grpo.py:558);
    flash likewise enables the Pallas attention kernel (own VJP)."""
    hidden, _, *aux = forward(
        config, params, tokens, attention_mask=attention_mask, lora=lora,
        lora_scale=lora_scale, flash=flash, return_aux=return_aux)
    lp = _hidden_logprobs(config, params, hidden, tokens, temperature,
                          chunk_size, use_pallas)
    return (lp, aux[0]) if return_aux else lp


def _hidden_logprobs(config, params, hidden, tokens, temperature, chunk_size,
                     use_pallas):
    """``token_logprobs`` from the final hidden states [B, T, D]."""
    if use_pallas:
        from agilerl_tpu.ops.fused_loss import fused_token_logprob_diff

        # operands in the configuration's compute dtype, like every other
        # matmul of the step (the kernel keeps its softmax statistics and
        # accumulators in f32); cast here, before the shard_map, so that a
        # replicated head is gathered at the narrow width
        head = (params["tok_emb"].T if config.tie_embeddings
                else params["lm_head"]).astype(config.dtype)
        B, T, D = hidden.shape
        flat_h = hidden[:, :-1].reshape(-1, D).astype(config.dtype)
        flat_t = tokens[:, 1:].reshape(-1)
        smesh = _shard_mesh(getattr(config, "fused_loss_shard_axes", None))
        bspec = (_axes_in_mesh(config.fused_loss_shard_axes, smesh)
                 if smesh is not None else None)
        if bspec is not None:
            n_shards = int(np.prod([smesh.shape[a] for a in bspec]))
            if flat_h.shape[0] % n_shards:
                bspec = None  # rows don't tile the axes: plain call
        if bspec is not None:
            # rows shard over the batch axes; the replicated head's dW
            # cotangent is psummed by shard_map's transpose rule
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            lp = shard_map(
                lambda hh, ww, tt: fused_token_logprob_diff(
                    hh, ww, tt, temperature),
                mesh=smesh,
                in_specs=(P(bspec, None), P(None, None), P(bspec)),
                out_specs=P(bspec),
                check_vma=False,
            )(flat_h, head, flat_t)
        else:
            lp = fused_token_logprob_diff(flat_h, head, flat_t, temperature)
        return lp.reshape(B, T - 1)
    hidden = hidden[:, :-1]  # predict next token
    targets = tokens[:, 1:]
    head = (params["tok_emb"].T if config.tie_embeddings else params["lm_head"]).astype(
        jnp.float32
    )

    B, Tm1, D = hidden.shape
    flat_h = hidden.reshape(-1, D)
    flat_t = targets.reshape(-1)
    n = flat_h.shape[0]
    pad = (-n) % chunk_size
    flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
    flat_t = jnp.pad(flat_t, (0, pad))
    chunks_h = flat_h.reshape(-1, chunk_size, D)
    chunks_t = flat_t.reshape(-1, chunk_size)

    def one_chunk(carry, xs):
        h, t = xs
        logits = (h @ head) / temperature  # [chunk, V]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return carry, chosen - logz

    _, lp = jax.lax.scan(one_chunk, None, (chunks_h, chunks_t))
    return lp.reshape(-1)[:n].reshape(B, Tm1)


# --------------------------------------------------------------------------- #
# The weights a rollout computes on. It belongs beside forward_paged and
# stands here, at the end of the file, because a Mosaic kernel's serialized
# body holds the line of every Python frame above its call: a function put
# in above token_logprobs would give every learn program a new cache key.
# --------------------------------------------------------------------------- #

#: the leaves that every cached forward (forward_paged, paged_decode_step,
#: the prefill) rounds to ``config.dtype`` before it reads them, by the name
#: of the leaf: the lookup, _maybe_lora's and ssm._proj_f32's matrices, the
#: experts' and the shared expert's, latent attention's two, CCA's four
#: projections and its grouped convolution. Everything else is read as
#: stored — norm scales and biases (vectors, also where a run stacks them to
#: rank 2), the router and its MLP (float32 at ``highest``), conv taps,
#: ``A_log``, ``D``, ``dt_bias``, ``tau``, the merge vectors — and stays
#: what it is. It is the rule the bf16-stored configurations store by, but
#: for THE HEAD (``lm_head``, or the embedding of a stack that ties it),
#: which stays as stored: ``logits_fn`` widens it for an f32 product, and
#: though the chip's default precision rounds that product's operands to
#: bf16, a head handed over in bf16 does not give the same bits there (1 of
#: 6144 captured log-probabilities of a qwen2-7b rollout moved, by 2e-3:
#: PERF.md, section 6, PR 39).
COMPUTE_MATRICES = frozenset((
    "tok_emb", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "ws_gate", "ws_up", "ws_down", "wkv_a", "wkv_b", "wv1", "wv2", "conv1_w",
    "in_proj", "x_proj", "dt_proj", "out_proj", "lat_down", "lat_up"))


@functools.lru_cache(maxsize=None)
def _copy_program(dtype, narrow, runs, leaf_shardings, run_shardings):
    """ONE program that makes ``compute_params``' new leaves. ``leaves`` are
    cast to ``dtype``; ``blocks`` (one flat list of leaves a layer, or none)
    come back stacked as ``stack_run`` stacks them — for each of ``runs``
    (first layer, layers, period) and each position in its period, one array
    a leaf over that position's layers — with the leaves that ``narrow``
    marks cast. The shardings: one entry a result, a mesh placement or
    None."""
    def copy(leaves, blocks):
        blocks = [[x.astype(dtype) if cast else x
                   for x, cast in zip(layer, narrow)] for layer in blocks]
        return ([x.astype(dtype) for x in leaves],
                [jnp.stack(xs) for first, n, period in runs
                 for p in range(period)
                 for xs in zip(*blocks[first + p:first + n:period])])

    return jax.jit(copy, out_shardings=(list(leaf_shardings),
                                        list(run_shardings)))


def compute_params(config: GPTConfig, params: Params) -> Params:
    """The tree the cached forwards compute on: ``params`` with every leaf
    of ``COMPUTE_MATRICES`` that is not yet in ``config.dtype`` cast to it —
    the cast each of those programs would make itself, a call. A tree in
    which nothing is to cast is returned as the same object and dispatches
    nothing. Otherwise ONE jitted program makes the new leaves, and every
    leaf outside ``params["blocks"]`` that it does not cast is the SAME
    array (a leaf handed through a jit untouched is copied). A stack stored
    one tree a layer (``params["blocks"]``) comes back in the layout its
    programs scan, ``params["runs"]`` as ``init_params`` stores a stack by
    runs: the copy is being written anyway, and written a layer the layer
    loop would stack it again inside every program (a quarter of what the
    casts cost a decode chunk of qwen2-7b). Under a mesh each leaf keeps
    its placement, a stacked one with the layer axis replicated. The
    masters stay with the caller: what ``learn`` reads, a checkpoint saves
    and a population shares."""
    from jax.sharding import NamedSharding, PartitionSpec

    dtype = jnp.dtype(config.dtype)
    names = COMPUTE_MATRICES - ({"tok_emb"} if config.tie_embeddings else set())

    def narrows(path, x):
        return (getattr(path[-1], "key", None) in names and x.ndim >= 2
                and x.dtype != dtype)

    def placement(x, stacked=False):
        s = getattr(x, "sharding", None)
        if not isinstance(s, NamedSharding):
            return None
        return NamedSharding(s.mesh, PartitionSpec(None, *s.spec)) \
            if stacked else s

    rest = {k: v for k, v in params.items() if k != "blocks"}
    flat, treedef = jax.tree_util.tree_flatten_with_path(rest)
    leaves = [x for _, x in flat]
    todo = [i for i, (path, x) in enumerate(flat) if narrows(path, x)]
    blocks, narrow, runs, block_def = [], (), (), None
    if "blocks" in params:
        layers = [jax.tree_util.tree_flatten_with_path(
            params["blocks"][str(i)]) for i in range(config.n_layer)]
        block_def = layers[0][1]
        blocks = [[x for _, x in layer] for layer, _ in layers]
        narrow = tuple(narrows(path, x) for path, x in layers[0][0])
        runs = tuple((first, n, config.run_period(first, n))
                     for _, first, n in config.layer_runs())
    if not (todo or any(narrow)):
        return params
    cast, stacked = _copy_program(
        dtype, narrow, runs, tuple(placement(leaves[i]) for i in todo),
        tuple(placement(x, stacked=True) for first, _, period in runs
              for p in range(period) for x in blocks[first + p]),
    )([leaves[i] for i in todo], blocks)
    for i, x in zip(todo, cast):
        leaves[i] = x
    out = jax.tree_util.tree_unflatten(treedef, leaves)
    if block_def is not None:
        k = len(narrow)
        trees = [block_def.unflatten(stacked[j:j + k])
                 for j in range(0, len(stacked), k)]
        out["runs"] = []
        for _, _, period in runs:
            run, trees = trees[:period], trees[period:]
            out["runs"].append(run[0] if period == 1 else run)
    return out


# --------------------------------------------------------------------------- #
# Stacks of single-block layers named by a pattern (GPTConfig.layer_pattern):
# their layers, forwards and adapters are llm/nemotron_h.py's. Here, at the
# end of the file, for the reason in the notes below.
# --------------------------------------------------------------------------- #

#: a pattern letter -> the kind of its layer
PATTERN_KINDS = {"M": "mamba2", "E": "moe", "*": "attn"}


def _pattern():
    from agilerl_tpu.llm import nemotron_h

    return nemotron_h


def held_experts(config: GPTConfig) -> Tuple[int, int]:
    """(first expert id, experts) this chip holds of an expert layer: the
    share ``experts_held`` names, or all of the router's."""
    return config.experts_held or (0, config.n_experts)


def _check_pattern(config: GPTConfig) -> None:
    """``GPTConfig.__post_init__``'s checks of the pattern stack's fields."""
    if config.layer_pattern is not None:
        _pattern().check(config)
    elif (config.mamba_heads or config.moe_latent or config.experts_held
          or config.expert_act == "relu2"):
        raise ValueError(
            "mamba_heads, moe_latent, experts_held and squared-ReLU experts "
            "are read by a pattern stack's layers alone (layer_pattern)")


# --------------------------------------------------------------------------- #
# What each kind of stack cannot do, stated once (docs/serving.md, "What
# each stack refuses"). The serving tiers and GRPO call ``refuse`` where a
# feature is asked for; a new kind of stack adds a row here.
# --------------------------------------------------------------------------- #

#: a stack kind -> (how a refusal names it, whether a config is of it), in
#: the order kinds answer: a stack of two kinds (an expert stack: window,
#: CCA, Mamba-2 or latent attention with the dropless dispatch) refuses by
#: the first
STACK_KINDS = {
    "window": ("a stack with sliding-window layers", lambda c: c.varies),
    "cca": ("a CCA stack", lambda c: c.is_cca),
    "mamba2": ("a Mamba-2 stack", lambda c: c.state_kind == "mamba2"),
    "latent": ("a latent cache", lambda c: c.is_mla),
    "dropless": ("a dropless expert stack", lambda c: c.is_dropless),
    "hybrid": ("a hybrid stack", lambda c: c.is_hybrid),
}

#: a feature -> (the exception its refusal raises, what the refusal names)
FEATURES = {
    # ContinuousGenerator(speculate=...): the multi-token verify step
    "speculate": (ValueError, "speculative decoding"),
    # any plan or mesh: BucketedGenerator, ContinuousGenerator, GRPO.to_mesh
    "mesh": (ValueError, "a sharding plan or mesh"),
    # ContinuousGenerator(sharding_plan= / mesh=): the paged pool and caches
    "serving_plan": (ValueError, "a serving plan"),
    # GRPO.to_mesh: the learn state's placement
    "learn_mesh": (ValueError, "to_mesh"),
    # GRPO.attach_rollout_fleet: the fleet's prefill workers
    "fleet": (NotImplementedError, "attach_rollout_fleet"),
    # GRPO.attach_rollout_fleet and ContinuousGenerator.submit_prefilled
    "prefill_transfer": (NotImplementedError,
                         "the fleet's prefill-to-decode transfer "
                         "(attach_rollout_fleet, submit_prefilled)"),
    # GRPO(sequence_parallel_axis=...): ring attention over sequence shards
    "sequence_parallel": (NotImplementedError, "sequence_parallel_axis"),
}


class Unplaced(NamedTuple):
    """A ``learn_mesh`` entry that refuses only where the plan's 'params'
    rules leave one of the stack's own leaves unplaced: a plan written for
    attention stacks would replicate them silently, one that names them
    all places the stack."""
    leaves: str


#: a stack kind -> {feature: why it refuses}; a feature a kind does not
#: list, it does
CANNOT = {
    "window": {
        "speculate": "the multi-token verify step (speculate."
                     "paged_verify_step) is not carried over to a window by "
                     "layer",
        "mesh": "the period scan's weights (params['runs'][r] a list, one "
                "tree a position in the period) have no plan rule, and the "
                "windowed kernels have not been run on a mesh",
        "fleet": "the fleet's prefill workers size their prompt grid "
                 "themselves and have not run a window by layer",
        "sequence_parallel": "ring attention has no window and no "
                             "positions by layer",
    },
    "cca": {
        "speculate": "the verify step advances the attention layers' "
                     "rolling state (convolution windows and the previous "
                     "token's value half) past rejected drafts and nothing "
                     "rolls it back",
        "serving_plan": "no rule places the rolling-state cache (per-slot "
                        "convolution windows and value half beside the "
                        "paged pool) nor the leaves conv0_*, conv1_*, tau, "
                        "wv1, wv2, router_*, merge*",
        "learn_mesh": Unplaced("its leaves (wv1, wv2, conv0_w, conv1_w, "
                               "tau, router_in, router_w1, merge1, ...)"),
        "prefill_transfer": "the export carries prompt KV only, not the "
                            "attention layers' rolling state (convolution "
                            "windows and value half, nor its snapshot)",
        "sequence_parallel": "a shard's first positions would lose the "
                             "rolling state (the previous shard's last "
                             "convolution inputs and value half)",
    },
    "mamba2": {
        "speculate": "the verify step advances the per-slot SSM and "
                     "convolution state past rejected drafts and nothing "
                     "rolls it back",
        "serving_plan": "no rule places the state cache (per-slot SSM and "
                        "convolution state beside the paged pool) nor the "
                        "leaves in_proj, conv_w, A_log, lat_down, ...",
        "learn_mesh": "no plan rule places its leaves (in_proj, conv_w, "
                      "A_log, lat_down, the held experts) nor its experts "
                      "over an ep axis",
        "prefill_transfer": "the export carries prompt KV only, not the "
                            "Mamba-2 layers' SSM and convolution state (nor "
                            "its snapshot)",
    },
    "latent": {
        "speculate": "the multi-token verify step (speculate."
                     "paged_verify_step, model.paged_scatter_multi) is not "
                     "carried over to the one-array pool",
        "serving_plan": "no rule places a latent cache's pool (one array, "
                        "one head) nor the leaves wkv_a, wkv_b, kv_norm",
        "learn_mesh": Unplaced("the latent attention leaves (wkv_a, wkv_b, "
                               "kv_norm)"),
        "prefill_transfer": "the export and the import (model."
                            "paged_scatter_prompt through _admit_import) "
                            "carry K and V arrays, not the one latent array",
        "sequence_parallel": "ring attention runs over GQA keys and values",
    },
    "dropless": {
        "speculate": "the multi-token verify step (speculate."
                     "paged_verify_step, model.paged_scatter_multi) is not "
                     "carried over to the decode chunk's expert counts",
        "serving_plan": "no rule places the expert leaves (router_bias, "
                        "ws_*)",
        "learn_mesh": Unplaced("the expert leaves (router_bias, ws_gate, "
                               "ws_up, ws_down)"),
        "sequence_parallel": "the long-context path shards tokens without "
                             "an expert exchange",
    },
    "hybrid": {
        "speculate": "the verify step advances the recurrent state past "
                     "rejected drafts: it needs recurrent-state rollback",
        "serving_plan": "no rule places the recurrent-state cache (per-slot "
                        "conv and SSM state beside the paged pool)",
        "learn_mesh": Unplaced("the state-space layers' leaves (in_proj, "
                               "conv_w, x_proj, dt_proj, A_log, out_proj, "
                               "...)"),
        "prefill_transfer": "the export carries prompt KV only, not the "
                            "state-space layers' recurrent state (nor its "
                            "snapshot)",
        "sequence_parallel": "a state-space layer's recurrence would have "
                             "to hand its state from shard to shard",
    },
}


def stack_kinds(config: GPTConfig) -> Tuple[str, ...]:
    """The kinds of ``STACK_KINDS`` that ``config`` is, in their order."""
    return tuple(k for k, (_, of) in STACK_KINDS.items() if of(config))


def refuse(config: GPTConfig, *features: str, unplaced=None) -> None:
    """Raise for the first of ``config``'s kinds that cannot do one of
    ``features`` (``CANNOT``), that feature's exception naming the kind and
    why; return where none refuses. ``unplaced()`` -> the error of the
    plan's strict resolution of the base, or None: what an ``Unplaced``
    entry refuses on."""
    for kind in stack_kinds(config):
        name = STACK_KINDS[kind][0]
        for feature in features:
            why = CANNOT[kind].get(feature)
            if why is None:
                continue
            error, what = FEATURES[feature]
            if isinstance(why, Unplaced):
                err = unplaced()
                if err is not None:
                    raise error(
                        f"{what} over {name}: the plan's 'params' rules do "
                        f"not cover {why.leaves}: {err}") from err
                continue
            raise error(f"{what} over {name}: {why}; not implemented")


# --------------------------------------------------------------------------- #
# Notes kept below the code. A Mosaic kernel's body holds the file and line
# of every Python frame above its pallas_call, so a line added above a
# kernel's call frames in this file (``forward``, ``forward_paged``,
# ``_run_layers``, ``token_logprobs``, ...) changes the program text, and the
# compile-cache key, of every stack that runs the kernel. PR 40 made room
# for the pattern stack's dispatch by condensing comments up there; their
# full text is here, by the name they belong to.
#
# GPTConfig.scan_layers: every run of structurally equal layers rolls into
# ONE lax.scan on every path — training/logprob AND the KV-cached
# prefill/decode paths (the cache stacks all layers on a leading axis, so
# per-layer k/v ride as scan xs/ys): HLO size and XLA:TPU compile time
# become ~constant in n_layer instead of linear (the first live-chip window
# measured the unrolled 12-layer GRPO learn-step compile at >15 min against
# 35s for the rest of the program set). False calls every layer directly:
# the reference the tests hold the scan to.
#
# GPTConfig.flash_shard_axes: ((batch axes...), (head axes...)) mesh-axis
# names: wrap the flash kernel in an explicit shard_map over the active mesh
# — the AOT-compatible pod-scale route (Mosaic kernels can't be GSPMD
# auto-partitioned, and custom_partitioning's python callback is absent from
# compile-only PJRT clients). None = plain call (single chip, or runtime
# GSPMD via the kernel's custom partitioning).
#
# GPTConfig.fused_loss_shard_axes: (batch axes...) for the fused lm-head loss
# kernel: rows shard over these axes inside a shard_map, the head stays
# replicated per shard, and shard_map's transpose psums the dW cotangent
# automatically. The right mode for fsdp-only meshes; on tp-sharded pods
# prefer the chunked XLA loss (make_update_fn use_fused_loss=False) — a
# vocab-sharded softmax is XLA's game.
#
# GPTConfig.attn_layer_period / attn_layer_offset: layer i is an ATTENTION
# layer iff i % attn_layer_period == attn_layer_offset, every other layer a
# state-space (Mamba-1) layer (llm/ssm.py). Period 1 = attention everywhere,
# the uniform stack. Consecutive equal layers run as one lax.scan over
# stacked weights (_run_layers), so a program holds one body a RUN of
# layers, not one a layer.
#
# GPTConfig.kv_lora_rank: latent attention (llm/mla.py) in place of GQA in
# every attention layer iff kv_lora_rank > 0: keys and values are
# up-projected from a latent of kv_lora_rank values a token, queries and
# keys are qk_nope_dim + qk_rope_dim wide (rotary on the last qk_rope_dim,
# carried by ONE key head), values v_head_dim wide. A cache keeps the latent
# alone.
#
# _run_layers: a run of structurally equal layers is one lax.scan over its
# stacked weights, so the program holds one body a run — a uniform stack is
# one run. A run of one, a run whose adapters differ from layer to layer,
# and scan_layers=False call the layers directly. Where the weights come
# from is the one thing that differs between stacks: a hybrid stack stores
# them stacked a run (params["runs"][r]), any other one tree a layer
# (params["blocks"][str(i)]), stacked here inside the program.
#
# forward: with a cache, tokens are appended at cache.length (all rows share
# a length — use left-padding for ragged prompts so positions/masks do the
# aligning). return_aux adds the layers' summed aux: the capacity
# dispatch's balance loss, or over a dropless expert stack [sum of load max
# over mean, experts hit] (a pattern stack's adds the pairs that fell on
# held experts).
#
# forward_paged: return_aux (dropless expert stacks) adds a third result,
# the layers' summed aux as forward returns it. Per-slot write_pos is what
# distinguishes this from forward-with-cache: continuous batching admits
# slots at different times, so there is no shared scalar cache length.
# Greedy outputs are bit-identical to the dense path because the
# projection/FFN maths is the SAME code (_qkv_rope/_block_ffn), the
# attention loop is the same code too, and masked positions contribute
# exact zeros to the softmax.
#
# Why a pattern stack has its own forward (llm/nemotron_h.py) rather than
# layer kinds inside forward: its layers are ONE block each (x + block(
# rms(x)), no FFN after a mixer), its Mamba-2 state and its latent experts'
# share need their own cache and aux plumbing, and the 11 letters have no
# repeating period to scan; putting them in forward's layer loop would add
# lines above every kernel's call frame here, and change every cell's
# programs.
# --------------------------------------------------------------------------- #
