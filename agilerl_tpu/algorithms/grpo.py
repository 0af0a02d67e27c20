"""GRPO — group-relative policy optimisation for LLM finetuning
(parity: agilerl/algorithms/grpo.py — group sampling get_action:259,
group-relative advantage _calculate_advantage:409, clipped-ratio + k3-KL loss
_grpo_loss_standard:517, learn:321 recomputes old/ref logprobs then runs
update_epochs minibatch epochs, test:380; and the LLMAlgorithm adapter design
core/base.py:1894 — actor/reference as two LoRA subtrees over one frozen base).

TPU-first deltas vs the reference:
- no vLLM: generation is the in-tree jitted decode loop (llm/generate.py)
  sharing the training param tree — no weight hot-swap, no engine sleep/wake;
- no DeepSpeed: the base params + LoRA live in one pytree that parallel/mesh.py
  shards GSPMD-style (fsdp/tp axes);
- the fused chunked loss (ops/fused_loss.py) replaces Liger's Triton kernel;
- a learn call of exactly one optimizer step recomputes the reference
  logprobs only: the ratio's anchor is the update's own logprobs under
  stop_gradient (_grpo_loss_core), not a second no-grad forward.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from agilerl_tpu.ops import pallas_enabled

from agilerl_tpu.algorithms.core.base import EvolvableAlgorithm
from agilerl_tpu.algorithms.core.optimizer import (
    CosineLRScheduleConfig,
    OptimizerWrapper,
)
from agilerl_tpu.algorithms.core.registry import (
    HyperparameterConfig,
    NetworkGroup,
    OptimizerConfig,
    RLParameter,
)
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.generate import generate
from agilerl_tpu.observability import PhaseTimer, get_registry


def default_hp_config() -> HyperparameterConfig:
    return HyperparameterConfig(
        lr=RLParameter(min=1e-8, max=1e-4, dtype=float),
        beta=RLParameter(min=1e-4, max=0.1, dtype=float),
        group_size=RLParameter(min=2, max=16, dtype=int),
    )


def _grpo_loss_core(lp, batch, clip, beta):
    """Clipped-ratio + k3-KL GRPO loss from per-token logprobs
    (parity: grpo.py:517 _grpo_loss_standard). Returns (loss, mean k3 KL).

    When the batch carries ``rho`` — the truncated per-token importance
    weight ``min(exp(old_lp - behavior_lp), rho_clip)`` the online flywheel
    computes between the learn-start policy (the ratio's anchor) and the
    BEHAVIOR epoch's logprobs (IMPALA lineage: Espeholt et al., V-trace's
    clipped behind-ness ratio) — it multiplies the policy-gradient term, so
    the combined ``ratio * rho`` applies the full truncated pi/mu
    correction exactly once and bounded-staleness off-policy data tilts
    the update instead of biasing it. ``rho`` is computed outside the grad
    (a constant under differentiation, like ``old_lp``); a batch without
    the key compiles the exact on-policy program as before.

    ``old_lp`` — the clipped ratio's anchor — is optional too. A batch
    without it anchors the ratio at the update's own masked logprobs under
    ``stop_gradient``: the ratio is 1 in value and ``d ratio / d theta =
    d lp / d theta``, which is what a no-grad pass over the same adapter
    and tokens gives up to the rounding between two compiled programs.
    That holds for the FIRST optimizer step from an adapter only, so
    ``GRPO.learn`` leaves the key out when a call takes exactly one step
    (TRL's ``GRPOTrainer`` at ``num_iterations == 1`` does the same). A
    batch that carries ``old_lp`` compiles the program it always did."""
    lp = lp * batch["loss_mask"]
    old_lp = batch.get("old_lp")
    if old_lp is None:
        old_lp = jax.lax.stop_gradient(lp)
    ratio = jnp.exp(lp - old_lp)
    adv = batch["advantage"][:, None]
    s1 = ratio * adv
    s2 = jnp.clip(ratio, 1 - clip, 1 + clip) * adv
    pg = -jnp.minimum(s1, s2)
    rho = batch.get("rho")
    if rho is not None:
        pg = pg * rho
    # k3 KL estimator vs the reference adapter (parity: grpo.py:517)
    log_ratio_ref = batch["ref_lp"] - lp
    kl = jnp.exp(log_ratio_ref) - log_ratio_ref - 1.0
    denom = jnp.maximum(batch["loss_mask"].sum(), 1.0)
    loss = ((pg + beta * kl) * batch["loss_mask"]).sum() / denom
    kl_mean = (kl * batch["loss_mask"]).sum() / denom
    return loss, kl_mean


class _LoraNet:
    """Minimal network-shaped holder so the registry/clone machinery sees the
    adapter as an evolvable attribute (configs never mutate for LLMs — the
    reference blocks arch mutations too, training/train_llm.py:97-109)."""

    def __init__(self, config, params):
        self.config = config
        self.params = params


def make_update_fn(config, tx, lora_scale: float, use_flash: bool,
                   use_fused_loss: Optional[bool] = None):
    """The production GRPO update as a pure function of (base, lora,
    opt_state, batch, clip, beta). Base params ride as an ARGUMENT (not a
    closure) so AOT tooling can lower the exact training step from abstract
    ShapeDtypeStructs without materialising the weights — the 7B dress
    rehearsal (benchmarking/grpo_7b_plan.py) lowers this very function.

    ``use_fused_loss`` (default: follow ``use_flash``) routes the lm-head
    loss through the fused Pallas kernel. Keep it OFF for tp-sharded pod
    training: with the lm head sharded over tp, the log-softmax over vocab
    is a cross-shard reduction, and XLA's chunked sharded-matmul + psum path
    IS the right distributed algorithm — the fused kernel's win is the
    single-chip / serving hot path (flash attention, by contrast, is
    embarrassingly parallel over (batch, heads) and stays Pallas at any
    scale via its custom partitioning, ops/flash_attention_vjp.py)."""
    if use_fused_loss is None:
        use_fused_loss = use_flash
    # a dropless expert stack's update has a fifth result (a pattern stack's
    # a sixth too): M.load_stats. What the gauge moe/load_max_over_mean and
    # the counter moe/held_pairs show; no other stack's program has them
    extra = ({"return_aux": True} if config.is_dropless else {})

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def update(base, lora, opt_state, batch, clip, beta):
        def loss_fn(lo):
            out = M.token_logprobs(
                config, base, batch["tokens"], attention_mask=batch["mask"],
                lora=lo, lora_scale=lora_scale, flash=use_flash,
                use_pallas=use_fused_loss, **extra,
            )
            lp, *aux = out if extra else (out,)
            loss, kl = _grpo_loss_core(lp, batch, clip, beta)
            stats = M.load_stats(config, aux[0]) if extra else ()
            return loss, (kl, *stats)

        (loss, rest), grads = jax.value_and_grad(loss_fn, has_aux=True)(lora)
        updates, opt_state = tx.update(grads, opt_state, lora)
        lora = optax.apply_updates(lora, updates)
        return (lora, opt_state, loss, *rest)

    return update


class GRPO(EvolvableAlgorithm):
    supports_activation_mutation = False

    def __init__(
        self,
        config: M.GPTConfig,
        base_params: Any = None,
        pad_token_id: int = 0,
        eos_token_id: Optional[int] = None,
        index: int = 0,
        hp_config: Optional[HyperparameterConfig] = None,
        batch_size: int = 8,
        beta: float = 0.04,
        lr: float = 5e-6,
        clip_coef: float = 0.2,
        max_grad_norm: float = 0.1,
        update_epochs: int = 1,
        group_size: int = 8,
        temperature: float = 0.9,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        max_output_tokens: int = 64,
        min_output_tokens: Optional[int] = None,
        cosine_lr_schedule_config: Optional["CosineLRScheduleConfig"] = None,
        lora_rank: int = 8,
        lora_targets: Tuple[str, ...] = ("wq", "wv"),
        lora_scale: float = 2.0,
        sequence_parallel_axis: Optional[str] = None,
        bucketed_decode: bool = True,
        continuous_decode: bool = False,
        speculative_decode=None,
        capture_logprobs: bool = False,
        **kwargs,
    ):
        super().__init__(index=index, hp_config=hp_config or default_hp_config(), **kwargs)
        self.model_config = config
        self.pad_token_id = int(pad_token_id)
        self.eos_token_id = eos_token_id
        self.batch_size = int(batch_size)
        self.beta = float(beta)
        self.lr = float(lr)
        self.clip_coef = float(clip_coef)
        self.max_grad_norm = float(max_grad_norm)
        self.update_epochs = int(update_epochs)
        self.group_size = int(group_size)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.max_output_tokens = int(max_output_tokens)
        self.min_output_tokens = min_output_tokens
        self.cosine_lr_schedule_config = cosine_lr_schedule_config
        self.lora_rank = int(lora_rank)
        self.lora_targets = tuple(lora_targets)
        self.lora_scale = float(lora_scale)
        # long-context: shard the SEQUENCE over this mesh axis (ring attention)
        # — requires to_mesh() with a mesh containing the axis before learn()
        self.sequence_parallel_axis = sequence_parallel_axis
        # ragged generation with a bounded compile set (llm/serving.py — the
        # vLLM continuous-batching role); False = the dense generate, with
        # its exact RNG stream. The two flags are independent —
        # bucketed_decode=False with continuous_decode=True is a valid
        # continuous-only configuration.
        self.bucketed_decode = bool(bucketed_decode)
        # OPT-IN: rollouts through the continuous/paged serving tier
        # (llm/serving.ContinuousGenerator). Wins when prompts within a
        # learn batch are ragged in OUTPUT length (slots recycle per chunk
        # instead of the whole batch draining together) and group_size
        # repeats hit the prefix cache (one prefill per unique prompt).
        self.continuous_decode = bool(continuous_decode)
        # continuous-tier extras (NOT part of _serving_knobs: the bucketed
        # generator takes neither, and attach_rollout_fleet's recipe check
        # compares the SAMPLING contract — speculation never changes the
        # greedy stream and capture only adds a side channel)
        # speculative_decode: None/False off, True defaults, dict/SpecConfig
        # knobs (llm/speculate.SpecConfig) — continuous_decode only
        self.speculative_decode = speculative_decode
        # capture_logprobs: the continuous tier records each emitted token's
        # behavior logprob during decode so rollout_once skips the extra
        # dense behavior_logprobs forward (llm/flywheel.py)
        self.capture_logprobs = bool(capture_logprobs)
        self._bucketed_gen = None
        self._bucketed_gen_knobs = None
        self._continuous_gen = None
        self._continuous_gen_knobs = None
        # continuous rollouts route through this ServingFleet (router +
        # replicas) instead of a private bare generator when attached
        # (attach_rollout_fleet) — the flywheel rollout tier. Not part of
        # init_dict: clones/evolved children must be re-attached explicitly.
        self.rollout_fleet = None
        self.last_generation_info = None
        # ordinals the grpo/get_action and grpo/learn phases carry into a
        # profile (docs/observability.md, "Host phases")
        self._rollouts = 0
        self._learn_calls = 0

        if base_params is None:
            base_params = M.init_params(self.next_key(), config)
        self.base_params = base_params  # frozen
        # actor adapter (trainable) + reference adapter (frozen snapshot)
        self.actor = _LoraNet(
            config, M.init_lora(self.next_key(), config, lora_rank, self.lora_targets)
        )
        self.reference = _LoraNet(
            config, jax.tree_util.tree_map(jnp.copy, self.actor.params)
        )
        self.optimizer = OptimizerWrapper(
            optimizer="adamw", lr=self.lr, max_grad_norm=self.max_grad_norm,
            lr_schedule=cosine_lr_schedule_config,
        )
        self.register_network_group(NetworkGroup(eval="actor", policy=True))
        self.register_optimizer(
            OptimizerConfig(name="optimizer", networks=["actor"], lr="lr")
        )
        self.finalize_registry()
        self._reference_epoch = -1

    # ------------------------------------------------------------------ #
    @property
    def init_dict(self) -> Dict[str, Any]:
        return {
            "config": self.model_config,
            "base_params": self.base_params,  # shared reference, not copied
            "pad_token_id": self.pad_token_id,
            "eos_token_id": self.eos_token_id,
            "index": self.index,
            "batch_size": self.batch_size,
            "beta": self.beta,
            "lr": self.lr,
            "clip_coef": self.clip_coef,
            "max_grad_norm": self.max_grad_norm,
            "update_epochs": self.update_epochs,
            "group_size": self.group_size,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "top_p": self.top_p,
            "max_output_tokens": self.max_output_tokens,
            "min_output_tokens": self.min_output_tokens,
            "cosine_lr_schedule_config": self.cosine_lr_schedule_config,
            "lora_rank": self.lora_rank,
            "lora_targets": self.lora_targets,
            "lora_scale": self.lora_scale,
            "sequence_parallel_axis": self.sequence_parallel_axis,
            "bucketed_decode": self.bucketed_decode,
            "continuous_decode": self.continuous_decode,
            "speculative_decode": self.speculative_decode,
            "capture_logprobs": self.capture_logprobs,
        }

    def _on_clone(self, parent) -> None:
        self.reference.params = jax.tree_util.tree_map(jnp.copy, parent.reference.params)
        self._reference_epoch = parent._reference_epoch

    def set_reference_policy(self, epoch: int) -> None:
        """Refresh the reference adapter from the actor once per dataset epoch
        (parity: core/base.py:2544 — the adapter-copy replaces the reference's
        enable/disable-adapter trick)."""
        if epoch != self._reference_epoch:
            self.reference.params = jax.tree_util.tree_map(jnp.copy, self.actor.params)
            self._reference_epoch = epoch

    # ------------------------------------------------------------------ #
    def _serving_knobs(self):
        """The ONE sampling-recipe tuple both serving generators are built
        from — a knob added here reaches the bucketed and continuous paths
        together (they take identical constructor kwargs)."""
        return dict(
            max_new_tokens=self.max_output_tokens,
            pad_id=self.pad_token_id, eos_id=self.eos_token_id,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, min_new_tokens=self.min_output_tokens,
            lora_scale=self.lora_scale,
        )

    def _get_bucketed_generator(self):
        """Lazily build (and rebuild on knob change) the bounded-compile
        ragged generator (llm/serving.py)."""
        from agilerl_tpu.llm.serving import BucketedGenerator

        knobs = self._serving_knobs()
        if self._bucketed_gen is None or self._bucketed_gen_knobs != knobs:
            self._bucketed_gen = BucketedGenerator(self.model_config, **knobs)
            self._bucketed_gen_knobs = knobs
        return self._bucketed_gen

    def _get_continuous_generator(self):
        """Lazily build (and rebuild on knob change) the continuous/paged
        serving-tier generator (llm/serving.ContinuousGenerator). GRPO
        rollouts are the no-shed path: every row must come back."""
        from agilerl_tpu.llm.serving import ContinuousGenerator

        knobs = dict(self._serving_knobs(),
                     speculate=self.speculative_decode,
                     capture_logprobs=self.capture_logprobs)
        if self._continuous_gen is None or self._continuous_gen_knobs != knobs:
            self._continuous_gen = ContinuousGenerator(
                self.model_config, **knobs)
            self._continuous_gen_knobs = knobs
        return self._continuous_gen

    def attach_rollout_fleet(self, fleet) -> None:
        """Route continuous rollouts through a
        :class:`~agilerl_tpu.llm.fleet.ServingFleet` — prefix-affinity
        routing over N replicas instead of a private bare generator, the
        flywheel rollout tier's horizontal-scale path. The fleet's sampling
        recipe must match this agent's (same generate() key-fold contract,
        so a fleet and a bare generator given the same key produce
        identical streams); a mismatch would silently change the rollout
        distribution, so it is rejected here. Sets ``continuous_decode``.
        Pass None to detach (restores the pre-attach ``continuous_decode``
        setting — detaching must not leave the agent on a private bare
        generator it never used before)."""
        M.refuse(self.model_config, "fleet", "prefill_transfer")
        if fleet is None:
            if self.rollout_fleet is not None:
                self.continuous_decode = self._pre_fleet_continuous_decode
            self.rollout_fleet = None
            return
        ref = fleet._grid_ref()
        theirs = dict(
            max_new_tokens=ref.max_new_tokens, pad_id=ref.pad_id,
            eos_id=ref.eos_id, temperature=ref.temperature,
            top_k=ref.top_k, top_p=ref.top_p,
            min_new_tokens=ref.min_new_tokens, lora_scale=ref.lora_scale,
        )
        mine = self._serving_knobs()
        if theirs != mine:
            raise ValueError(
                f"fleet sampling recipe {theirs} does not match this "
                f"agent's serving knobs {mine}; build the fleet from the "
                "same recipe (ContinuousGenerator kwargs) as the agent")
        if self.rollout_fleet is None:
            self._pre_fleet_continuous_decode = self.continuous_decode
        self.rollout_fleet = fleet
        self.continuous_decode = True

    def get_action(self, prompts: Dict[str, np.ndarray], training: bool = True):
        """Generate group_size completions per prompt
        (parity: grpo.py:259; the vLLM wake/swap/gather dance collapses into one
        jitted generate call). prompts: {"input_ids": [B, P], "attention_mask"}.
        Returns (completion_ids [B*G, N], completion_mask [B*G, N]).

        With ``bucketed_decode`` (default), ragged prompt batches route
        through llm/serving.BucketedGenerator: compile count is bounded by
        the bucket grid instead of one program per (B, P), and decode stops
        within one chunk of every row hitting EOS (the vLLM continuous-
        batching role). With ``continuous_decode`` (opt-in), rollouts route
        through the paged continuous scheduler instead: short completions
        free their slot for queued rows per chunk, and group_size repeats of
        a prompt prefill once via the prefix cache (docs/serving.md). Telemetry lands in
        ``last_generation_info``."""
        self._rollouts += 1
        with PhaseTimer(get_registry(), "grpo/get_action",
                        rollout=self._rollouts):
            return self._get_action(prompts, training)

    def _get_action(self, prompts, training: bool):
        ids_np = np.asarray(prompts["input_ids"])
        mask_np = np.asarray(prompts["attention_mask"])
        g = self.group_size if training else 1
        ids_np = np.repeat(ids_np, g, axis=0)
        mask_np = np.repeat(mask_np, g, axis=0)
        if ids_np.shape[0] == 0:
            N = self.max_output_tokens
            self.last_generation_info = None
            return np.zeros((0, N), np.int32), np.zeros((0, N), np.int32)
        if self.continuous_decode:
            # fleet-attached rollouts go through the router (affinity +
            # least-loaded over N replicas); same generate() contract and
            # per-row key fold as the bare generator, so the streams are
            # token-for-token identical (tests/test_llm/test_flywheel.py)
            gen = (self.rollout_fleet if self.rollout_fleet is not None
                   else self._get_continuous_generator())
            row_lens = mask_np.sum(axis=1)
            longest = int(row_lens.max()) if mask_np.size else 0
            # an all-pad row has no prompt to admit — dense path handles it
            if int(row_lens.min() if mask_np.size else 0) > 0 and \
                    gen.fits(ids_np.shape[0], longest):
                seqs = [row[m.astype(bool)]
                        for row, m in zip(ids_np, mask_np)]
                comp, cmask, self.last_generation_info = \
                    self._continuous_rollout(gen, seqs, training)
                return comp, cmask
            # prompt too long for the bucket grid: dense path below
        elif self.bucketed_decode:
            gen = self._get_bucketed_generator()
            longest = int(mask_np.sum(axis=1).max()) if mask_np.size else 0
            if gen.fits(ids_np.shape[0], longest):
                seqs = [row[m.astype(bool)]
                        for row, m in zip(ids_np, mask_np)]
                comp, cmask, self.last_generation_info = gen.generate(
                    seqs, self.next_key(), self.base_params,
                    lora=self.actor.params, greedy=not training,
                )
                return comp, cmask
            # too many rows / too long for the bucket grid: dense path
        self.last_generation_info = None  # no stale bucketed telemetry
        comp, cmask = generate(
            self.model_config, self.base_params, jnp.asarray(ids_np),
            jnp.asarray(mask_np), self.next_key(),
            max_new_tokens=self.max_output_tokens, lora=self.actor.params,
            lora_scale=self.lora_scale,
            temperature=self.temperature if training else 0.0,
            top_k=self.top_k, top_p=self.top_p,
            min_new_tokens=self.min_output_tokens,
            eos_id=self.eos_token_id, pad_id=self.pad_token_id,
        )
        return np.asarray(comp), np.asarray(cmask)

    # ------------------------------------------------------------------ #
    @staticmethod
    def _calculate_advantage(rewards: jax.Array, eps: float = 1e-4) -> jax.Array:
        """Group z-score (parity: grpo.py:409). rewards [B, G] -> [B*G]."""
        mean = rewards.mean(axis=1, keepdims=True)
        std = rewards.std(axis=1, keepdims=True)
        return ((rewards - mean) / (std + eps)).reshape(-1)

    def _logprob_fn(self):
        config = self.model_config
        base = self.base_params
        scale = self.lora_scale
        # no-grad passes use the fused Pallas lm-head kernel on TPU
        use_pallas = pallas_enabled()

        # base rides as an ARGUMENT, like make_update_fn's: a jit that closes
        # over the weights bakes them into the program as literals (a second
        # copy of the model in HBM, and a lowering that never ends at 7B
        # widths)
        @jax.jit
        def logprobs(base, lora, tokens, mask):
            return M.token_logprobs(
                config, base, tokens, attention_mask=mask, lora=lora,
                lora_scale=scale, use_pallas=use_pallas, flash=use_pallas,
            )

        def bound(lora, tokens, mask):
            return logprobs(base, lora, tokens, mask)

        return bound

    def _update_fn(self):
        base = self.base_params
        # both Pallas kernels carry custom VJPs (flash_attention_vjp.py,
        # fused_loss.py), so the TRAINING loss runs fully fused on TPU
        update = make_update_fn(
            self.model_config, self.optimizer.tx, self.lora_scale,
            use_flash=pallas_enabled(),
        )

        def bound(lora, opt_state, batch, clip, beta):
            return update(base, lora, opt_state, batch, clip, beta)

        return bound

    # -- sequence-parallel (long-context) variants ---------------------- #
    def _require_sp_mesh(self):
        axis = self.sequence_parallel_axis
        mesh = getattr(self, "mesh", None)
        if mesh is None or axis not in mesh.axis_names:
            raise RuntimeError(
                f"sequence_parallel_axis={axis!r} requires to_mesh() with a "
                f"mesh containing that axis (got {getattr(mesh, 'axis_names', None)})"
            )
        return mesh, axis

    def _sp_logprob_fn(self):
        from agilerl_tpu.llm.long_context import make_sp_logprob_fn

        mesh, axis = self._require_sp_mesh()
        fn = make_sp_logprob_fn(
            self.model_config, mesh, axis_name=axis, lora_scale=self.lora_scale
        )
        base = self.base_params

        @jax.jit
        def logprobs(lora, tokens, mask):
            # ring attention is causal over the real+pad suffix; pads are
            # excluded from the loss via loss_mask (right-padding constraint,
            # llm/long_context.py)
            return fn(base, lora, tokens)

        return logprobs

    def _sp_update_fn(self):
        from agilerl_tpu.llm.long_context import make_sp_logprob_fn

        mesh, axis = self._require_sp_mesh()
        sp_fn = make_sp_logprob_fn(
            self.model_config, mesh, axis_name=axis, lora_scale=self.lora_scale
        )
        base = self.base_params
        tx = self.optimizer.tx

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(lora, opt_state, batch, clip, beta):
            def loss_fn(lo):
                lp = sp_fn(base, lo, batch["tokens"])
                return _grpo_loss_core(lp, batch, clip, beta)

            (loss, kl), grads = jax.value_and_grad(loss_fn, has_aux=True)(lora)
            updates, opt_state = tx.update(grads, opt_state, lora)
            lora = optax.apply_updates(lora, updates)
            return lora, opt_state, loss, kl

        return update

    def learn(self, experiences: Tuple) -> Tuple[float, float]:
        """experiences = (ids, action_masks, rewards[, attention_mask]):
        ids [B*G, P+N] full prompt+completion sequences, action_masks [B*G, P+N-1]
        marking completion-token predictions, rewards [B, G]; pass the optional
        4th element when a prompt holds pad_token_id as a real token (without
        it: ids != pad_token_id, and every completion token the mask marks)
        (parity: grpo.py:321). Returns (mean loss, mean k3 KL vs reference).

        A call runs the reference adapter's no-grad pass, then the update.
        The clipped ratio's anchor (the actor's logprobs at learn start)
        costs a second no-grad pass only when the call takes more than one
        optimizer step (``update_epochs > 1``, or more rows than
        ``batch_size``): the later steps start from an adapter that has
        moved. A call of exactly one step anchors the ratio at the update's
        own logprobs under ``stop_gradient`` (:func:`_grpo_loss_core`) —
        the same update, without recomputing a value the update's forward
        already holds. Decided per call from ``update_epochs``,
        ``batch_size`` and the batch's rows (HPO may mutate them); counted
        in ``grpo/anchor_reused_total`` / ``grpo/anchor_recomputed_total``.

        With ``sequence_parallel_axis`` set (and ``to_mesh`` called with a mesh
        containing that axis), every forward — old/ref logprobs AND the
        differentiable update — runs with the sequence sharded across the axis
        via ring attention (llm/long_context.py); sequences must be
        right-padded and T divisible by the axis size."""
        if len(experiences) == 4:
            ids, action_masks, rewards, attn = experiences
        else:
            ids, action_masks, rewards = experiences
            attn = None
        metrics = get_registry()
        self._learn_calls += 1
        with PhaseTimer(metrics, "grpo/learn", call=self._learn_calls):
            with PhaseTimer(metrics, "learn/prepare"):
                ids, mask, loss_mask = self._learn_masks(
                    ids, action_masks, attn)
                rewards = jnp.asarray(rewards, jnp.float32)
                advantage = self._calculate_advantage(rewards)
                logprobs, update = self._resolve_learn_fns(ids, mask)
            # one optimizer step from the adapter the anchor would be
            # computed under: the update's own forward is that anchor
            single_step = (self.update_epochs == 1
                           and ids.shape[0] <= self.batch_size)
            with PhaseTimer(metrics, "learn/logprobs"):
                old_lp = None if single_step else (
                    logprobs(self.actor.params, ids, mask) * loss_mask)
                ref_lp = logprobs(self.reference.params, ids, mask) * loss_mask
            if single_step:
                metrics.counter(
                    "grpo/anchor_reused_total",
                    help="learn calls of one optimizer step: the ratio's "
                         "anchor is the update's own logprobs, no anchor pass",
                ).inc()
            else:
                metrics.counter(
                    "grpo/anchor_recomputed_total",
                    help="learn calls of several optimizer steps: the "
                         "ratio's anchor took a no-grad pass of its own",
                ).inc()
            return self._run_update_epochs(
                update, ids, mask, loss_mask, old_lp, ref_lp, advantage)

    def _resolve_learn_fns(self, ids, mask):
        """(logprobs, update) for the active parallelism mode, with the
        sequence-parallel input contract validated against THIS batch."""
        if self.sequence_parallel_axis is not None:
            M.refuse(self.model_config, "sequence_parallel")
            mesh, axis = self._require_sp_mesh()
            sp_size = mesh.shape[axis]
            if ids.shape[1] % sp_size:
                raise ValueError(
                    f"sequence length {ids.shape[1]} not divisible by "
                    f"sp axis size {sp_size}"
                )
            # ring attention carries no key-padding mask: correctness relies
            # on RIGHT padding (causal attention never lets real tokens attend
            # pads; pad-position outputs are excluded via loss_mask). Reject
            # anything else instead of silently computing wrong logprobs.
            m = np.asarray(mask)
            if (np.diff(m, axis=1) > 0).any():
                raise ValueError(
                    "sequence_parallel_axis requires right-padded sequences "
                    "(attention mask must be non-increasing per row)"
                )
            return (self.jit_fn("sp_logprobs", self._sp_logprob_fn),
                    self.jit_fn("sp_update", self._sp_update_fn))
        # NOT cacheable (executable store): these factories close over the
        # frozen base weights — a captured constant is fingerprint-SAFE
        # (its literal lands in the lowered text, so value skew is a miss)
        # but materialising that text at 7B scale is prohibitive, and
        # _update_fn returns a plain closure with no .lower at all. The
        # store-backed layout path is parallel/layout_search +
        # compile_step_with_plan, where weights are ARGUMENTS; caching
        # these fns awaits the base-as-argument factory refactor
        # (ROADMAP item 5 follow-up).
        return (self.jit_fn("logprobs", self._logprob_fn),
                self.jit_fn("update", self._update_fn))

    def _run_update_epochs(self, update, ids, mask, loss_mask, old_lp,
                           ref_lp, advantage, rho=None):
        """The shared minibatch-epoch engine behind :meth:`learn` and
        :meth:`learn_from_trajectory` (one home for permutation order, the
        donated-buffer bookkeeping, and the NaN guard — the two entry
        points cannot drift). ``rho`` (per-token truncated importance
        weights, or None) rides into each minibatch dict; so does
        ``old_lp``, and None leaves the key out: the update then anchors
        the ratio at its own logprobs (:func:`_grpo_loss_core`), which is
        right for a call of one optimizer step and for no other."""
        lora, opt_state = self.actor.params, self.optimizer.opt_state
        n_rows = ids.shape[0]
        total, total_kl, n_updates = 0.0, 0.0, 0
        metrics = get_registry()
        for _ in range(self.update_epochs):
            with PhaseTimer(metrics, "learn/shuffle"):
                perm = np.asarray(
                    jax.random.permutation(self.next_key(), n_rows))
            for s in range(0, n_rows, self.batch_size):
                with PhaseTimer(metrics, "learn/minibatch"):
                    idx = perm[s : s + self.batch_size]
                    batch = {
                        "tokens": ids[idx],
                        "mask": mask[idx],
                        "loss_mask": loss_mask[idx],
                        "ref_lp": ref_lp[idx],
                        "advantage": advantage[idx],
                    }
                    if old_lp is not None:
                        batch["old_lp"] = old_lp[idx]
                    if rho is not None:
                        batch["rho"] = rho[idx]
                with PhaseTimer(metrics, "learn/update"):
                    lora, opt_state, loss, kl, *load = update(
                        lora, opt_state, batch, jnp.float32(self.clip_coef),
                        jnp.float32(self.beta),
                    )
                with PhaseTimer(metrics, "learn/loss_sync"):
                    if load:  # a dropless expert stack's update alone:
                        # the gauge moe/load_max_over_mean and, a pattern
                        # stack's alone, the counter moe/held_pairs (the
                        # routed pairs that fell on the held experts). At
                        # the file's end: see the note there on line numbers
                        _note_load(metrics, load)
                    if not np.isfinite(float(loss)):
                        # the update donated the previous buffers — store the
                        # (live) returned state first so the agent stays
                        # usable/savable
                        self.actor.params = lora
                        self.optimizer.opt_state = opt_state
                        raise RuntimeError(
                            f"Non-finite GRPO loss {float(loss)} — aborting "
                            "(parity: grpo.py:370 NaN guard)"
                        )
                    total += float(loss)
                    total_kl += float(kl)
                n_updates += 1
        self.actor.params = lora
        self.optimizer.opt_state = opt_state
        n = max(n_updates, 1)
        return total / n, total_kl / n

    def _learn_masks(self, ids, action_masks, attention_mask):
        """(ids, attention mask, loss mask) as jnp arrays — the shared batch
        preamble of every learn surface."""
        ids = jnp.asarray(ids)
        if attention_mask is not None:
            mask = jnp.asarray(attention_mask, jnp.int32)
        else:  # a completion token counts whatever its id (_attention_mask)
            mask = _attention_mask(ids, action_masks, self.pad_token_id)
        return ids, mask, jnp.asarray(action_masks, jnp.float32)

    def behavior_logprobs(self, ids, action_masks,
                          attention_mask=None) -> np.ndarray:
        """Per-token logprobs of ``ids`` under the CURRENT actor adapter,
        masked to completion predictions — the behavior-policy record a
        flywheel rollout pod captures at decode time and ships with each
        trajectory batch, standing in for the on-policy path's recomputed
        old logprobs (the learner recomputes nothing; llm/flywheel.py)."""
        ids, mask, loss_mask = self._learn_masks(
            ids, action_masks, attention_mask)
        logprobs, _ = self._resolve_learn_fns(ids, mask)
        return np.asarray(logprobs(self.actor.params, ids, mask) * loss_mask)

    def learn_from_trajectory(
        self,
        ids,
        action_masks,
        rewards,
        behavior_lp,
        attention_mask=None,
        rho_clip: Optional[float] = 2.0,
    ) -> Tuple[float, float]:
        """Staleness-aware off-policy GRPO update — the flywheel learner's
        surface (llm/flywheel.py; ROADMAP item 3).

        ``behavior_lp`` is the per-token completion logprob record captured
        under the BEHAVIOR adapter (the weight epoch the completions were
        decoded under; :meth:`behavior_logprobs`). The clipped-surrogate
        anchor ``old_lp`` stays what it is on-policy — the CURRENT adapter's
        logprobs recomputed at learn start (so the PPO ratio only meters
        within-learn-step drift, exactly as :meth:`learn`) — and, unless
        ``rho_clip`` is None, the decode→learn staleness is corrected ONCE
        by the truncated per-token importance weight
        ``rho = min(exp(old_lp - behavior_lp), rho_clip)`` (the IMPALA /
        V-trace clipped behind-ness ratio between the learn-start policy
        and the behavior epoch, computed once outside the grad) multiplying
        the policy-gradient term. The combined weight ``ratio * rho`` is
        the full truncated pi/mu correction applied exactly once —
        anchoring the ratio at ``behavior_lp`` AND multiplying by rho would
        double-count the staleness (rho^2 suppression of behind samples).
        The learner never needs the behavior ADAPTER, only its shipped
        logprob record. With the learner's adapter still AT the behavior
        epoch (staleness 0), ``old_lp == behavior_lp`` and ``rho == 1``
        exactly — the update reproduces :meth:`learn` on the same batch,
        the flywheel's synchronous-mode equivalence contract. With
        ``rho_clip=None`` the staleness is deliberately IGNORED (the
        uncorrected ablation), not hidden behind a behavior-anchored
        ratio."""
        ids, mask, loss_mask = self._learn_masks(
            ids, action_masks, attention_mask)
        rewards = jnp.asarray(rewards, jnp.float32)
        advantage = self._calculate_advantage(rewards)
        logprobs, update = self._resolve_learn_fns(ids, mask)

        old_lp = logprobs(self.actor.params, ids, mask) * loss_mask
        ref_lp = logprobs(self.reference.params, ids, mask) * loss_mask
        rho = None
        if rho_clip is not None:
            # re-masking is idempotent for a 0/1 mask — shipped records are
            # already masked, but a hand-built batch may not be
            behavior = jnp.asarray(behavior_lp, jnp.float32) * loss_mask
            rho = jnp.minimum(jnp.exp(old_lp - behavior),
                              jnp.float32(rho_clip))
        return self._run_update_epochs(
            update, ids, mask, loss_mask, old_lp, ref_lp, advantage, rho=rho)

    # ------------------------------------------------------------------ #
    def test(self, env) -> float:
        """Greedy-decode the FULL eval split and average the reward
        (parity: grpo.py:380 — the reference iterates its whole test loader;
        a fixed-slice eval would rank tournament members on the same handful
        of prompts every generation)."""
        all_rewards = []
        batches = env.eval_batches() if hasattr(env, "eval_batches") else [
            env.reset(eval_mode=True)
        ]
        for prompts in batches:
            comp, cmask = self.get_action(prompts, training=False)
            _, rewards = env.step_eval(comp, cmask)
            all_rewards.append(np.ravel(np.asarray(rewards)))
        fitness = float(np.mean(np.concatenate(all_rewards)))
        self.fitness.append(fitness)
        return fitness

    def to_mesh(self, mesh=None, plan=None) -> None:
        """Place base params, adapters and optimizer state with real GSPMD
        shardings — the one-call DeepSpeed-config replacement (parity
        contrast: _configure_batch_size/ZeRO plumbing,
        core/base.py:2961-3009).

        Now a thin wrapper over the declarative rule engine: pass ``mesh``
        to resolve through the built-in GRPO rule set
        (``parallel/plan.grpo_plan_for_mesh``), or ``plan`` (a
        :class:`~agilerl_tpu.parallel.plan.ShardingPlan` or registered plan
        name) to use a custom layout — its mesh is built from the plan's
        axis spec when ``mesh`` is omitted. Axes the mesh doesn't carry
        (e.g. an sp-only long-context mesh) fall back to replication."""
        from agilerl_tpu.parallel import plan as PL

        if plan is None:
            if mesh is None:
                raise ValueError("to_mesh needs a mesh or a plan")
            plan = PL.grpo_plan_for_mesh(mesh)
        plan, mesh = PL.resolve_plan_and_mesh(plan, mesh)

        def unplaced():
            try:
                plan.shardings("params", self.base_params, mesh, strict=True)
            except PL.UnmatchedLeafError as err:
                return err

        M.refuse(self.model_config, "mesh", "learn_mesh", unplaced=unplaced)

        # cached logprob/update closures capture the OLD base_params (and, for
        # sp fns, the old mesh) — drop them so learn() rebuilds against the
        # re-placed params
        self._clear_jit_cache()

        self.base_params = plan.place("params", self.base_params, mesh)
        self.actor.params = plan.place("lora", self.actor.params, mesh)
        self.reference.params = plan.place("lora", self.reference.params, mesh)
        self.optimizer.opt_state = plan.place(
            "optimizer", self.optimizer.opt_state, mesh
        )
        self.mesh = mesh
        self.sharding_plan = plan

    def clean_up(self) -> None:
        """Free cached jit executables (parity: core/base.py:2335 clean_up —
        the DeepSpeed-engine teardown has no analogue; XLA buffers free with
        the params)."""
        self._clear_jit_cache()

    def _continuous_rollout(self, gen, seqs, training: bool):
        """``gen.generate`` (a ``ContinuousGenerator`` or a fleet) over the
        matrices in the compute type: cast once a rollout, not once inside
        every prefill and decode-chunk program, and let go again before
        ``learn`` needs the memory (``serving.rollout_weights``). The
        masters stay what ``base_params`` holds; a base already stored in
        the compute type is handed over as it is."""
        from agilerl_tpu.llm.serving import rollout_weights

        with rollout_weights(gen, self.model_config,
                             self.base_params) as weights:
            return gen.generate(
                seqs, self.next_key(), weights,
                lora=self.actor.params, greedy=not training)


def _note_load(metrics, load):
    """``M.load_stats``' results into the registry: the gauge
    ``moe/load_max_over_mean`` and, where there, the counter
    ``moe/held_pairs`` (docs/observability.md)."""
    metrics.gauge(
        "moe/load_max_over_mean",
        help="learn: the fullest expert's rows over the mean, averaged over "
             "the expert layers").set(float(load[0]))
    if len(load) > 1:
        metrics.counter(
            "moe/held_pairs",
            help="learn: routed pairs of the updates' forwards that fell on "
                 "the experts this chip holds, summed over the expert "
                 "layers").inc(float(load[1]))


@functools.partial(jax.jit, static_argnames="pad_id")
def _attention_mask(ids, action_masks, pad_id: int):
    """1 where a token is real: every id but ``pad_id``, and every completion
    token ``action_masks`` marks whatever its id — the rollout attended to
    it, and the sampler may draw the pad id. One program, not one a jnp
    operation: ``GRPO.learn`` runs it every call."""
    completion = jnp.pad(action_masks > 0, ((0, 0), (1, 0)))
    return ((ids != pad_id) | completion).astype(jnp.int32)
