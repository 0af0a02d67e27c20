"""Bucketed ragged generation — the continuous-batching role of vLLM
(parity target: /root/reference/agilerl/algorithms/core/base.py:3101
_configure_vllm + :2799 _generate_with_vllm_colocate + output budgeting
:2821-2831), redesigned for XLA's compile-once model.

vLLM solves two problems for the reference's GRPO loop: ragged prompt
lengths (continuous batching) and not decoding finished rows (paged
scheduling). Under jit the equivalents are:

1. **Prompt/row bucketing** — prompt length rounds UP to a bucket and rows
   pad to a row bucket, so an arbitrary stream of ragged batches compiles at
   most ``2 x |buckets used|`` programs (one prefill + one decode-chunk per
   prompt bucket) instead of one per distinct ``(B, P)``.
2. **Chunked decode with host early-exit** — decode runs in fixed-size
   chunks (one compiled program, reused every chunk) with an all-rows-done
   check between chunks: a batch whose completions all hit EOS stops within
   ``decode_chunk`` tokens instead of burning ``max_new_tokens`` steps.

Greedy decoding is bit-identical to ``llm/generate.generate`` (same prefill
maths, same per-step decode); sampled decoding differs only in RNG
fold order across chunks.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agilerl_tpu import observability
from agilerl_tpu.observability import PhaseTimer
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.generate import (
    decode_step,
    left_pad,
    paged_decode_step,
    prefill_head,
)
from agilerl_tpu.llm.speculate import (
    CompletionCache,
    NgramProposer,
    SpecConfig,
    as_spec_config,
    paged_verify_step,
)

#: TTFT buckets (s): serving SLO granularity — sub-ms compile-cached prefill
#: through multi-second cold compiles
TTFT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                2.5, 5.0, 10.0, 30.0, 60.0, 120.0)
#: per-token decode buckets (s): 10µs .. 1s
DECODE_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
                  5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0)
#: queue-depth buckets (rows in flight) — mirrors the row bucket grid
QUEUE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
#: accepted-draft-length buckets (tokens) — 0 is a real outcome (all drafts
#: rejected) and must stay observable, so the first bound sits at 0
SPEC_LEN_BUCKETS = (0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32)


def _round_up(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {buckets[-1]}")


#: the prompt grid of a model whose context is 2048 positions or fewer
BASE_PROMPT_BUCKETS = (64, 128, 256, 512, 1024, 2048)


def default_prompt_buckets(config: M.GPTConfig) -> Tuple[int, ...]:
    """The prompt grid where none is passed: ``BASE_PROMPT_BUCKETS`` and,
    for a model with a longer context, doublings past 2048 up to
    ``config.max_seq_len`` — so the longest prompt the continuous and the
    bucketed tier take, and with it the pool's blocks a slot, follow from
    the model and not from a constant."""
    grid = list(BASE_PROMPT_BUCKETS)
    while grid[-1] * 2 <= config.max_seq_len:
        grid.append(grid[-1] * 2)
    return tuple(grid)


def _device_bytes_free() -> Optional[int]:
    """Bytes of the first local device's memory that nothing holds yet,
    where the backend says (a TPU does); None where it does not (the CPU
    backend)."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def _refuse_window(config: M.GPTConfig, speculate, sharding_plan, mesh):
    """What the serving tiers do not do over a stack that mixes window and
    full attention (or states positions by layer), by mechanism."""
    if not config.varies:
        return
    if as_spec_config(speculate) is not None:
        raise ValueError(
            "speculative decoding over a stack with sliding-window layers: "
            "the multi-token verify step (speculate.paged_verify_step) is "
            "not carried over to a window by layer; not implemented: build "
            "the generator with speculate=None")
    if sharding_plan is not None or mesh is not None:
        raise ValueError(
            "a serving plan over a stack with sliding-window layers: the "
            "period scan's weights (one tree a position in the period) have "
            "no rule, and the window has not been run on a mesh; not "
            "implemented on a mesh")


def _sampling_knobs(gen, greedy: bool, lora) -> Dict[str, Any]:
    """The per-call knob dict both serving generators hand to the shared
    prefill/decode building blocks — ONE home so the two tiers cannot
    sample differently (same no-drift contract as generate._filter_logits)."""
    return dict(
        lora=lora, lora_scale=gen.lora_scale,
        temperature=0.0 if greedy else gen.temperature,
        top_k=gen.top_k, top_p=gen.top_p, eos_id=gen.eos_id,
        pad_id=gen.pad_id, min_new_tokens=gen.min_new_tokens,
    )


def _resolve_serving_plan(sharding_plan, mesh):
    """Normalise the (plan, mesh) pair both generators accept (shared
    ``plan.resolve_plan_and_mesh``). Passing neither keeps the
    single-device fast path with zero plan machinery on it."""
    if sharding_plan is None:
        return None, mesh
    from agilerl_tpu.parallel.plan import resolve_plan_and_mesh

    return resolve_plan_and_mesh(sharding_plan, mesh)


def _constrain_kv(gen, caches):
    """Pin a KV-cache pytree to the plan's ``kv`` rules inside jit (no-op
    without a plan). NamedSharding-based constraints need no enclosing mesh
    context, so call sites stay context-free."""
    if gen.sharding_plan is None:
        return caches
    return jax.tree_util.tree_map(
        jax.lax.with_sharding_constraint,
        caches,
        gen.sharding_plan.shardings("kv", caches, gen.mesh),
    )


def _place_params(gen, params, lora=None):
    """Place weight trees by the plan's ``params``/``lora`` rules — the host
    side of serving under a plan (train and serve share one layout)."""
    if gen.sharding_plan is None:
        return (params, lora) if lora is not None else params
    params = gen.sharding_plan.place("params", params, gen.mesh)
    if lora is not None:
        return params, gen.sharding_plan.place("lora", lora, gen.mesh)
    return params


@contextlib.contextmanager
def rollout_weights(gen, config: M.GPTConfig, params):
    """The weights ONE rollout's programs compute on, for the length of the
    block: ``M.compute_params`` — the matrices cast to ``config.dtype`` and
    a stack stored a layer stacked a run, by one program, once, where every
    prefill and every decode chunk would do both again (a chunk call of
    qwen2-7b at four layers read 8 GB of f32 masters and wrote 4 GB of bf16
    before its first token) — as one tree object, so ``gen`` (a
    ``ContinuousGenerator``, or a fleet of them) sees one weight epoch. A
    tree already stored in the compute type is handed through as it is, and
    nothing else happens. A copy is let go at the block's end, the
    generator's own reference with it (``end_weight_epoch``): the caller's
    learn step needs the memory.

    ``serving/weight_cast_total`` counts the copies made,
    ``serving/weight_cast_bytes`` is the size of the live one (0 outside a
    rollout, and always for a tree that needs none); the host phase
    ``rollout/weight_cast`` is the dispatch."""
    reg = gen.metrics
    live = reg.gauge("serving/weight_cast_bytes",
                     help="bytes of the rollout's live compute-type copy "
                          "of the weights")
    with PhaseTimer(reg, "rollout/weight_cast"):
        weights = M.compute_params(config, params)
    if weights is params:
        live.set(0)
        yield params
        return
    reg.counter("serving/weight_cast_total",
                help="compute-type copies of the weights made for a "
                     "rollout").inc()
    masters = {id(x) for x in jax.tree_util.tree_leaves(params)}
    live.set(sum(x.nbytes for x in jax.tree_util.tree_leaves(weights)
                 if id(x) not in masters))
    try:
        yield weights
    finally:
        gen.end_weight_epoch()
        live.set(0)


def measured_cache_size(*jitted) -> int:
    """Total LIVE compiled-program count across jitted callables, read from
    the jit caches themselves (VERDICT r4 #4: a self-inserted signature set
    asserts a proxy; the measured cache size cannot lie). ``_cache_size`` is
    private jax API, present and correct on the installed jax 0.9.0; the
    getattr guard degrades a future rename into the -1 sentinel instead of
    crashing generate() (both paths are pinned in
    tests/test_llm/test_continuous_batching.py).
    Notes: ``jax.clear_caches()`` restarts the count, a change of input
    sharding/dtype is honestly a new program, and an early-exit batch that
    never reached decode counts only its prefill."""
    sizes = [getattr(fn, "_cache_size", None) for fn in jitted]
    if None in sizes:
        return -1
    return sum(s() for s in sizes)


class BucketedGenerator:
    """Compile-bounded ragged serving over one (config, sampling-recipe).

    Sampling knobs are fixed at construction (they are compile-time
    constants); params/lora ride as call arguments so training steps between
    calls never retrigger compilation.
    """

    def __init__(
        self,
        config: M.GPTConfig,
        max_new_tokens: int = 64,
        pad_id: int = 0,
        eos_id: Optional[int] = None,
        prompt_buckets: Optional[Sequence[int]] = None,
        row_buckets: Sequence[int] = (8, 16, 32, 64, 128),
        decode_chunk: int = 32,
        temperature: float = 1.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_new_tokens: Optional[int] = None,
        lora_scale: float = 2.0,
        metrics=None,
        sharding_plan=None,
        mesh=None,
    ):
        self.config = config
        # latency telemetry: TTFT / per-token decode / queue depth land in
        # this registry (process default unless a dedicated one is passed)
        self.metrics = metrics if metrics is not None else observability.get_registry()
        # declarative serving layout (parallel/plan.py): the plan's "kv"
        # rules pin the cache layout inside prefill (batch over (dp,fsdp),
        # kv-heads over tp) and place_params places weight trees by the
        # "params"/"lora" rules — one ShardingPlan covers train AND serve
        self.sharding_plan, self.mesh = _resolve_serving_plan(
            sharding_plan, mesh)
        self._pending_rows = 0
        self._pending_lock = threading.Lock()
        self.pad_id = int(pad_id)
        self.eos_id = eos_id
        _refuse_window(config, None, sharding_plan, mesh)
        # None: the grid follows from the model (default_prompt_buckets)
        self.prompt_buckets = tuple(sorted(
            default_prompt_buckets(config) if prompt_buckets is None
            else prompt_buckets))
        self.row_buckets = tuple(sorted(row_buckets))
        # a chunk larger than the whole budget would waste decode forwards
        # past max_new_tokens (review finding)
        self.decode_chunk = min(int(decode_chunk), int(max_new_tokens))
        # cache length is static per prompt bucket: bucket + whole chunks
        self.n_chunks = -(-int(max_new_tokens) // self.decode_chunk)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.min_new_tokens = min_new_tokens
        self.lora_scale = lora_scale
        self._prefill = jax.jit(
            self._prefill_impl, static_argnames=("greedy",))
        self._decode = jax.jit(
            self._decode_impl, static_argnames=("greedy",))

    # -- compiled pieces (the SHARED generate.py prefill/decode maths — the
    # two paths cannot drift, review finding) -----------------------------
    def _knobs(self, greedy: bool, lora) -> Dict[str, Any]:
        return _sampling_knobs(self, greedy, lora)

    def place_params(self, params, lora=None):
        """Place weight trees by the construction-time plan's rules (no-op
        without one)."""
        return _place_params(self, params, lora)

    def _prefill_impl(self, params, lora, prompt, prompt_mask, row_valid,
                      key, greedy=False):
        B, P = prompt.shape
        caches = _constrain_kv(self, M.init_caches(
            self.config, B, P + self.n_chunks * self.decode_chunk))
        return prefill_head(
            self.config, params, prompt, prompt_mask, caches, key,
            row_valid=row_valid, **self._knobs(greedy, lora),
        )

    def _decode_impl(self, params, lora, carry, start_step, greedy=False):
        """One fixed-size decode chunk, restartable via the carry."""
        knobs = self._knobs(greedy, lora)

        def step(carry, i):
            return decode_step(self.config, params, carry, i, **knobs)

        carry, (toks, emits) = jax.lax.scan(
            step, carry, start_step + jnp.arange(self.decode_chunk))
        return carry, (toks.T, emits.T)  # [B, chunk]

    # -- host API ----------------------------------------------------------
    def generate(
        self,
        sequences: List[Any],
        key: jax.Array,
        params,
        lora=None,
        greedy: bool = False,
    ) -> Tuple[np.ndarray, np.ndarray, Dict[str, Any]]:
        """sequences: list of 1-D token id arrays (ragged). Returns
        (completions [B, max_new_tokens], mask, info) trimmed back to the
        true row count; info reports bucketing + early-exit telemetry."""
        B = len(sequences)
        if B == 0:
            raise ValueError(
                "BucketedGenerator.generate got an empty sequence list; "
                "callers should gate batches with fits(n_rows, longest)")
        longest = max(len(s) for s in sequences)
        if not self.fits(B, longest):
            raise ValueError(
                f"batch of {B} rows / longest prompt {longest} exceeds the "
                f"bucket grid (row_buckets<= {self.row_buckets[-1]}, "
                f"prompt_buckets<= {self.prompt_buckets[-1]}); check "
                "fits() and fall back to the dense generate path")
        Pb = _round_up(longest, self.prompt_buckets)
        Bb = _round_up(B, self.row_buckets)
        toks, mask = left_pad(sequences, self.pad_id, Pb)
        if Bb > B:
            toks = np.concatenate(
                [toks, np.full((Bb - B, Pb), self.pad_id, np.int32)])
            mask = np.concatenate([mask, np.zeros((Bb - B, Pb), np.int32)])
        row_valid = jnp.asarray(np.arange(Bb) < B)

        # queue depth = rows admitted and not yet fully decoded (covers
        # callers generating from multiple threads over one generator)
        with self._pending_lock:
            self._pending_rows += B
            pending = self._pending_rows
            self.metrics.gauge("serving/queue_depth").set(pending)
        self.metrics.histogram(
            "serving/queue_depth_rows", buckets=QUEUE_BUCKETS,
            help="rows in flight when a batch is admitted",
        ).observe(pending)
        t0 = time.perf_counter()

        steps = 1
        decode_elapsed_s = 0.0
        try:
            carry, (tok0, emit0) = self._prefill(
                params, lora, jnp.asarray(toks), jnp.asarray(mask), row_valid,
                key, greedy=greedy,
            )
            out_toks = [np.asarray(tok0)[:, None]]
            out_masks = [np.asarray(emit0)[:, None]]
            # the np.asarray above synced the device: the batch's first token
            # exists on the host — that is TTFT
            ttft_s = time.perf_counter() - t0
            self.metrics.histogram(
                "serving/ttft_s", buckets=TTFT_BUCKETS,
                help="prefill-to-first-token latency").observe(ttft_s)
            for c in range(self.n_chunks):
                if bool(np.asarray(carry[4]).all()):
                    break  # every live row hit EOS — skip the remaining chunks
                if steps >= self.max_new_tokens:
                    break
                t_chunk = time.perf_counter()
                carry, (toks_c, emits_c) = self._decode(
                    params, lora, carry, jnp.int32(steps), greedy=greedy)
                out_toks.append(np.asarray(toks_c))
                out_masks.append(np.asarray(emits_c))
                dt_chunk = time.perf_counter() - t_chunk
                decode_elapsed_s += dt_chunk
                # the final chunk may overshoot max_new_tokens; metering by
                # decode_chunk would overstate delivered-token throughput on
                # that chunk — divide by DELIVERED tokens (the same trim the
                # tokens_decoded_total counter applies below)
                delivered_chunk = (
                    min(steps + self.decode_chunk, self.max_new_tokens) - steps)
                self.metrics.histogram(
                    "serving/decode_time_per_token_s", buckets=DECODE_BUCKETS,
                    help="decode-chunk wall time / delivered chunk tokens",
                ).observe(dt_chunk / max(delivered_chunk, 1))
                steps += self.decode_chunk
        finally:
            with self._pending_lock:
                self._pending_rows -= B
                self.metrics.gauge("serving/queue_depth").set(self._pending_rows)
        comp = np.concatenate(out_toks, axis=1)
        cmask = np.concatenate(out_masks, axis=1).astype(np.int32)
        # trim: decode may stop early (short outputs) or overshoot the last
        # chunk boundary; rows beyond B are bucket padding
        N = self.max_new_tokens
        if comp.shape[1] < N:
            pad = N - comp.shape[1]
            comp = np.pad(comp, ((0, 0), (0, pad)), constant_values=self.pad_id)
            cmask = np.pad(cmask, ((0, 0), (0, pad)))
        info = {
            "prompt_bucket": Pb,
            "row_bucket": Bb,
            "decode_steps": steps,
            "max_new_tokens": N,
            "compiled_programs": self.compiled_programs,
            "ttft_s": round(ttft_s, 6),
            # delivered decode tokens beyond tok0 = min(steps, N) - 1: the
            # overshooting final chunk must not inflate per-token throughput
            "decode_time_per_token_s": (
                round(decode_elapsed_s / (min(steps, N) - 1), 8)
                if min(steps, N) > 1 else None
            ),
        }
        self.metrics.counter("serving/requests_total").inc()
        self.metrics.counter("serving/rows_total").inc(B)
        # the last chunk may overshoot the budget; delivered output is
        # trimmed to N, so the throughput counter must be too
        self.metrics.counter("serving/tokens_decoded_total").inc(B * min(steps, N))
        self.metrics.emit("serving", rows=B, **info)
        return comp[:B, :N], cmask[:B, :N], info

    def latency_summary(self) -> Dict[str, Any]:
        """p50/p95/p99 for TTFT and per-token decode time plus request/row
        counters — the serving SLO readout."""
        reg = self.metrics
        return {
            "ttft_s": reg.histogram(
                "serving/ttft_s", buckets=TTFT_BUCKETS).summary(),
            "decode_time_per_token_s": reg.histogram(
                "serving/decode_time_per_token_s",
                buckets=DECODE_BUCKETS).summary(),
            "queue_depth_rows": reg.histogram(
                "serving/queue_depth_rows", buckets=QUEUE_BUCKETS).summary(),
            "requests_total": reg.counter("serving/requests_total").value,
            "rows_total": reg.counter("serving/rows_total").value,
        }

    def fits(self, n_rows: int, longest_prompt: int) -> bool:
        """Whether a batch can be served inside the bucket grid (callers
        fall back to dense generation otherwise)."""
        return (0 < n_rows <= self.row_buckets[-1]
                and 0 < longest_prompt <= self.prompt_buckets[-1])

    @property
    def compiled_programs(self) -> int:
        """Total compiled (prefill + decode) program count — the bounded
        compile set the bucketing exists to guarantee (measured from the jit
        caches; see measured_cache_size for the accounting contract)."""
        return measured_cache_size(self._prefill, self._decode)


# --------------------------------------------------------------------------- #
# Continuous (in-flight) batching on a paged KV pool — the Orca
# iteration-level-scheduling + vLLM PagedAttention pair (Yu et al. OSDI 2022;
# Kwon et al. SOSP 2023), redesigned for XLA: ONE compiled decode program
# over a fixed [slots, ...] width is reused forever, and the host scheduler
# admits queued requests into freed slots BETWEEN decode chunks instead of
# waiting for a whole batch to drain.
# --------------------------------------------------------------------------- #

#: queue-wait buckets (s): sub-ms same-iteration admission through
#: multi-second backlog under load shedding
QUEUE_WAIT_BUCKETS = (0.0001, 0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5,
                      1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def chain_hashes(toks_row: np.ndarray, mask_row: np.ndarray,
                 block_size: int) -> List[bytes]:
    """Block-hash chain over a LEFT-PADDED prompt layout — ONE home shared
    by the generator's prefix cache and the fleet router's affinity map, so
    the two tiers key the same prompt identically. The chain covers content
    AND pad pattern, so a hit guarantees every real position's KV is
    identical (causal attention: a block's KV depends only on content at
    <= positions, i.e. on the chain prefix). Pad positions' stored KV never
    matters — masked slots contribute exact zeros to every later softmax."""
    hashes, h = [], b""
    for i in range(toks_row.size // block_size):
        m = hashlib.sha1()
        m.update(h)
        m.update(toks_row[i * block_size:(i + 1) * block_size].tobytes())
        m.update(mask_row[i * block_size:(i + 1) * block_size].tobytes())
        h = m.digest()
        hashes.append(h)
    return hashes


class AdmissionPolicy:
    """The admission decision as ONE reusable object, shared by
    generator-level shedding (:meth:`ContinuousGenerator.submit`) and
    router-level shedding (:class:`agilerl_tpu.llm.fleet.ServingFleet`).

    Splitting *decide* (:meth:`reason` — pure, no counters) from *record*
    (:meth:`shed` — increments ``serving/shed_requests_total`` exactly once)
    is the point: a router that pre-checks every replica's policy and then
    dispatches with ``no_shed=True`` can never double-count one request in
    the shed counter, while a bare generator keeps the old submit()
    behaviour through the same object."""

    def __init__(
        self,
        max_queue: int = 256,
        ttft_slo_s: Optional[float] = None,
        min_slo_samples: int = 20,
        free_block_watermark: float = 0.0,
        metrics=None,
    ):
        self.max_queue = int(max_queue)
        self.ttft_slo_s = ttft_slo_s
        self.min_slo_samples = int(min_slo_samples)
        self.free_block_watermark = float(free_block_watermark)
        self._metrics = metrics

    @property
    def metrics(self):
        return (self._metrics if self._metrics is not None
                else observability.get_registry())

    def bind_metrics(self, metrics) -> "AdmissionPolicy":
        """Adopt an owner's registry when constructed without one — the
        generator/fleet wiring, so shed counts land in the SAME registry
        their ``latency_summary()`` reads. A policy built with an explicit
        registry keeps it."""
        if self._metrics is None:
            self._metrics = metrics
        return self

    def reason(
        self,
        *,
        queue_len: int,
        recent_ttft: Sequence[float] = (),
        available_blocks: Optional[int] = None,
        n_blocks: Optional[int] = None,
    ) -> Optional[str]:
        """Why a request arriving NOW would be shed, or None to admit.
        Pure read — no counter moves, so callers may probe candidates
        freely (the router probes every replica per request)."""
        if queue_len >= self.max_queue:
            return "queue_full"
        if self.free_block_watermark > 0 and available_blocks is not None:
            watermark = int(self.free_block_watermark * int(n_blocks or 0))
            if available_blocks < watermark:
                return "free_block_watermark"
        if self.ttft_slo_s is not None:
            recent = list(recent_ttft)
            if (len(recent) >= self.min_slo_samples
                    and float(np.percentile(np.asarray(recent), 95))
                    > self.ttft_slo_s):
                return "ttft_slo"
        return None

    def shed(self, reason: str, *, source: str = "generator",
             **fields: Any) -> None:
        """Record ONE shed decision (counter + structured event). Exactly
        one of generator or router calls this per dropped request — the
        no-double-count contract."""
        self.metrics.counter(
            "serving/shed_requests_total",
            help="requests dropped by admission control").inc()
        self.metrics.emit("serving_shed", reason=reason, source=source,
                          **fields)


class BlockAllocator:
    """Host-side physical-block free list with a refcounted prefix cache.

    Block 0 is reserved as the garbage sink the decode program points free
    slots at, so it is never handed out. Prompt blocks registered in the
    prefix cache survive their request: at refcount 0 they become EVICTABLE
    (still hit-able) and are reclaimed LRU-first when the free list runs
    dry — the vLLM cached-block lifecycle."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks - 1, 0, -1))  # LIFO: low ids first
        self._ref: Dict[int, int] = {}        # cached block -> refcount
        self._by_hash: Dict[bytes, int] = {}  # chain hash -> block id
        self._hash_of: Dict[int, bytes] = {}
        # refcount-0 cached blocks in eviction order (oldest first)
        self._lru: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        #: called with every chain hash that leaves the cache (eviction,
        #: flush): what else is kept per chain — a hybrid stack's recurrent-
        #: state snapshot — goes with it
        self.on_forget: Optional[Callable[[bytes], None]] = None

    def _forget(self, bid: int) -> None:
        h = self._hash_of.pop(bid)
        del self._by_hash[h]
        if self.on_forget is not None:
            self.on_forget(h)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def evictable_blocks(self) -> int:
        return len(self._lru)

    def available(self) -> int:
        return len(self._free) + len(self._lru)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n private blocks, evicting cold cached blocks if needed; None
        (and no state change) when even eviction cannot cover the request."""
        if self.available() < n:
            return None
        out = []
        for _ in range(n):
            if self._free:
                out.append(self._free.pop())
            else:
                bid, _ = self._lru.popitem(last=False)
                self._forget(bid)
                del self._ref[bid]
                out.append(bid)
        return out

    def free(self, ids: Sequence[int]) -> None:
        """Return PRIVATE (decode / copy) blocks to the free list."""
        self._free.extend(ids)

    def register(self, chain_hash: bytes, bid: int) -> bool:
        """Enter a freshly prefilled prompt block into the prefix cache with
        one reference (its owning slot). First writer wins: if another block
        already serves this hash (e.g. the identical all-pad leading block
        of two different prompts that both MISSED on later blocks), the new
        block is refused and the caller keeps it private — a silent
        overwrite would orphan the old block's reverse mapping."""
        if chain_hash in self._by_hash:
            return False
        self._by_hash[chain_hash] = bid
        self._hash_of[bid] = chain_hash
        self._ref[bid] = self._ref.get(bid, 0) + 1
        self._lru.pop(bid, None)
        return True

    def lookup_chain(self, hashes: Sequence[bytes]) -> Optional[List[int]]:
        """All-or-nothing hit on a full block-hash chain; a hit takes one
        reference on every block."""
        ids = []
        for h in hashes:
            bid = self._by_hash.get(h)
            if bid is None:
                return None
            ids.append(bid)
        for bid in ids:
            self._ref[bid] += 1
            self._lru.pop(bid, None)
        return ids

    def release_shared(self, ids: Sequence[int]) -> None:
        """Drop one reference per block; refcount-0 blocks stay CACHED but
        become evictable (future identical prompts still hit them). Blocks
        whose hash was forgotten by invalidate_cache() go straight back to
        the free list instead."""
        for bid in ids:
            self._ref[bid] -= 1
            if self._ref[bid] == 0:
                if bid in self._hash_of:
                    self._lru[bid] = None
                else:  # weight-epoch flush forgot the hash: plain free
                    del self._ref[bid]
                    self._free.append(bid)

    def invalidate_cache(self) -> None:
        """Flush the prefix cache (weight update: every cached KV block is
        stale). Evictable blocks return to the free list now; blocks still
        referenced by in-flight slots merely forget their hashes, so no
        future admission can hit them and release_shared frees them."""
        for bid in list(self._lru):
            self._forget(bid)
            del self._ref[bid]
            self._free.append(bid)
        self._lru.clear()
        for bid in list(self._hash_of):
            self._forget(bid)


class StateSnapshots:
    """Host index of the snapshot entries in the state cache of a stack
    whose layers keep state (``GPTConfig.state_kind``: a hybrid stack's
    state-space layers, a CCA stack's attention layers;
    ``PagedKVCache.snap``): chain hash of a prompt's LAST block -> entry.

    An entry holds the per-layer state from before the prompt's last token,
    written by the prefill that computed it. A prefix-cache hit needs it as
    much as it needs the chain's KV blocks: the last prompt token re-enters
    on the first decode step, and a layer that keeps state must start that
    step from where the prompt stood, not from zero. So an entry lives and dies
    with its chain: dropped when the allocator forgets the hash (eviction,
    weight-epoch flush), and — there being ``n`` entries only — when a newer
    prompt needs the room (least recently used first); a chain whose entry
    is gone admits as a miss. Entry ``n`` is the sink for prefills nobody
    will hit (prefix cache off)."""

    def __init__(self, n: int, metrics):
        self.n = int(n)
        self.metrics = metrics
        self._by_hash: "collections.OrderedDict[bytes, int]" = (
            collections.OrderedDict())
        self._free = list(range(self.n - 1, -1, -1))

    @property
    def sink(self) -> int:
        return self.n

    def __len__(self) -> int:
        return len(self._by_hash)

    def get(self, chain_hash: bytes) -> Optional[int]:
        entry = self._by_hash.get(chain_hash)
        if entry is not None:
            self._by_hash.move_to_end(chain_hash)
        return entry

    def store(self, chain_hash: bytes) -> int:
        """The entry a prefill writes this chain's snapshot to."""
        entry = self.get(chain_hash)
        if entry is None:
            if not self._free:
                self.drop(next(iter(self._by_hash)))
            entry = self._by_hash[chain_hash] = self._free.pop()
        self.metrics.counter(
            "serving/state_snapshots_stored_total",
            help="recurrent-state snapshots written by prefills").inc()
        return entry

    def drop(self, chain_hash: bytes) -> None:
        entry = self._by_hash.pop(chain_hash, None)
        if entry is not None:
            self._free.append(entry)
            self.metrics.counter(
                "serving/state_snapshot_evictions_total",
                help="recurrent-state snapshots dropped: their chain was "
                     "evicted or flushed, or a newer prompt took the "
                     "entry").inc()

    def clear(self) -> None:
        for h in list(self._by_hash):
            self.drop(h)


@dataclasses.dataclass
class _Request:
    ticket: int
    tokens: np.ndarray          # [plen] int32
    key: np.ndarray             # [2] uint32 per-request PRNG key
    max_new: int
    arrival_s: float
    admitted_s: Optional[float] = None
    ttft_observed: bool = False
    prefix_hit: bool = False
    toks: List[np.ndarray] = dataclasses.field(default_factory=list)
    emits: List[np.ndarray] = dataclasses.field(default_factory=list)
    n_emitted: int = 0
    #: per-request speculation opt-out (submit(speculate=False)): the slot
    #: rides the verify step with zero drafts — exactly one plain decode
    #: step, same tokens AND same RNG stream as speculation off
    speculate: bool = True
    #: decode-captured per-token logprobs (capture_logprobs generators):
    #: same per-chunk row layout as ``toks``/``emits``
    lps: List[np.ndarray] = dataclasses.field(default_factory=list)
    hashes: Optional[List[bytes]] = None  # chain hashes, computed once
    #: externally prefilled prompt KV (disaggregated topology): dict with
    #: k/v [L, Pb, KV, hd], tok0, done0, key_next — admission scatters it
    #: into the pool instead of dispatching a local prefill
    prefilled: Optional[Dict[str, Any]] = None
    #: distributed-tracing parent context (a SpanContext or injected dict)
    #: — set by a fleet router so the decode-admission span stitches into
    #: the fleet-level request trace
    trace_ctx: Optional[Any] = None
    #: the per-request root span a BARE generator opens when tracing is
    #: configured and no upstream context was handed in (fleet-dispatched
    #: requests carry trace_ctx instead; the fleet owns their lifecycle)
    span: Any = None


class ContinuousGenerator:
    """Compile-bounded continuous-batching serving over one
    (config, sampling-recipe): the millions-of-users path of ROADMAP item 3.

    Architecture (all host state numpy; device sees only the block pool plus
    small per-slot arrays):

    - **Slot pool** — ``slots`` decode lanes; ONE jitted chunk program over
      ``[slots, ...]`` (plus a greedy variant) regardless of request count,
      arrival order, or lengths. Free slots are parked ``done=True`` with an
      all-zero block table (writes land in the reserved garbage block 0).
    - **Paged KV** — llm/model.PagedKVCache: requests own whole
      ``block_size``-token physical blocks via per-slot block tables; a
      finished request's blocks return to the free list at the chunk
      boundary it finishes in, not when its batch drains.
    - **Prefix cache** — prompt blocks are keyed by a hash chain over the
      left-padded block contents; a FULL-chain hit skips prefill entirely
      (one private copy of the last prompt block so decode writes cannot
      touch shared state). Covers identical prompts — GRPO group_size
      repeats, best-of-N, retries. Partial-prefix resume is future work
      (docs/serving.md sketches the design).
    - **Admission control** — a bounded queue with load shedding on queue
      overflow, on p95 TTFT exceeding ``ttft_slo_s``, and on the free-block
      watermark; ``submit(..., no_shed=True)`` bypasses shedding for
      training rollouts.

    Greedy decode is token-for-token identical to ``llm/generate.generate``
    at the same prompt bucket: prefill is the SAME prefill_head at the same
    cache extent, and the paged decode runs the same projection/FFN code
    with masked positions contributing exact zeros."""

    def __init__(
        self,
        config: M.GPTConfig,
        max_new_tokens: int = 64,
        pad_id: int = 0,
        eos_id: Optional[int] = None,
        prompt_buckets: Optional[Sequence[int]] = None,
        slots: int = 8,
        block_size: int = 32,
        n_blocks: Optional[int] = None,
        decode_chunk: int = 32,
        temperature: float = 1.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_new_tokens: Optional[int] = None,
        lora_scale: float = 2.0,
        metrics=None,
        max_queue: int = 256,
        ttft_slo_s: Optional[float] = None,
        min_slo_samples: int = 20,
        free_block_watermark: float = 0.0,
        prefix_cache: bool = True,
        sharding_plan=None,
        mesh=None,
        admission: Optional[AdmissionPolicy] = None,
        tracer=None,
        compile_cache=None,
        speculate=None,
        capture_logprobs: bool = False,
    ):
        self.config = config
        self.metrics = metrics if metrics is not None else observability.get_registry()
        _refuse_window(config, speculate, sharding_plan, mesh)
        if config.is_cca:
            if as_spec_config(speculate) is not None:
                raise ValueError(
                    "speculative decoding over a CCA stack would lose the "
                    "attention layers' rolling state (convolution windows "
                    "and the previous token's value half): the verify step "
                    "advances it past rejected drafts and nothing rolls it "
                    "back; not implemented: build the generator with "
                    "speculate=None")
            if sharding_plan is not None or mesh is not None:
                raise ValueError(
                    "a serving plan has no rule for a CCA stack's rolling-"
                    "state cache (per-slot convolution windows and value "
                    "half beside the paged pool) nor for its leaves "
                    "(conv0_*, conv1_*, tau, wv1, wv2, router_*, merge*); "
                    "not implemented on a mesh")
        if config.is_mla or config.is_dropless:
            # what this tier does not do over a latent cache or a dropless
            # expert stack refuses here, by mechanism
            if as_spec_config(speculate) is not None:
                raise ValueError(
                    "speculative decoding over a latent cache or a dropless "
                    "expert stack: the multi-token verify step "
                    "(speculate.paged_verify_step, model.paged_scatter_multi) "
                    "is not carried over to them; not implemented: build "
                    "the generator with speculate=None")
            if sharding_plan is not None or mesh is not None:
                raise ValueError(
                    "a serving plan has no rule for a latent cache's pool "
                    "(one array, one head) nor for the leaves of a latent-"
                    "attention or dropless expert layer (wkv_a, wkv_b, "
                    "kv_norm, router_bias, ws_*); not implemented on a mesh")
        if config.is_hybrid:
            # what this tier does not do for a stack with state-space
            # layers refuses here, by mechanism, before any program exists
            if as_spec_config(speculate) is not None:
                raise ValueError(
                    "speculative decoding over a hybrid stack needs "
                    "recurrent-state rollback for rejected drafts (the "
                    "verify step advances the state past them); not "
                    "implemented: build the generator with speculate=None")
            if sharding_plan is not None or mesh is not None:
                raise ValueError(
                    "a serving plan has no rule for a hybrid stack's "
                    "recurrent-state cache (per-slot conv and SSM state "
                    "beside the paged pool); not implemented on a mesh")
        self._tracer = tracer
        #: layers whose attention has a sliding window (0: no such stack)
        self._window_layers = config.n_window_layers
        # declarative serving layout: the paged pool is placed by the plan's
        # "kv" rules at allocation (kv-heads over tp; the pool has no batch
        # dim so (dp,fsdp) entries filter away), weights via place_params
        self.sharding_plan, self.mesh = _resolve_serving_plan(
            sharding_plan, mesh)
        self.pad_id = int(pad_id)
        self.eos_id = eos_id
        # None: the grid, and with it max_blocks and the pool, follow from
        # the model's context (default_prompt_buckets)
        self.prompt_buckets = tuple(sorted(
            default_prompt_buckets(config) if prompt_buckets is None
            else prompt_buckets))
        self.block_size = int(block_size)
        for b in self.prompt_buckets:
            if b % self.block_size:
                raise ValueError(
                    f"block_size {self.block_size} must divide every prompt "
                    f"bucket (got {b}): prompt KV is written whole blocks at "
                    "a time and prefix hashes chain at block granularity")
        self.decode_chunk = min(int(decode_chunk), int(max_new_tokens))
        self.n_chunks = -(-int(max_new_tokens) // self.decode_chunk)
        self.max_new_tokens = int(max_new_tokens)
        self.slots = int(slots)
        # per-slot logical extent mirrors the bucketed/dense cache sizing
        # (bucket + whole chunks) — the greedy-parity contract
        self._decode_extent = self.n_chunks * self.decode_chunk
        self.max_blocks = -(-(self.prompt_buckets[-1] + self._decode_extent)
                            // self.block_size)
        if n_blocks is None:
            # full provisioning: every slot can hold a worst-case request
            # (+1 for the reserved garbage block). Smaller pools exploit
            # paging harder and lean on admission control instead.
            n_blocks = 1 + self.slots * self.max_blocks
        self.n_blocks = int(n_blocks)
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.min_new_tokens = min_new_tokens
        self.lora_scale = lora_scale
        # admission decisions live in ONE policy object (decide vs record
        # split) so a fleet router can probe/shed without double-counting;
        # the legacy kwargs construct a default policy when none is passed,
        # and a registry-less custom policy adopts THIS registry so shed
        # counts land where latency_summary() reads them
        self.admission = (
            admission.bind_metrics(self.metrics) if admission is not None
            else AdmissionPolicy(
                max_queue=max_queue, ttft_slo_s=ttft_slo_s,
                min_slo_samples=min_slo_samples,
                free_block_watermark=free_block_watermark,
                metrics=self.metrics))
        self.prefix_cache = bool(prefix_cache)
        # draft-free speculative decoding (ROADMAP item 3; llm/speculate.py):
        # a host-side prompt-lookup proposer drafts per-slot continuations
        # and ONE fixed-shape verify program scores K candidates per slot per
        # step. None/False disables; True/dict/SpecConfig enable. Greedy
        # streams are token-for-token identical either way; sampled streams
        # keep the distribution (rejection sampling) but consume different
        # RNG draws.
        self.speculate = as_spec_config(speculate)
        #: capture per-token behavior logprobs during decode (the GRPO
        #: flywheel's record — saves RolloutPod the extra behavior_logprobs
        #: forward; see result_logprobs / generate()'s info["logprobs"])
        self.capture_logprobs = bool(capture_logprobs)
        #: snapshot entries of the state cache, one a slot; None for a stack
        #: none of whose layers keeps state
        self._snapshots = (StateSnapshots(self.slots, self.metrics)
                           if config.state_kind is not None else None)
        self._proposer = (NgramProposer(self.speculate)
                          if self.speculate is not None else None)
        self._completions = (
            CompletionCache(self.speculate.completion_cache_size)
            if self.speculate is not None and self.speculate.completion_cache
            else None)

        # persistent executable store (ROADMAP item 5): replica spin-up
        # LOADS the plan-compiled decode-chunk + per-bucket prefill
        # programs a previous process published instead of recompiling —
        # the autoscaler's cold-start killer. Opt-in (compile_cache= /
        # AGILERL_TPU_COMPILE_CACHE); programs stay bit-identical (tier-1
        # gated) and compiled_programs keeps counting loaded executables
        # through the same measured_cache_size contract.
        from agilerl_tpu.parallel.compile_cache import (
            CachedFunction, resolve_cache)

        self.compile_cache = resolve_cache(
            compile_cache, metrics=self.metrics, tracer=tracer)
        # a persisted program must not donate buffers sharded over >1
        # device: this image's jaxlib double-frees when a DESERIALIZED
        # executable's multi-device outputs are donated back to it on the
        # next chunk (the pool self-feed pattern). Single-device aliasing
        # is unaffected, so the plan-less fast path keeps donation.
        donate = (self.compile_cache is None or self.mesh is None
                  or int(self.mesh.devices.size) <= 1)
        self._prefill = jax.jit(self._prefill_admit_impl,
                                static_argnames=("greedy",),
                                donate_argnums=(5,) if donate else ())
        self._decode = jax.jit(self._decode_chunk_impl,
                               static_argnames=("greedy",),
                               donate_argnums=(2,) if donate else ())
        # multi-token verify (speculative decoding): built unconditionally —
        # jit is lazy, an unused verify contributes zero compiled programs
        self._verify = jax.jit(self._verify_impl,
                               static_argnames=("greedy",),
                               donate_argnums=(2,) if donate else ())
        self._copy_block = jax.jit(
            M.paged_copy_block, donate_argnums=(0,) if donate else ())
        # decode-side import of a prefill worker's exported prompt KV
        # (disaggregated topology): one program per prompt bucket
        self._scatter_import = jax.jit(
            M.paged_scatter_prompt, donate_argnums=(0,) if donate else ())
        if self.compile_cache is not None:
            if not donate:
                self.metrics.warn_once(
                    "serving/compile_cache_no_donation",
                    "compile cache + mesh-sharded pool: serving programs "
                    "compiled WITHOUT donation (deserialized multi-device "
                    "donation is unsafe on this jaxlib) — peak pool memory "
                    "doubles transiently per chunk")
            wrap = dict(store=self.compile_cache, plan=self.sharding_plan,
                        mesh=self.mesh, metrics=self.metrics, tracer=tracer)
            self._prefill = CachedFunction(
                self._prefill, name="serving/prefill_admit",
                donate_argnums=(5,) if donate else (),
                static_argnames=("greedy",), **wrap)
            self._decode = CachedFunction(
                self._decode, name="serving/decode_chunk",
                donate_argnums=(2,) if donate else (),
                static_argnames=("greedy",), **wrap)
            # verify fingerprint covers K and the bucket grid through the
            # drafts/pool arg signature and every sampler knob through the
            # lowered-HLO sha — a knob change is a MISS, never a wrong
            # executable (tests/test_llm/test_speculative.py pins the skew)
            self._verify = CachedFunction(
                self._verify, name="serving/paged_verify",
                donate_argnums=(2,) if donate else (),
                static_argnames=("greedy",), **wrap)
            self._copy_block = CachedFunction(
                self._copy_block, name="serving/copy_block",
                donate_argnums=(0,) if donate else (), **wrap)
            self._scatter_import = CachedFunction(
                self._scatter_import, name="serving/scatter_import",
                donate_argnums=(0,) if donate else (), **wrap)

        # -- host scheduler state --
        # Threading contract: submit()/result() may be called from request
        # threads (deque append/pop are atomic; the ticket counter takes
        # this lock), but step()/run_until_drained()/generate() must be
        # driven by ONE scheduler thread — slot state is not locked.
        self._submit_lock = threading.Lock()
        self._last_shed_span_s = float("-inf")  # shed-span 1/s throttle
        self.allocator = BlockAllocator(self.n_blocks)
        if self._snapshots is not None:
            self.allocator.on_forget = self._snapshots.drop
        self._queue: "collections.deque[_Request]" = collections.deque()
        # shed decisions use a ROLLING window of recent TTFTs, not the
        # lifetime histogram — a cold-compile outlier in a cumulative p95
        # would keep shedding healthy traffic long after latency recovered
        self._recent_ttft: "collections.deque[float]" = collections.deque(
            maxlen=max(self.min_slo_samples, 64))
        self._next_ticket = 0
        self._results: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._pool: Optional[M.PagedKVCache] = None
        S = self.max_blocks * self.block_size
        self._tables = np.zeros((self.slots, self.max_blocks), np.int32)
        self._mask = np.zeros((self.slots, S), np.int32)
        self._lengths = np.zeros(self.slots, np.int32)
        self._prev_tok = np.zeros(self.slots, np.int32)
        self._prev_ok = np.zeros(self.slots, bool)
        self._pos = np.zeros(self.slots, np.int32)
        self._step_idx = np.zeros(self.slots, np.int32)
        self._done = np.ones(self.slots, bool)
        self._keys = np.zeros((self.slots, 2), np.uint32)
        self._slot_req: List[Optional[_Request]] = [None] * self.slots
        self._slot_shared: List[List[int]] = [[] for _ in range(self.slots)]
        self._slot_private: List[List[int]] = [[] for _ in range(self.slots)]
        # speculation host state: per-slot token history (prompt + emitted —
        # the proposer's lookup corpus) and the finished completion the slot
        # is currently following (the GRPO group-repeat draft source)
        self._slot_hist: List[List[int]] = [[] for _ in range(self.slots)]
        self._slot_plen: List[int] = [0] * self.slots
        self._slot_follow: List[Optional[np.ndarray]] = [None] * self.slots
        # decode-captured logprob results, keyed like _results
        self._result_lps: Dict[int, np.ndarray] = {}
        # whether the finished request was admitted by a prefix-cache hit
        self._result_hits: Dict[int, bool] = {}
        # strong refs to the last-served weight trees: cached prompt KV is
        # only valid for the weights that prefilled it
        self._weights: Optional[Tuple[Any, Any]] = None
        # ordinals the sched/step phase carries into a profile: generate()
        # calls so far (0 while a server drives step() itself) and
        # scheduler iterations since the last one began
        self._rollout = 0
        self._chunk = 0

    # -- compiled pieces ---------------------------------------------------
    def _knobs(self, greedy: bool, lora) -> Dict[str, Any]:
        return _sampling_knobs(self, greedy, lora)

    def _prefill_admit_impl(self, params, lora, prompt, prompt_mask, key,
                            cache, block_ids, greedy=False, state_ids=None):
        """Prefill ONE request at its prompt bucket (the SHARED prefill_head
        — dense-parity maths) and scatter its prompt KV into the assigned
        physical blocks. Compiles once per (prompt bucket, greedy).
        ``state_ids`` (stacks whose layers keep state: int32 [slot, snapshot
        entry]) says where the state after the prompt and the one before
        its last token go."""
        Pb = prompt.shape[1]
        # keep_prev_state: the snapshot of layers that keep state (below);
        # nothing to keep, and the same program, for a stack without them
        # dense-parity extent: the same Pb + chunks*chunk the bucketed/dense
        # paths allocate, so chunked-attention chunking is identical
        dense = M.init_caches(self.config, 1, Pb + self._decode_extent)
        if self.capture_logprobs:
            carry, (tok0, _emit0), last_logits = prefill_head(
                self.config, params, prompt, prompt_mask, dense, key,
                return_logits=True, keep_prev_state=True,
                **self._knobs(greedy, lora),
            )
        else:
            carry, (tok0, _emit0) = prefill_head(
                self.config, params, prompt, prompt_mask, dense, key,
                keep_prev_state=True, **self._knobs(greedy, lora),
            )
        filled, _tok0, _rv, pos, done0, key_next = carry
        cache = M.paged_scatter_prompt(
            cache, block_ids, filled.k[:, 0, :Pb],
            None if filled.v is None else filled.v[:, 0, :Pb])
        if self.config.state_kind is not None:
            cache = M.paged_write_state(cache, state_ids[0], state_ids[1],
                                        filled.state, filled.prev_state)
        if self.capture_logprobs:
            # raw log p(tok0) — the token_logprobs convention the flywheel's
            # behavior-logprob record uses (temperature 1.0, no EOS floor)
            lp0 = jax.nn.log_softmax(last_logits, axis=-1)[0, tok0[0]]
            return cache, tok0[0], pos[0], done0[0], key_next, lp0
        return cache, tok0[0], pos[0], done0[0], key_next

    def _decode_chunk_impl(self, params, lora, cache, tables, slot_mask,
                           lengths, prev_tok, prev_ok, pos, step_idx, done,
                           keys, greedy=False):
        """One fixed-size decode chunk over the WHOLE slot pool — the single
        compiled program the scheduler reuses forever."""
        knobs = self._knobs(greedy, lora)

        def step(carry, _):
            return paged_decode_step(self.config, params, carry,
                                     capture_lp=self.capture_logprobs,
                                     **knobs)

        carry = (cache, tables, slot_mask, lengths, prev_tok, prev_ok, pos,
                 step_idx, done, keys)
        carry, ys = jax.lax.scan(step, carry, None, length=self.decode_chunk)
        hit = ()
        if self.config.is_dropless:
            # distinct experts the chunk's steps touched, summed over the
            # expert layers and the steps: one small integer beside the ys
            *ys, hits = ys
            hit = (hits.sum().astype(jnp.int32),)
        if self.capture_logprobs:
            toks, emits, lps = ys
            return carry, (toks.T, emits.T, lps.T) + hit  # [slots, chunk]
        toks, emits = ys
        return carry, (toks.T, emits.T) + hit  # [slots, chunk]

    def _verify_impl(self, params, lora, cache, tables, slot_mask, lengths,
                     prev_tok, prev_ok, pos, step_idx, done, keys, drafts,
                     draft_len, greedy=False):
        """Score K drafted tokens per slot in ONE forward and advance each
        slot by its traced accepted length (llm/speculate.paged_verify_step
        — the multi-token twin of the decode chunk). A slot with
        draft_len 0 takes exactly one plain decode step: same token, same
        RNG stream, so opt-outs and proposer misses riding a mixed verify
        step stay stream-identical to speculation off."""
        carry = (cache, tables, slot_mask, lengths, prev_tok, prev_ok, pos,
                 step_idx, done, keys)
        return paged_verify_step(
            self.config, params, carry, drafts, draft_len,
            capture_lp=self.capture_logprobs, **self._knobs(greedy, lora))

    # -- host API ----------------------------------------------------------
    @property
    def tracer(self):
        """The distributed tracer (construction-time override, else the
        process default — read lazily so configuring tracing AFTER the
        generator exists still takes effect)."""
        return (self._tracer if self._tracer is not None
                else observability.get_tracer())

    @tracer.setter
    def tracer(self, tracer) -> None:
        self._tracer = tracer

    def fits(self, n_rows: int, longest_prompt: int) -> bool:
        """Row count is unbounded (the queue absorbs it); only the prompt
        must fit the bucket grid."""
        return n_rows > 0 and 0 < longest_prompt <= self.prompt_buckets[-1]

    def _enqueue(self, tokens: np.ndarray, *, max_new: Optional[int],
                 key, no_shed: bool, hashes: Optional[List[bytes]],
                 arrival_s: Optional[float] = None,
                 prefilled: Optional[Dict[str, Any]] = None,
                 shed_source: str = "generator",
                 trace_ctx: Optional[Any] = None,
                 speculate: bool = True) -> Optional[int]:
        """The shared admission preamble behind :meth:`submit` and
        :meth:`submit_prefilled` — ONE home for bucket validation, the shed
        probe/record, budget clamping, ticket allocation, key defaulting,
        and the queue-depth telemetry, so the unified and disaggregated
        entry points cannot drift."""
        if tokens.size == 0 or tokens.size > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt of {tokens.size} tokens outside the bucket grid "
                f"(1..{self.prompt_buckets[-1]}); check fits() and fall "
                "back to the dense generate path")
        if not no_shed:
            reason = self._shed_reason()
            if reason is not None:
                tr = self.tracer
                now_s = time.perf_counter()
                if tr.enabled and now_s - self._last_shed_span_s >= 1.0:
                    # a shed is an ANOMALY: always sampled, even when
                    # steady traffic isn't (force=True) — but a shed STORM
                    # is exactly when admission control fires, so span
                    # emission (a flushed JSONL write) is throttled to
                    # ~1/s; the shed counter/event stays exact
                    self._last_shed_span_s = now_s
                    tr.start_span(
                        "serving.shed", parent=trace_ctx, force=True,
                        attributes={"reason": reason,
                                    "source": shed_source}).end()
                self.admission.shed(reason, queue_len=len(self._queue),
                                    source=shed_source)
                return None
        if max_new is None:
            budget = self.max_new_tokens
        else:
            budget = min(int(max_new), self.max_new_tokens)
            if budget <= 0:
                # a falsy-zero fallback here would silently burn a slot on
                # a full-budget generation the caller asked NOT to run
                raise ValueError(f"max_new must be positive, got {max_new}")
        with self._submit_lock:
            ticket = self._next_ticket
            self._next_ticket += 1
        if key is None:
            key = jax.random.PRNGKey(ticket)
        span = None
        if trace_ctx is None:
            tr = self.tracer
            if tr.enabled:
                # bare-generator usage (no fleet upstream): this request IS
                # the trace root; the generator ends it at _finish_slot
                span = tr.start_span(
                    "serving.request",
                    attributes={"ticket": ticket,
                                "prompt_tokens": int(tokens.size)})
                trace_ctx = span.context()
        self._queue.append(_Request(
            ticket=ticket, tokens=tokens, key=np.asarray(key, np.uint32),
            max_new=budget,
            arrival_s=(float(arrival_s) if arrival_s is not None
                       else time.perf_counter()),
            hashes=list(hashes) if hashes is not None else None,
            prefilled=prefilled, trace_ctx=trace_ctx, span=span,
            speculate=bool(speculate)))
        self.metrics.histogram(
            "serving/queue_depth_rows", buckets=QUEUE_BUCKETS,
            help="rows in flight when a batch is admitted",
        ).observe(len(self._queue) + self._occupancy())
        return ticket

    def submit(self, tokens, *, max_new: Optional[int] = None, key=None,
               no_shed: bool = False,
               hashes: Optional[List[bytes]] = None,
               trace_ctx: Optional[Any] = None,
               speculate: bool = True) -> Optional[int]:
        """Enqueue one request; returns a ticket, or None when admission
        control sheds it (queue overflow / TTFT SLO breach / free-block
        watermark). ``no_shed`` bypasses shedding — the training-rollout
        mode, where dropping a rollout would corrupt the learn batch.
        ``hashes`` lets a router that already computed the prompt's block
        chain (at THIS generator's bucket/block layout) skip the re-hash at
        admission. ``trace_ctx`` parents the decode-admission span onto an
        upstream (fleet-level) trace; without one, a configured tracer
        opens a per-request root span instead. ``speculate=False`` opts
        THIS request out of speculative decoding (it rides the verify step
        with zero drafts — exactly one plain decode step per step, same
        tokens and same RNG stream as a speculation-off generator)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        return self._enqueue(tokens, max_new=max_new, key=key,
                             no_shed=no_shed, hashes=hashes,
                             trace_ctx=trace_ctx, speculate=speculate)

    def submit_prefilled(
        self,
        tokens,
        *,
        k_prompt: np.ndarray,
        v_prompt: np.ndarray,
        tok0: int,
        done0: bool,
        key_next,
        lp0: Optional[float] = None,
        key=None,
        max_new: Optional[int] = None,
        arrival_s: Optional[float] = None,
        no_shed: bool = False,
        hashes: Optional[List[bytes]] = None,
        trace_ctx: Optional[Any] = None,
        speculate: bool = True,
    ) -> Optional[int]:
        """Enqueue a request whose prompt KV was already computed by a
        prefill worker (the disaggregated topology's decode-side entry).

        ``k_prompt``/``v_prompt`` are ``[L, Pb, KV, hd]`` at THIS
        generator's prompt bucket — the worker must share the bucket grid
        and decode sizing so the prefill cache extent matches (the
        dense-parity contract). The import must be computed under the
        weights of this generator's CURRENT/next step: the fleet driver
        guarantees it by consuming transfers in the same ``step()`` that
        prefilled them, and ``_check_weight_epoch`` drops queued imports
        that a LATER weight swap strands — but a first-step import under
        foreign weights is the caller's contract to uphold. ``tok0``/``done0``/``key_next`` are the
        prefill head's first sampled token, its EOS state, and the
        continued RNG stream; admission seeds the slot with them exactly as
        the local miss path would after its own prefill, so the decode
        stream is token-for-token identical. ``key`` is the RAW request key,
        kept so a prefix-cache HIT on an already-cached chain can resume the
        same split stream without touching the import. ``arrival_s`` lets
        the router carry the ORIGINAL arrival time across the transfer so
        TTFT includes prefill + transfer latency. Decode-side admission
        control (free-block watermark, queue, TTFT SLO) applies unless
        ``no_shed``."""
        if self.config.is_mla:
            raise NotImplementedError(
                "submit_prefilled over a latent cache: the prefill worker's "
                "export and this tier's import (model.paged_scatter_prompt "
                "through _admit_import) carry K and V arrays, not the one "
                "latent array; not implemented")
        if self.config.is_hybrid:
            raise NotImplementedError(
                "submit_prefilled over a hybrid stack: the prefill worker's "
                "export carries prompt KV only, not the recurrent state of "
                "the state-space layers (nor its snapshot); not implemented")
        if self.config.is_cca:
            raise NotImplementedError(
                "submit_prefilled over a CCA stack: the prefill worker's "
                "export carries prompt KV only, not the attention layers' "
                "rolling state (convolution windows and value half, nor "
                "its snapshot); not implemented")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if key is None:
            # the raw request key is load-bearing: a prefix-cache HIT on an
            # already-cached chain bypasses the import and re-derives tok0
            # from THIS key — a local-ticket default would silently diverge
            # the sampled stream from the transferred prefill
            raise ValueError(
                "submit_prefilled needs the ORIGINAL request key (the one "
                "the prefill worker sampled tok0/key_next from)")
        # out-of-grid sizes fall through to _enqueue's friendlier error
        if 0 < tokens.size <= self.prompt_buckets[-1]:
            Pb = _round_up(tokens.size, self.prompt_buckets)
            if k_prompt.shape[1] != Pb:
                raise ValueError(
                    f"imported prompt KV covers {k_prompt.shape[1]} "
                    f"positions but this generator buckets the prompt to "
                    f"{Pb}; prefill workers must share the decode "
                    "replica's bucket grid")
        return self._enqueue(
            tokens, max_new=max_new, key=key, no_shed=no_shed,
            hashes=hashes, arrival_s=arrival_s,
            shed_source="decode_import", trace_ctx=trace_ctx,
            speculate=speculate,
            prefilled=dict(
                k=np.asarray(k_prompt), v=np.asarray(v_prompt),
                tok0=int(tok0), done0=bool(done0),
                key_next=np.asarray(key_next, np.uint32),
                lp0=(float(lp0) if lp0 is not None else None),
            ))

    def _shed_reason(self) -> Optional[str]:
        with self._submit_lock:  # scheduler thread appends concurrently
            recent = list(self._recent_ttft)
        return self.admission.reason(
            queue_len=len(self._queue), recent_ttft=recent,
            available_blocks=self.allocator.available(),
            n_blocks=self.n_blocks)

    def admission_reason(self) -> Optional[str]:
        """Why a request arriving NOW would be shed, or None to admit —
        the pure probe a fleet router uses to pick/skip this replica
        without moving any shed counter."""
        return self._shed_reason()

    # legacy admission knobs delegate to the policy (runtime tuning like
    # ``gen.ttft_slo_s = 0.5`` keeps taking effect on the next submit — a
    # construction-time snapshot would silently freeze it)
    @property
    def max_queue(self) -> int:
        return self.admission.max_queue

    @max_queue.setter
    def max_queue(self, v: int) -> None:
        self.admission.max_queue = int(v)

    @property
    def ttft_slo_s(self) -> Optional[float]:
        return self.admission.ttft_slo_s

    @ttft_slo_s.setter
    def ttft_slo_s(self, v: Optional[float]) -> None:
        self.admission.ttft_slo_s = v

    @property
    def min_slo_samples(self) -> int:
        return self.admission.min_slo_samples

    @min_slo_samples.setter
    def min_slo_samples(self, v: int) -> None:
        self.admission.min_slo_samples = int(v)

    @property
    def free_block_watermark(self) -> float:
        return self.admission.free_block_watermark

    @free_block_watermark.setter
    def free_block_watermark(self, v: float) -> None:
        self.admission.free_block_watermark = float(v)

    def _observe_ttft(self, ttft_s: float) -> None:
        with self._submit_lock:
            self._recent_ttft.append(ttft_s)
        self.metrics.histogram(
            "serving/ttft_s", buckets=TTFT_BUCKETS,
            help="submit-to-first-token latency").observe(ttft_s)

    def _occupancy(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def backlog(self) -> int:
        """Queued + in-flight rows — the queue-depth load signal the fleet
        router dispatches on."""
        return len(self._queue) + self._occupancy()

    def place_params(self, params, lora=None):
        """Place weight trees by the construction-time plan's rules (no-op
        without one)."""
        return _place_params(self, params, lora)

    def _refuse_a_pool_the_device_cannot_hold(self) -> None:
        """The pool is provisioned for ``slots`` requests of the grid's top
        bucket + the new tokens, and without ``prompt_buckets`` the grid's
        top is the model's ``max_seq_len`` (``default_prompt_buckets``): at
        a long context that is more than one device has free, and the
        allocator would fail without saying what to pass."""
        free = _device_bytes_free()
        if free is None or self.mesh is not None:
            return
        shapes = jax.eval_shape(lambda: M.init_paged_cache(
            self.config, self.n_blocks, self.block_size, slots=self.slots,
            snapshots=0 if self._snapshots is None else self._snapshots.n))
        need = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(shapes))
        if need > free:
            raise ValueError(
                f"the paged pool would hold {need / 1e9:.2f} GB "
                f"({self.n_blocks} blocks of {self.block_size} tokens: "
                f"{self.slots} slots x {self.max_blocks} blocks, a prompt "
                f"grid up to {self.prompt_buckets[-1]} + "
                f"{self._decode_extent} new tokens) and the device has "
                f"{free / 1e9:.2f} GB free: pass prompt_buckets= with a lower "
                "top, fewer slots=, n_blocks= below full provisioning, or "
                "a GPTConfig with the max_seq_len the prompts need")

    def _ensure_pool(self) -> None:
        if self._pool is None:
            self._refuse_a_pool_the_device_cannot_hold()
            if self._snapshots is None:
                pool = M.init_paged_cache(
                    self.config, self.n_blocks, self.block_size)
            else:  # both cache kinds in one object, one owner
                pool = M.init_paged_cache(
                    self.config, self.n_blocks, self.block_size,
                    slots=self.slots, snapshots=self._snapshots.n)
                self.metrics.gauge(
                    "serving/state_cache_bytes",
                    help="recurrent-state cache, slots and snapshots",
                ).set(M.state_cache_bytes(pool))
            self.metrics.gauge(
                "serving/pool_block_bytes",
                help="bytes one physical block holds across the attention "
                     "layers, from the pool's own arrays",
            ).set(M.paged_block_bytes(pool))
            if self._window_layers:
                self.metrics.gauge(
                    "serving/window_layers",
                    help="layers of the stack whose attention has a sliding "
                         "window (a constant of the model)",
                ).set(self._window_layers)
            if self.sharding_plan is not None:
                # kv_paged, NOT kv: the pool's axis 1 is global block ids —
                # the dense rules' (dp,fsdp) batch entry must never touch it
                pool = self.sharding_plan.place("kv_paged", pool, self.mesh)
            self._pool = pool

    def warm_start(self, params=None, lora=None,
                   greedy: Optional[bool] = None,
                   only_cached: bool = False) -> List[Dict[str, Any]]:
        """Eagerly load-or-compile the decode-chunk program(s) from the
        persistent executable store (no-op without ``compile_cache``) so a
        freshly spawned replica is ready BEFORE its first request — the
        autoscaler's spin-up path (``ServingFleet.scale_up``).

        Warms the decode-chunk program(s) AND one prefill program per
        prompt bucket, so the first request on any bucket pays neither a
        compile nor a load in the request path.

        ``params``/``lora`` may be the real weight trees or abstract
        ``ShapeDtypeStruct`` trees; by default the config's ``init_params``
        shapes are used (pass the real trees when serving differently-typed
        weights). ``greedy=None`` warms both sampling variants.
        ``only_cached=True`` loads what the store already has and leaves
        misses LAZY (the fleet's spin-up mode: a cold store must not pay
        eager compiles for variants/buckets that may never be dispatched).
        Returns one load-or-compile info dict per warmed program."""
        if self.compile_cache is None:
            return []
        self._ensure_pool()
        if params is None:
            params = jax.eval_shape(
                lambda k: M.init_params(k, self.config),
                jax.random.PRNGKey(0))

        def _abs(leaf):
            # keep mesh placements (they change the program), drop
            # single-device/committed-ness (it doesn't — see
            # compile_cache._sharding_desc)
            from jax.sharding import NamedSharding

            sh = getattr(leaf, "sharding", None)
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=sh if isinstance(sh, NamedSharding) else None)

        params_abs = jax.tree_util.tree_map(_abs, params)
        if self.sharding_plan is not None:
            params_abs = self.sharding_plan.abstract(
                "params", params_abs, self.mesh)
        pool_abs = jax.tree_util.tree_map(_abs, self._pool)
        S = self.max_blocks * self.block_size
        a = jax.ShapeDtypeStruct
        decode_args = (
            a((self.slots, self.max_blocks), jnp.int32),   # tables
            a((self.slots, S), jnp.int32),                 # slot mask
            a((self.slots,), jnp.int32),                   # lengths
            a((self.slots,), jnp.int32),                   # prev_tok
            a((self.slots,), jnp.bool_),                   # prev_ok
            a((self.slots,), jnp.int32),                   # pos
            a((self.slots,), jnp.int32),                   # step_idx
            a((self.slots,), jnp.bool_),                   # done
            a((self.slots, 2), jnp.uint32),                # keys
        )
        infos = []
        variants = [False, True] if greedy is None else [bool(greedy)]
        for g in variants:
            infos.append(self._decode.prepare(
                params_abs, lora, pool_abs, *decode_args,
                only_cached=only_cached, greedy=g))
            if self._proposer is not None:
                infos.append(self._verify.prepare(
                    params_abs, lora, pool_abs, *decode_args,
                    a((self.slots, self.speculate.k), jnp.int32),  # drafts
                    a((self.slots,), jnp.int32),                   # draft_len
                    only_cached=only_cached, greedy=g))
            for Pb in self.prompt_buckets:
                # mirror the _admit dispatch exactly (line ~1200): bucketed
                # prompt/mask, request key, pool, whole-prompt block list
                infos.append(self._prefill.prepare(
                    params_abs, lora,
                    a((1, Pb), jnp.int32), a((1, Pb), jnp.int32),
                    a((2,), jnp.uint32), pool_abs,
                    a((Pb // self.block_size,), jnp.int32),
                    only_cached=only_cached, greedy=g,
                    **({"state_ids": a((2,), jnp.int32)}
                       if self._snapshots is not None else {})))
        return infos

    def _chain_hashes(self, toks_row: np.ndarray,
                      mask_row: np.ndarray) -> List[bytes]:
        """Block-hash chain at this generator's block size (shared module
        function — the fleet router keys its affinity map the same way)."""
        return chain_hashes(toks_row, mask_row, self.block_size)

    def _admit(self, params, lora, greedy: bool) -> List[int]:
        """Fill free slots from the queue head; returns tickets completed AT
        admission (immediate-EOS / budget-1 requests never enter a chunk).

        Prefill dispatches are NOT synced inside the loop — each miss's
        (tok0, done0, key) device handles are collected and converted once
        after every admission has been dispatched, so host-side hashing /
        allocation / left_pad for request i+1 overlaps request i's prefill
        on the device."""
        finished: List[int] = []
        pending: List[Tuple[int, _Request, Any, Any, Any, Any]] = []
        while self._queue:
            try:
                slot = self._slot_req.index(None)
            except ValueError:
                break  # no free slot: decode must free one first
            req = self._queue[0]
            Pb = _round_up(req.tokens.size, self.prompt_buckets)
            nb_p = Pb // self.block_size
            req_chunks = -(-req.max_new // self.decode_chunk)
            n_dec = -(-(req_chunks * self.decode_chunk) // self.block_size)
            toks_row, mask_row = left_pad([req.tokens], self.pad_id, Pb)
            toks_row, mask_row = toks_row[0], mask_row[0]
            if self.prefix_cache and req.hashes is None:
                req.hashes = self._chain_hashes(toks_row, mask_row)
            shared = (self.allocator.lookup_chain(req.hashes)
                      if self.prefix_cache else None)
            snap = None
            if shared is not None and self._snapshots is not None:
                # a hybrid stack's hit needs the chain's state snapshot too;
                # where it is gone (a newer prompt took the entry) the
                # request prefills again
                snap = self._snapshots.get(req.hashes[-1])
                if snap is None:
                    self.allocator.release_shared(shared)
                    shared = None
            if shared is not None:
                private = self.allocator.alloc(1 + n_dec)
                if private is None:
                    # hit unaffordable: fall back to a MISS — releasing the
                    # shared refs makes those cold blocks evictable, so the
                    # larger miss allocation may still fit (a pool that
                    # served this prompt once must keep serving it)
                    self.allocator.release_shared(shared)
                    shared = None
            if shared is None:
                private = self.allocator.alloc(nb_p + n_dec)
                if private is None:
                    break
            self._queue.popleft()
            now = time.perf_counter()
            req.admitted_s = now
            self.metrics.histogram(
                "serving/queue_wait_s", buckets=QUEUE_WAIT_BUCKETS,
                help="submit-to-admission wait").observe(now - req.arrival_s)
            if req.trace_ctx is not None:
                tr = self.tracer
                if tr.enabled:
                    # the decode-admission hop of the request trace (instant
                    # span: the admission decision, not the decode itself)
                    tr.start_span(
                        "serving.admit", parent=req.trace_ctx,
                        attributes={
                            "slot": slot,
                            "path": ("prefix_hit" if shared is not None
                                     else "import"
                                     if req.prefilled is not None
                                     else "prefill"),
                            "queue_wait_s": now - req.arrival_s,
                        }).end()
            self._ensure_pool()
            plen = int(mask_row.sum())
            table = np.zeros(self.max_blocks, np.int32)
            if shared is not None:
                # full prefix hit: reuse every prompt block; the LAST one is
                # copied into a private block because the first decode write
                # (the re-entering last prompt token) lands inside it
                req.prefix_hit = True
                self.metrics.counter("serving/prefix_cache_hits_total").inc()
                copy_dst = private[0]
                if snap is None:
                    self._pool = self._copy_block(
                        self._pool, jnp.int32(shared[-1]),
                        jnp.int32(copy_dst))
                else:
                    # one program: the private copy of the last KV block AND
                    # the state from before the re-entering last token
                    with PhaseTimer(self.metrics, "sched/state_restore"):
                        self._pool = self._copy_block(
                            self._pool, jnp.int32(shared[-1]),
                            jnp.int32(copy_dst), jnp.int32(snap),
                            jnp.int32(slot))
                    self.metrics.counter(
                        "serving/state_snapshot_restores_total",
                        help="prefix-cache hits that restored a recurrent-"
                             "state snapshot into their slot").inc()
                table[:nb_p - 1] = shared[:-1]
                table[nb_p - 1] = copy_dst
                table[nb_p:nb_p + n_dec] = private[1:]
                self._slot_shared[slot] = list(shared)
                self._slot_private[slot] = list(private)
                # resume state: the last prompt token re-enters the cache on
                # the first decode step; seeding the slot key with the RAW
                # request key continues the same split stream prefill_head
                # would have used (split -> (carry, sample))
                self._lengths[slot] = Pb - 1
                self._prev_tok[slot] = toks_row[-1]
                self._pos[slot] = plen - 1
                self._step_idx[slot] = 0
                self._done[slot] = False
                self._keys[slot] = req.key
                self._mask[slot] = 0
                self._mask[slot, :Pb] = mask_row
                self._mask[slot, Pb - 1] = 0  # set by the first decode step
                self._seed_spec_slot(slot, req)
            elif req.prefilled is not None:
                # disaggregated import: the prompt KV arrived from a prefill
                # worker — scatter it instead of dispatching a local prefill
                # (helper method: keeps this loop body free of host syncs)
                self._admit_import(slot, req, table, private, nb_p, n_dec,
                                   Pb, plen, mask_row)
            else:
                self.metrics.counter("serving/prefix_cache_misses_total").inc()
                prompt_blocks, dec_blocks = private[:nb_p], private[nb_p:]
                state_ids = {}
                if self._snapshots is not None:
                    entry = (self._snapshots.store(req.hashes[-1])
                             if self.prefix_cache else self._snapshots.sink)
                    state_ids = dict(state_ids=jnp.asarray(
                        np.asarray([slot, entry], np.int32)))
                out = self._prefill(
                    params, lora, jnp.asarray(toks_row[None]),
                    jnp.asarray(mask_row[None]), jnp.asarray(req.key),
                    self._pool, jnp.asarray(np.asarray(prompt_blocks,
                                                       np.int32)),
                    greedy=greedy, **state_ids,
                )
                if self.capture_logprobs:
                    self._pool, tok0, _pos0, done0, key_next, lp0 = out
                else:
                    (self._pool, tok0, _pos0, done0, key_next), lp0 = out, None
                pending.append((slot, req, tok0, done0, key_next, lp0))
                shared_blocks, dup_private = [], []
                if self.prefix_cache:
                    for h, bid in zip(req.hashes[:nb_p], prompt_blocks):
                        (shared_blocks if self.allocator.register(h, bid)
                         else dup_private).append(bid)
                else:  # no cache: prompt blocks are plain private blocks
                    dup_private = list(prompt_blocks)
                table[:nb_p] = prompt_blocks
                table[nb_p:nb_p + n_dec] = dec_blocks
                self._slot_shared[slot] = shared_blocks
                self._slot_private[slot] = list(dec_blocks) + dup_private
                req.emits.append(np.asarray([1], np.int32))
                req.n_emitted = 1
                self._lengths[slot] = Pb
                self._pos[slot] = plen
                self._step_idx[slot] = 1
                self._mask[slot] = 0
                self._mask[slot, :Pb] = mask_row
                self._seed_spec_slot(slot, req)
            self._tables[slot] = table
            self._prev_ok[slot] = True
            self._slot_req[slot] = req
            # the prompt KV (if any was imported) now lives in the pool —
            # pinning the multi-MB host arrays for the decode lifetime
            # would leak slots x transfer size per replica (hit path
            # included: it carries the payload but never needed it)
            req.prefilled = None
            self.metrics.counter("serving/requests_total").inc()
            self.metrics.counter("serving/rows_total").inc()
        # ONE sync pass over every prefill dispatched above
        for slot, req, tok0, done0, key_next, lp0 in pending:
            tok0 = int(np.asarray(tok0))
            # TTFT from ARRIVAL (includes queue wait — the SLO the
            # admission controller sheds on), matching the hit path
            req.ttft_observed = True
            self._observe_ttft(time.perf_counter() - req.arrival_s)
            req.toks.append(np.asarray([tok0], np.int32))
            self._prev_tok[slot] = tok0
            self._done[slot] = bool(np.asarray(done0))
            self._keys[slot] = np.asarray(key_next, np.uint32)
            self._record_lp0(req, lp0)
            if self._proposer is not None:
                self._slot_hist[slot].append(tok0)
        for slot in list(range(self.slots)):
            req = self._slot_req[slot]
            if req is not None and (self._done[slot]
                                    or req.n_emitted >= req.max_new):
                finished.append(self._finish_slot(slot))
        return finished

    def _admit_import(self, slot: int, req: _Request, table: np.ndarray,
                      private: List[int], nb_p: int, n_dec: int, Pb: int,
                      plen: int, mask_row: np.ndarray) -> None:
        """Admit ONE externally prefilled request: scatter the imported
        prompt KV into the assigned blocks and seed the slot exactly as the
        miss path does after its local prefill returns (lengths=Pb,
        step_idx=1, prev_tok=tok0, keys=key_next) — the decode stream
        continues token-for-token as if the prefill had run here. Imported
        prompt blocks enter the prefix cache like locally prefilled ones,
        so repeats of the chain hit on this replica from now on (the
        router's affinity contract)."""
        pf = req.prefilled
        prompt_blocks, dec_blocks = private[:nb_p], private[nb_p:]
        self._pool = self._scatter_import(
            self._pool, jnp.asarray(np.asarray(prompt_blocks, np.int32)),
            jnp.asarray(pf["k"]), jnp.asarray(pf["v"]))
        self.metrics.counter(
            "serving/prefilled_imports_total",
            help="admissions whose prompt KV was imported from a prefill "
                 "worker").inc()
        shared_blocks, dup_private = [], []
        if self.prefix_cache:
            for h, bid in zip(req.hashes[:nb_p], prompt_blocks):
                (shared_blocks if self.allocator.register(h, bid)
                 else dup_private).append(bid)
        else:
            dup_private = list(prompt_blocks)
        table[:nb_p] = prompt_blocks
        table[nb_p:nb_p + n_dec] = dec_blocks
        self._slot_shared[slot] = shared_blocks
        self._slot_private[slot] = list(dec_blocks) + dup_private
        tok0 = int(pf["tok0"])
        req.toks.append(np.asarray([tok0], np.int32))
        req.emits.append(np.asarray([1], np.int32))
        req.n_emitted = 1
        # tok0 was produced by the prefill worker; it reaches the caller at
        # import time — TTFT from the ORIGINAL arrival (spans the transfer)
        req.ttft_observed = True
        self._observe_ttft(time.perf_counter() - req.arrival_s)
        self._lengths[slot] = Pb
        self._pos[slot] = plen
        self._step_idx[slot] = 1
        self._prev_tok[slot] = tok0
        self._done[slot] = bool(pf["done0"])
        self._keys[slot] = np.asarray(pf["key_next"], np.uint32)
        self._mask[slot] = 0
        self._mask[slot, :Pb] = mask_row
        self._seed_spec_slot(slot, req, tok0)
        self._record_lp0(req, pf.get("lp0"))

    # ---- speculative decoding: host-side proposer plumbing --------------- #

    def _seed_spec_slot(self, slot: int, req: _Request,
                        tok0: Optional[int] = None) -> None:
        """Seed the slot's token history (what the n-gram proposer suffix-
        matches against: the prompt, plus the prefill-produced first token
        when the admission path already has one) and look up a cached
        completion of this exact prompt — the GRPO group-repeat fast path."""
        if self._proposer is None:
            return
        hist = req.tokens.tolist()
        if tok0 is not None:
            hist.append(int(tok0))
        self._slot_hist[slot] = hist
        self._slot_plen[slot] = int(req.tokens.size)
        follow = None
        if self._completions is not None and req.speculate and req.hashes:
            follow = self._completions.get(req.hashes[-1])
        self._slot_follow[slot] = follow

    def _record_lp0(self, req: _Request, lp0) -> None:
        """First-token logprob (prefill-produced) into the request's
        captured stream — row 0 of the result's [max_new] logprob vector."""
        if not self.capture_logprobs:
            return
        if lp0 is None:
            # imported payload without lp0 (pre-speculation prefill worker):
            # keep the stream aligned; token 0 reads as 0.0
            req.lps.append(np.zeros(1, np.float32))
            return
        req.lps.append(np.asarray(lp0, np.float32).reshape(1))

    def _propose_slot(self, slot: int) -> List[int]:
        """Draft tokens for ONE slot: the completion-cache follow while the
        cached completion still agrees with what the slot actually emitted,
        else the n-gram suffix match over the slot's own history. [] for
        parked/done/opted-out slots, budget-exhausted slots, and proposer
        misses — a [] slot rides a verify step as EXACTLY one plain decode
        step (draft_len 0)."""
        req = self._slot_req[slot]
        if req is None or not req.speculate or self._done[slot]:
            return []
        # cap: n_emit <= cap + 1, so a full accept never overshoots max_new
        cap = min(self.speculate.k, req.max_new - req.n_emitted - 1)
        if cap <= 0:
            return []
        hist = self._slot_hist[slot]
        emitted = hist[self._slot_plen[slot]:]
        follow = self._slot_follow[slot]
        if follow is not None:
            n = len(emitted)
            if follow.size > n and (n == 0 or np.array_equal(
                    follow[:n], np.asarray(emitted, follow.dtype))):
                self.metrics.counter(
                    "serving/spec_follow_hits_total",
                    help="draft windows served by the completion "
                         "cache").inc()
                return follow[n:n + cap].tolist()
            self._slot_follow[slot] = None  # diverged: stop consulting it
        return self._proposer.propose(np.asarray(hist, np.int32), cap).tolist()

    def _propose_all(self) -> Tuple[np.ndarray, np.ndarray]:
        """(drafts [slots, K], draft_len [slots]) — fixed verify shapes;
        un-drafted positions are pad filler the verify step never reads."""
        K = self.speculate.k
        drafts = np.full((self.slots, K), self.pad_id, np.int32)
        dlens = np.zeros(self.slots, np.int32)
        for slot in range(self.slots):
            d = self._propose_slot(slot)
            if d:
                drafts[slot, :len(d)] = d
                dlens[slot] = len(d)
        return drafts, dlens

    def _harvest_hist(self, slot: int, toks_row: np.ndarray,
                      emits_row: np.ndarray) -> None:
        """Append a step's emitted tokens to the slot's proposer history."""
        if self._proposer is None:
            return
        self._slot_hist[slot].extend(
            toks_row[emits_row.astype(bool)].tolist())

    def _finish_slot(self, slot: int) -> int:
        """Assemble the result, release the slot's blocks to the free
        list / prefix cache, and park the slot."""
        req = self._slot_req[slot]
        toks = np.concatenate(req.toks) if req.toks else np.zeros(0, np.int32)
        emits = (np.concatenate(req.emits) if req.emits
                 else np.zeros(0, np.int32))
        N = req.max_new
        toks, emits = toks[:N], emits[:N].astype(np.int32)
        if toks.size < N:  # immediate-EOS rows may undershoot the budget
            toks = np.pad(toks, (0, N - toks.size),
                          constant_values=self.pad_id)
            emits = np.pad(emits, (0, N - emits.size))
        # masked positions are pad (the dense path's post-EOS convention)
        toks = np.where(emits.astype(bool), toks, self.pad_id).astype(np.int32)
        self._results[req.ticket] = (toks, emits)
        self._result_hits[req.ticket] = req.prefix_hit
        if self.capture_logprobs:
            lps = (np.concatenate(req.lps) if req.lps
                   else np.zeros(0, np.float32))
            lps = lps[:N].astype(np.float32)
            if lps.size < N:
                lps = np.pad(lps, (0, N - lps.size))
            # masked positions are 0.0 (the dense behavior_logprobs
            # convention under loss_mask)
            self._result_lps[req.ticket] = np.where(
                emits.astype(bool), lps, 0.0).astype(np.float32)
        if self._completions is not None and req.speculate and req.hashes:
            # finished completion becomes next repeat's draft stream (the
            # GRPO group-repeat case: same prompt => same tail chain hash)
            self._completions.put(req.hashes[-1], toks[emits.astype(bool)])
        self._slot_hist[slot] = []
        self._slot_plen[slot] = 0
        self._slot_follow[slot] = None
        self.metrics.counter("serving/tokens_decoded_total").inc(
            int(emits.sum()))
        if req.span is not None:
            # bare-generator root span: the request is complete
            req.span.set_attribute("tokens_emitted", int(emits.sum()))
            req.span.end()
            req.span = None
        self.allocator.release_shared(self._slot_shared[slot])
        self.allocator.free(self._slot_private[slot])
        self._slot_shared[slot] = []
        self._slot_private[slot] = []
        self._slot_req[slot] = None
        self._tables[slot] = 0
        self._mask[slot] = 0
        self._lengths[slot] = 0
        self._prev_tok[slot] = self.pad_id
        self._prev_ok[slot] = False
        self._pos[slot] = 0
        self._step_idx[slot] = 0
        self._done[slot] = True
        return req.ticket

    def _check_weight_epoch(self, params, lora) -> None:
        """Cached prompt KV is a pure function of (weights, chain prefix):
        a NEW params/lora tree (GRPO swaps the actor adapter every learn
        step; the flywheel adopting a published weight epoch; a server
        hot-swapping weights) invalidates every cached block. Identity
        comparison is the contract — callers that mutate a tree in place
        must call allocator.invalidate_cache() themselves.

        Queued requests carrying an EXTERNALLY prefilled prompt KV
        (disaggregated imports) were computed under the OLD weights: their
        payloads are dropped here so admission recomputes the prefill
        locally under the new weights — without this, a weight bump landing
        while an import waits for a free slot would scatter stale KV into
        the pool AND register it in the fresh prefix cache (wrong tokens
        for every future hit on that chain)."""
        if self._weights is not None and (self._weights[0] is params
                                          and self._weights[1] is lora):
            return
        self._flush_weight_epoch()
        self._weights = (params, lora)

    def end_weight_epoch(self) -> None:
        """Forget the weight trees of the epoch that ends here: what a new
        tree at the next step would flush is flushed now, and no reference
        to either tree is kept. For a caller whose trees live no longer
        than its call (``rollout_weights``: a copy in the compute type that
        must be gone before ``learn`` needs the memory) — the identity test
        above would otherwise keep the copy alive until the next rollout."""
        self._flush_weight_epoch()
        self._weights = None

    def _flush_weight_epoch(self) -> None:
        """Drop everything that was computed under the current weights (a
        generator that has seen none yet has nothing to drop)."""
        if self._weights is not None:
            if self.prefix_cache:
                self.allocator.invalidate_cache()
                self.metrics.counter(
                    "serving/prefix_cache_invalidations_total",
                    help="prefix-cache flushes on weight updates").inc()
            if self._snapshots is not None:
                # state snapshots are a function of the weights like the KV
                # blocks: the flush above dropped each with its chain; none
                # may outlive the epoch whatever the allocator still knew
                self._snapshots.clear()
            stale = 0
            # snapshot: submit() may append from a request thread while
            # the scheduler thread scans (in-place req mutation is fine,
            # iterating a deque being appended to is not)
            for req in list(self._queue):
                if req.prefilled is not None:
                    req.prefilled = None
                    stale += 1
            if stale:
                self.metrics.counter(
                    "serving/stale_imports_dropped_total",
                    help="queued prefilled imports dropped on a weight "
                         "update (recomputed by local prefill)").inc(stale)
            if self._completions is not None:
                # cached completions are a function of the weights too —
                # a stale follow would just be rejected by verify, but at
                # zero accept rate it costs a wider forward for nothing
                self._completions.clear()
                self._slot_follow = [None] * self.slots

    def step(self, params, lora=None, greedy: bool = False) -> List[int]:
        """ONE scheduler iteration: admit into free slots, then run one
        decode chunk over the pool. Returns tickets finished this step
        (fetch results with ``result()``).

        The host's work is cut into phases (``observability.PhaseTimer``:
        docs/observability.md, "Host phases"); the device is meant to be
        busy in ``sched/decode_wait`` only."""
        self._chunk += 1
        with PhaseTimer(self.metrics, "sched/step", rollout=self._rollout,
                        chunk=self._chunk):
            return self._step(params, lora, greedy)

    def _step(self, params, lora, greedy: bool) -> List[int]:
        metrics = self.metrics
        self._check_weight_epoch(params, lora)
        with PhaseTimer(metrics, "sched/admit"):
            finished = self._admit(params, lora, greedy)
        if self._occupancy() == 0:
            if self._queue and not finished:
                raise RuntimeError(
                    f"scheduler wedged: {len(self._queue)} queued requests "
                    f"but none admittable (pool of {self.n_blocks} blocks "
                    "too small for a single request?)")
            self._set_pool_gauges()
            return finished
        if self._proposer is not None:
            # hybrid scheduler: any drafted slot => ONE verify step (the
            # other slots ride it at draft_len 0); no drafts anywhere =>
            # the plain decode chunk below, exactly as without speculation
            drafts, dlens = self._propose_all()
            if int(dlens.sum()):
                return self._step_verify(params, lora, greedy, drafts,
                                         dlens, finished)
        t0 = time.perf_counter()
        with PhaseTimer(metrics, "sched/upload"):
            mirrors = [jnp.asarray(x) for x in (
                self._tables, self._mask, self._lengths, self._prev_tok,
                self._prev_ok, self._pos, self._step_idx, self._done,
                self._keys)]
        with PhaseTimer(metrics, "sched/decode_dispatch"):
            carry, ys = self._decode(params, lora, self._pool, *mirrors,
                                     greedy=greedy)
            # released while the chunk runs, not between two chunks
            del mirrors
        (self._pool, _tables, slot_mask, lengths, prev_tok, prev_ok, pos,
         step_idx, done, keys) = carry
        experts_hit = None
        if self.config.is_dropless:
            *ys, experts_hit = ys
        with PhaseTimer(metrics, "sched/decode_wait"):
            if self.capture_logprobs:
                toks, emits, lps = ys
                lps = np.asarray(lps)
            else:
                (toks, emits), lps = ys, None
            toks = np.asarray(toks)
            emits = np.asarray(emits)
        dt_chunk = time.perf_counter() - t0
        with PhaseTimer(metrics, "sched/mirror"):
            # host mirrors for the next chunk — np.array COPIES (np.asarray
            # of a device array is a read-only view; admissions mutate these
            # in place)
            self._mask = np.array(slot_mask)
            self._lengths = np.array(lengths)
            self._prev_tok = np.array(prev_tok)
            self._prev_ok = np.array(prev_ok)
            self._pos = np.array(pos)
            self._step_idx = np.array(step_idx)
            self._done = np.array(done)
            self._keys = np.array(keys)
            if experts_hit is not None:
                metrics.counter(
                    "serving/moe_experts_hit_total",
                    help="distinct experts decode steps touched, summed "
                         "over expert layers and steps",
                ).inc(int(np.asarray(experts_hit)))
                metrics.counter(
                    "serving/moe_expert_slots_total",
                    help="expert layers x experts x decode steps: what the "
                         "hits are a share of",
                ).inc(self.config.n_moe_layers * self.config.n_experts
                      * self.decode_chunk)
        with PhaseTimer(metrics, "sched/account"):
            delivered = 0
            now = time.perf_counter()
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                req.toks.append(toks[slot])
                req.emits.append(emits[slot])
                if lps is not None:
                    req.lps.append(lps[slot])
                chunk_emitted = int(emits[slot].sum())
                delivered += min(chunk_emitted, req.max_new - req.n_emitted)
                req.n_emitted += chunk_emitted
                if not req.ttft_observed and chunk_emitted:
                    # prefix-hit requests produce their first token here
                    req.ttft_observed = True
                    self._observe_ttft(now - req.arrival_s)
                self._harvest_hist(slot, toks[slot], emits[slot])
            if delivered:
                metrics.histogram(
                    "serving/decode_time_per_token_s", buckets=DECODE_BUCKETS,
                    help="decode-chunk wall time / delivered chunk tokens",
                ).observe(dt_chunk / delivered)
            if self._window_layers:
                self._set_window_dead_bytes()
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                if self._done[slot] or req.n_emitted >= req.max_new:
                    finished.append(self._finish_slot(slot))
            self._set_pool_gauges()
        return finished

    def _set_window_dead_bytes(self) -> None:
        """``serving/window_dead_bytes``: pool bytes of the live slots'
        blocks that lie WHOLLY behind the window in the window layers —
        kept (the pool has one layout and one table for every layer) and
        never read again. A block two slots share counts once. What an
        allocator with two kinds of layer would free."""
        live = np.array([req is not None for req in self._slot_req])
        # blocks a slot holds wholly behind the next query's window (host
        # mirrors: numpy, no device value)
        behind = np.maximum(
            self._lengths - self.config.sliding_window + 1, 0
        ) // self.block_size
        dead = self._tables[(np.arange(self.max_blocks)[None, :]
                             < behind[:, None]) & live[:, None]]
        layers = self.config.n_layers_of("attn")
        per_layer = M.paged_block_bytes(self._pool) // layers
        self.metrics.gauge(
            "serving/window_dead_bytes",
            help="pool bytes of live slots wholly behind a window layer's "
                 "window: held, never read",
        ).set(np.unique(dead[dead != 0]).size * per_layer
              * self._window_layers)

    def _set_pool_gauges(self) -> None:
        """Once per scheduler iteration, after its last change to either."""
        self.metrics.gauge("serving/slot_occupancy").set(self._occupancy())
        self.metrics.gauge("serving/free_blocks").set(
            self.allocator.available())

    def _step_verify(self, params, lora, greedy: bool, drafts: np.ndarray,
                     dlens: np.ndarray, finished: List[int]) -> List[int]:
        """ONE verify step over the pool: score every slot's pending token
        plus its drafts in a single fixed-shape forward and advance each
        slot by its accepted length + 1. Greedy output is token-for-token
        identical to the decode-chunk path; sampled output preserves the
        sampler's distribution (rejection sampling — llm/speculate.py)."""
        t0 = time.perf_counter()
        carry, ys = self._verify(
            params, lora, self._pool, jnp.asarray(self._tables),
            jnp.asarray(self._mask), jnp.asarray(self._lengths),
            jnp.asarray(self._prev_tok), jnp.asarray(self._prev_ok),
            jnp.asarray(self._pos), jnp.asarray(self._step_idx),
            jnp.asarray(self._done), jnp.asarray(self._keys),
            jnp.asarray(drafts), jnp.asarray(dlens),
            greedy=greedy,
        )
        if self.capture_logprobs:
            toks, emits, n_emit, n_acc, lps = ys
            lps = np.asarray(lps)
        else:
            (toks, emits, n_emit, n_acc), lps = ys, None
        (self._pool, _tables, slot_mask, lengths, prev_tok, prev_ok, pos,
         step_idx, done, keys) = carry
        toks = np.asarray(toks)
        emits = np.asarray(emits)
        dt_step = time.perf_counter() - t0
        self._mask = np.array(slot_mask)
        self._lengths = np.array(lengths)
        self._prev_tok = np.array(prev_tok)
        self._prev_ok = np.array(prev_ok)
        self._pos = np.array(pos)
        self._step_idx = np.array(step_idx)
        self._done = np.array(done)
        self._keys = np.array(keys)
        n_emit_l = np.asarray(n_emit).tolist()
        n_acc_l = np.asarray(n_acc).tolist()
        dlens_l = dlens.tolist()
        proposed = int(dlens.sum())
        accepted = 0
        delivered = 0
        now = time.perf_counter()
        acc_hist = self.metrics.histogram(
            "serving/spec_accepted_len", buckets=SPEC_LEN_BUCKETS,
            help="accepted draft tokens per drafted slot per verify step")
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            # harvest ONLY the emitted prefix — a verify row's tail is pad
            # filler, and the NEXT step keeps emitting, so keeping it would
            # break _finish_slot's emitted-tokens-are-a-stream-prefix trim
            ne = n_emit_l[slot]
            req.toks.append(toks[slot][:ne])
            req.emits.append(emits[slot][:ne].astype(np.int32))
            if lps is not None:
                req.lps.append(lps[slot][:ne])
            # the draft cap bounds n_emit by the remaining budget, so every
            # emitted token is a delivered token (unlike the chunk path,
            # which may overshoot max_new inside a chunk)
            delivered += ne
            req.n_emitted += ne
            accepted += n_acc_l[slot]
            if dlens_l[slot]:
                acc_hist.observe(n_acc_l[slot])
            if not req.ttft_observed and ne:
                req.ttft_observed = True
                self._observe_ttft(now - req.arrival_s)
            self._harvest_hist(slot, toks[slot], emits[slot])
        self.metrics.counter(
            "serving/spec_proposed_tokens_total",
            help="draft tokens submitted to verify").inc(proposed)
        self.metrics.counter(
            "serving/spec_accepted_tokens_total",
            help="draft tokens accepted by verify").inc(accepted)
        self.metrics.counter(
            "serving/spec_rejected_tokens_total",
            help="draft tokens rejected by verify").inc(proposed - accepted)
        if delivered:
            self.metrics.histogram(
                "serving/decode_time_per_token_s", buckets=DECODE_BUCKETS,
                help="decode-chunk wall time / delivered chunk tokens",
            ).observe(dt_step / delivered)
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            if self._done[slot] or req.n_emitted >= req.max_new:
                finished.append(self._finish_slot(slot))
        self._set_pool_gauges()
        return finished

    def result(self, ticket: int) -> Tuple[np.ndarray, np.ndarray]:
        """(tokens [max_new], emit mask [max_new]) for a finished ticket
        (pops it)."""
        self._result_hits.pop(ticket, None)
        return self._results.pop(ticket)

    def result_logprobs(self, ticket: int) -> Optional[np.ndarray]:
        """Decode-captured behavior logprobs [max_new] for a finished ticket
        (pops the record; None unless ``capture_logprobs``). Masked
        positions are 0.0 — the dense ``behavior_logprobs`` convention
        under a loss mask, so the flywheel consumes rows verbatim."""
        return self._result_lps.pop(ticket, None)

    def run_until_drained(self, params, lora=None,
                          greedy: bool = False) -> List[int]:
        finished: List[int] = []
        while self._queue or self._occupancy():
            finished.extend(self.step(params, lora=lora, greedy=greedy))
        return finished

    def generate(
        self,
        sequences: List[Any],
        key: jax.Array,
        params,
        lora=None,
        greedy: bool = False,
    ) -> Tuple[np.ndarray, np.ndarray, Dict[str, Any]]:
        """Batch convenience with the BucketedGenerator.generate contract:
        (completions [B, max_new_tokens], mask, info). Internally each row
        is an independent request — rows are admitted/finished per chunk, so
        a short row's slot is re-used while long rows still decode."""
        B = len(sequences)
        if B == 0:
            raise ValueError(
                "ContinuousGenerator.generate got an empty sequence list; "
                "callers should gate batches with fits(n_rows, longest)")
        # validate EVERY row before enqueueing ANY: a mid-batch submit()
        # failure would orphan the earlier rows in the queue (served and
        # leaked by the next caller)
        lengths = [len(s) for s in sequences]
        if not self.fits(B, max(lengths)) or min(lengths) == 0:
            raise ValueError(
                f"prompt lengths {min(lengths)}..{max(lengths)} outside the "
                f"bucket grid (1..{self.prompt_buckets[-1]}); check fits() "
                "and fall back to the dense generate path")
        hits0 = self.metrics.counter("serving/prefix_cache_hits_total").value
        self._rollout += 1
        self._chunk = 0
        with PhaseTimer(self.metrics, "sched/submit"):
            tickets = [
                self.submit(s, key=jax.random.fold_in(key, i), no_shed=True)
                for i, s in enumerate(sequences)
            ]
        self.run_until_drained(params, lora=lora, greedy=greedy)
        with PhaseTimer(self.metrics, "sched/collect"):
            N = self.max_new_tokens
            comp = np.full((B, N), self.pad_id, np.int32)
            cmask = np.zeros((B, N), np.int32)
            lps = (np.zeros((B, N), np.float32) if self.capture_logprobs
                   else None)
            hit_rows = [self._result_hits.get(t, False) for t in tickets]
            for i, t in enumerate(tickets):
                toks, emits = self.result(t)
                comp[i, :toks.size] = toks
                cmask[i, :emits.size] = emits
                if lps is not None:
                    row = self.result_logprobs(t)
                    if row is not None:
                        lps[i, :row.size] = row
            info = {
                "slots": self.slots,
                "block_size": self.block_size,
                "compiled_programs": self.compiled_programs,
                "prefix_cache_hits": int(self.metrics.counter(
                    "serving/prefix_cache_hits_total").value - hits0),
                "free_blocks": self.allocator.available(),
                "max_new_tokens": N,
            }
            self.metrics.emit("serving", rows=B, **info)
        # after emit(): telemetry lines carry scalars, not per-row arrays
        info["prefix_hit_rows"] = hit_rows
        if lps is not None:
            info["logprobs"] = lps
        return comp, cmask, info

    def latency_summary(self) -> Dict[str, Any]:
        """The serving SLO readout: BucketedGenerator's percentiles PLUS the
        continuous-tier occupancy / shed / queue-wait telemetry."""
        reg = self.metrics
        return {
            "ttft_s": reg.histogram(
                "serving/ttft_s", buckets=TTFT_BUCKETS).summary(),
            "decode_time_per_token_s": reg.histogram(
                "serving/decode_time_per_token_s",
                buckets=DECODE_BUCKETS).summary(),
            "queue_wait_s": reg.histogram(
                "serving/queue_wait_s", buckets=QUEUE_WAIT_BUCKETS).summary(),
            "queue_depth_rows": reg.histogram(
                "serving/queue_depth_rows", buckets=QUEUE_BUCKETS).summary(),
            "requests_total": reg.counter("serving/requests_total").value,
            "rows_total": reg.counter("serving/rows_total").value,
            "tokens_decoded_total": reg.counter(
                "serving/tokens_decoded_total").value,
            "shed_requests_total": reg.counter(
                "serving/shed_requests_total").value,
            "prefix_cache_hits_total": reg.counter(
                "serving/prefix_cache_hits_total").value,
            "slot_occupancy": reg.gauge("serving/slot_occupancy").value,
            "free_blocks": reg.gauge("serving/free_blocks").value,
            "spec_proposed_tokens_total": reg.counter(
                "serving/spec_proposed_tokens_total").value,
            "spec_accepted_tokens_total": reg.counter(
                "serving/spec_accepted_tokens_total").value,
            "spec_rejected_tokens_total": reg.counter(
                "serving/spec_rejected_tokens_total").value,
            "spec_accepted_len": reg.histogram(
                "serving/spec_accepted_len",
                buckets=SPEC_LEN_BUCKETS).summary(),
        }

    @property
    def compiled_programs(self) -> int:
        """Prefill (per prompt bucket) + decode chunk (ONE program) + verify
        (ONE program when speculating — fixed [slots, K] draft shape, so
        accept outcomes never add programs) + block copy + import scatter
        (per prompt bucket, disaggregated only) — bounded by the grid,
        constant in request count/order (the tier-1 regression test pins
        this; see measured_cache_size)."""
        return measured_cache_size(self._prefill, self._decode,
                                   self._verify, self._copy_block,
                                   self._scatter_import)
