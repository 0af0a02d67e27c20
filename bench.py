"""Benchmark modes of the repo, one per run, in this one process.

`python bench.py` runs the mode named by BENCH_MODE (default `evoppo`) on
whatever backend JAX gives it and prints ONE JSON line. Every line names the
device it ran on (`platform`, `device_kind`, `device_count`). There is no
probe, no child, no fallback to another backend and no stored result: a mode
that raises, or that reports an `error`, ends the process with a non-zero
exit code.

Modes: `evoppo` (the EvoPPO population program — rollout -> GAE -> PPO
epochs -> tournament -> mutation, one jitted program — on JAX CartPole,
aggregate env-steps/sec) and `grpo` (GRPO learn-step tokens/sec) run at the
chip's sizes on an accelerator and at smaller default sizes on the CPU
backend. The rest are A/B comparisons written for the CPU backend:
`pipeline` / `serving` / `trace` / `fleet` (1-replica vs 2-replica
ServingFleet on a repeated-prompt trace) / `flywheel` (disaggregated
online-GRPO flywheel vs the interleaved loop) / `launch` / `anakin`
(scan-resident generation engine vs the interop off-policy hot loop) /
`sharding` / `elastic` (MTTR under a scripted host kill + heartbeat overhead
on the pod emulation; it asks the CPU backend for 4 virtual devices) /
`compile_cache` (serving replica spin-up with the executable store cold vs
warm) / `traffic` (synthetic-load scenarios graded against an SLO spec).
A number from a CPU run is a CPU number; the JSON line says so.

Env knobs: BENCH_POP/ENVS/ROLLOUT/GENS and BENCH_GRPO_BATCH/SEQ/LAYERS for
scale; each A/B mode documents its own. JAX's persistent compilation cache is
on: at JAX_COMPILATION_CACHE_DIR where that is set, else `.jax_cache` in the
checkout (agilerl_tpu.parallel.compile_cache.enable_jax_cache).
"""

import json
import os
import sys
import time


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def emit(result):
    """Print a mode's ONE JSON line, naming the device it ran on. A result
    that carries an `error` ends the run non-zero after it is printed."""
    import jax

    devices = jax.devices()
    result.update(platform=devices[0].platform,
                  device_kind=devices[0].device_kind,
                  device_count=len(devices))
    print(json.dumps(result), flush=True)
    if result.get("error"):
        raise SystemExit(f"bench: {result['error']}")


# --------------------------------------------------------------------------
# The modes.
# --------------------------------------------------------------------------


def grpo_learn_cell(B, T, n_layer, iters=3):
    """Time the fused GRPO learn step on a GPT-2-small-class model."""
    import jax.numpy as jnp
    import numpy as np

    from agilerl_tpu.algorithms.grpo import GRPO
    from agilerl_tpu.llm import model as M
    from agilerl_tpu.utils.profiling import estimate_mfu

    cfg = M.GPTConfig(
        vocab_size=32_000, n_layer=n_layer, n_head=12, d_model=768,
        max_seq_len=T,
    )
    agent = GRPO(config=cfg, pad_token_id=0, eos_token_id=1, group_size=4,
                 batch_size=B, seed=0)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(2, 31_000, size=(B, T)).astype(np.int32))
    loss_mask = np.zeros((B, T - 1), np.float32)
    loss_mask[:, T // 2:] = 1.0
    rewards = rng.normal(size=(B // 4, 4)).astype(np.float32)
    exp = (ids, jnp.asarray(loss_mask), jnp.asarray(rewards))
    agent.learn(exp)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        agent.learn(exp)
    dt = (time.perf_counter() - t0) / iters
    tokens = B * T
    return {
        "tokens_per_sec": round(tokens / dt),
        "mfu": round(estimate_mfu(cfg, tokens, dt), 4),
        "step_seconds": round(dt, 4),
    }


def bench_grpo():
    """Secondary bench: GRPO learn-step tokens/sec + MFU on a GPT-2-small-class
    model (the BASELINE.md LLM metric at reduced scale for one chip)."""
    import jax

    backend = jax.default_backend()
    on_cpu = backend == "cpu"
    B = int(os.environ.get("BENCH_GRPO_BATCH", 4 if on_cpu else 16))
    T = int(os.environ.get("BENCH_GRPO_SEQ", 128 if on_cpu else 512))
    n_layer = int(os.environ.get("BENCH_GRPO_LAYERS", 2 if on_cpu else 12))
    log(f"bench_grpo: backend={backend} B={B} T={T} layers={n_layer}; compiling")
    cell = grpo_learn_cell(B, T, n_layer)
    result = {
        "metric": f"GRPO learn-step tokens/sec (GPT2-small class, B={B} T={T})",
        "value": cell["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(cell["mfu"] / 0.35, 3),  # BASELINE: 35% MFU target
        "backend": backend,
        "error": None,
    }
    # a run under a compile-path kill switch must say so
    from agilerl_tpu.ops.kernel_mode import active_kill_switches

    disabled = active_kill_switches()
    if disabled:
        result["kill_switches"] = disabled
    emit(result)


def bench_evoppo():
    import jax
    import numpy as np
    import optax

    from agilerl_tpu.envs import CartPole
    from agilerl_tpu.modules.mlp import MLPConfig
    from agilerl_tpu.networks import distributions as D
    from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
    from agilerl_tpu.parallel.population import EvoPPO

    backend = jax.default_backend()
    on_cpu = backend == "cpu"
    # on an accelerator the defaults are the BASELINE.md workload; the CPU
    # backend's are sized for one core
    pop_size = int(os.environ.get("BENCH_POP", 16 if on_cpu else 64))
    num_envs = int(os.environ.get("BENCH_ENVS", 64 if on_cpu else 128))
    rollout_len = int(os.environ.get("BENCH_ROLLOUT", 64))
    generations = int(os.environ.get("BENCH_GENS", 4 if on_cpu else 5))

    env = CartPole()
    kind, enc = default_encoder_config(
        env.observation_space, latent_dim=64, encoder_config={"hidden_size": (64,)}
    )
    actor_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc,
        head=MLPConfig(num_inputs=64, num_outputs=2, hidden_size=(64,)), latent_dim=64,
    )
    critic_cfg = NetworkConfig(
        encoder_kind=kind, encoder=enc,
        head=MLPConfig(num_inputs=64, num_outputs=1, hidden_size=(64,)), latent_dim=64,
    )
    dist_cfg = D.dist_config_from_space(env.action_space)
    evo = EvoPPO(
        env, actor_cfg, critic_cfg, dist_cfg, optax.adam(3e-4),
        num_envs=num_envs, rollout_len=rollout_len, update_epochs=1, num_minibatches=4,
    )
    log(f"bench: backend={backend} devices={jax.devices()} pop={pop_size} "
        f"envs={num_envs} rollout={rollout_len} gens={generations}")
    pop = evo.init_population(jax.random.PRNGKey(0), pop_size)
    gen = evo.make_vmap_generation()

    # compile + warmup
    t_c = time.perf_counter()
    pop, fitness = gen(pop, jax.random.PRNGKey(1))
    jax.block_until_ready(fitness)
    log(f"bench: compiled+warmed in {time.perf_counter() - t_c:.1f}s")

    first_fitness = np.asarray(fitness)

    t0 = time.perf_counter()
    for i in range(generations):
        pop, fitness = gen(pop, jax.random.PRNGKey(2 + i))
    jax.block_until_ready(fitness)
    dt = time.perf_counter() - t0
    final_fitness = np.asarray(fitness)

    env_steps = pop_size * num_envs * rollout_len * generations
    sps = env_steps / dt
    baseline = 1_000_000.0  # BASELINE.md: >=1M env-steps/sec aggregate
    # achieved-FLOPs utilisation of the whole generation program (rollout +
    # GAE + PPO epochs + evolution) from XLA's own cost analysis — BASELINE
    # reports dual metrics (steps/s AND utilisation), so do we (VERDICT r3 #8)
    from agilerl_tpu.utils.profiling import achieved_flops_metrics

    flops_metrics = achieved_flops_metrics(
        gen.lower(pop, jax.random.PRNGKey(0)), generations, dt
    )
    emit({
        "metric": f"evo-PPO pop={pop_size} aggregate env-steps/sec",
        "value": round(sps),
        "unit": "env-steps/sec",
        "vs_baseline": round(sps / baseline, 3),
        "backend": backend,
        "error": None,
        # the measured program is demonstrably a LEARNING loop (VERDICT r4
        # #2): population fitness at warmup vs after the timed generations.
        # Long runs (BENCH_GENS) show real improvement; the learning-curve
        # proof lives in tests/test_parallel/test_population.py.
        "first_fitness_best": round(float(first_fitness.max()), 1),
        "final_fitness_best": round(float(final_fitness.max()), 1),
        "final_fitness_mean": round(float(final_fitness.mean()), 1),
        **flops_metrics,
    })


def bench_pipeline():
    """CPU-backend micro-bench for the host↔device pipelining layer
    (docs/performance.md): the SAME DQN/CartPole interop hot loop run
    per-step (eager buffer adds + host-driven sample→learn round-trips) vs
    chunked+fused (staged ingestion + single-dispatch learn_from_buffer).
    Run with BENCH_MODE=pipeline; knobs BENCH_PIPE_ENVS / BENCH_PIPE_STEPS."""
    import jax
    import numpy as np

    from agilerl_tpu.components.replay_buffer import ReplayBuffer
    from agilerl_tpu.components.sampler import Sampler
    from agilerl_tpu.envs import CartPole, JaxVecEnv
    from agilerl_tpu.utils.utils import create_population

    backend = jax.default_backend()
    num_envs = int(os.environ.get("BENCH_PIPE_ENVS", 8))
    steps = int(os.environ.get("BENCH_PIPE_STEPS", 384))
    learn_step = 4

    def run(chunked: bool) -> float:
        env = JaxVecEnv(CartPole(), num_envs=num_envs, seed=0)
        agent = create_population(
            "DQN", env.single_observation_space, env.single_action_space,
            population_size=1, seed=0,
            net_config={"latent_dim": 32,
                        "encoder_config": {"hidden_size": (64,)}},
            INIT_HP={"BATCH_SIZE": 64, "LR": 1e-3, "LEARN_STEP": learn_step},
        )[0]
        memory = ReplayBuffer(max_size=10_000, seed=0,
                              flush_every=8 if chunked else 1)
        sampler = Sampler(memory=memory)

        def loop(n_steps):
            # the pipelining layer targets HOST (gym-interop) envs, so the
            # probe env's outputs are materialised to host numpy exactly as
            # a gymnasium vector env would hand them over
            obs, _ = env.reset()
            obs = np.asarray(obs)
            pending = None
            for t in range(n_steps):
                action = agent.get_action(obs, epsilon=0.1)
                next_obs, reward, term, trunc, _ = env.step(np.asarray(action))
                next_obs = np.asarray(next_obs)
                tr = {"obs": obs, "action": np.asarray(action),
                      "reward": np.asarray(reward, np.float32),
                      "next_obs": next_obs,
                      "done": np.asarray(term, np.float32)}
                if chunked:
                    memory.stage(tr, batched=True)
                else:
                    memory.add(tr, batched=True)
                obs = next_obs
                if t % learn_step == 0:
                    if chunked:
                        memory.flush()
                    if len(memory) >= agent.batch_size:
                        if chunked:
                            pending = agent.learn_from_buffer(memory)
                        else:
                            agent.learn(sampler.sample(agent.batch_size))
            if pending is not None:
                jax.block_until_ready(pending)

        loop(max(steps // 4, 2 * learn_step * 64 // num_envs))  # compile+warmup
        t0 = time.perf_counter()
        loop(steps)
        return steps * num_envs / (time.perf_counter() - t0)

    # alternate the two paths and keep each one's best run: single-shot A/B
    # on a shared CPU host is dominated by scheduling noise
    repeats = int(os.environ.get("BENCH_PIPE_REPEATS", 2))
    per_step_sps = max(run(chunked=False) for _ in range(repeats))
    fused_sps = max(run(chunked=True) for _ in range(repeats))
    speedup = fused_sps / max(per_step_sps, 1e-9)
    log(f"bench_pipeline: per-step {per_step_sps:.0f} vs chunked+fused "
        f"{fused_sps:.0f} env-steps/s ({speedup:.2f}x)")
    emit({
        "metric": ("off-policy interop hot loop chunked+fused env-steps/sec "
                   f"(DQN CartPole, {num_envs} envs; vs_baseline = speedup "
                   "over the per-step path)"),
        "value": round(fused_sps),
        "unit": "env-steps/sec",
        "vs_baseline": round(speedup, 3),
        "per_step_env_steps_per_sec": round(per_step_sps),
        "chunked_fused_env_steps_per_sec": round(fused_sps),
        "backend": backend,
        "error": None,
    })


def bench_serving():
    """CPU-backend micro-bench for the serving tier (docs/serving.md): the
    SAME ragged request trace — mixed prompt lengths, >=4x spread in output
    budgets, periodic repeated prompts — served batch-synchronously
    (BucketedGenerator: every row pays the batch max decode length) vs
    continuously (ContinuousGenerator: slots recycle per chunk, repeats hit
    the prefix cache). Run with BENCH_MODE=serving; knobs BENCH_SERVE_REQS /
    BENCH_SERVE_REPEATS."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.serving import BucketedGenerator, ContinuousGenerator
    from agilerl_tpu.observability import MetricsRegistry

    backend = jax.default_backend()
    n_reqs = int(os.environ.get("BENCH_SERVE_REQS", 24))
    repeats = int(os.environ.get("BENCH_SERVE_REPEATS", 2))
    # sized so per-token forward cost dominates dispatch overhead (the
    # regime real serving lives in — at toy widths the A/B would measure
    # python scheduling, not decode waste)
    d_model = int(os.environ.get("BENCH_SERVE_DMODEL", 256))
    n_layer = int(os.environ.get("BENCH_SERVE_LAYERS", 4))
    cfg = M.GPTConfig(vocab_size=512, n_layer=n_layer, n_head=4, n_kv_head=2,
                      d_model=d_model, max_seq_len=256, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    max_new, chunk, rows = 64, 8, 8
    # heavy-tailed output lengths (the real serving distribution): 16x
    # spread — a batch-synchronous batch pays the 64-token straggler for
    # every row, continuous slots recycle at chunk granularity
    budgets_cycle = (4, 8, 16, 64)
    def make_trace(seed):
        rng = np.random.default_rng(seed)
        base_prompt = rng.integers(3, 500, size=14).astype(np.int32)
        trace = []
        for i in range(n_reqs):
            if i % 4 == 3:  # periodic repeat: the prefix-cache case
                prompt = base_prompt
            else:
                prompt = rng.integers(
                    3, 500, size=int(rng.integers(4, 28))).astype(np.int32)
            trace.append((prompt, budgets_cycle[i % len(budgets_cycle)]))
        return trace

    # ONE generator per path, fully warmed OUTSIDE the timed region (the
    # compile-once model is the whole point); each timed repeat serves a
    # FRESH trace so cross-repeat prefix-cache hits can't flatter the
    # continuous path — only the within-trace repeats may hit
    bgen = BucketedGenerator(cfg, max_new_tokens=max_new, pad_id=0,
                             eos_id=None, prompt_buckets=(32,),
                             row_buckets=(rows,), decode_chunk=chunk,
                             metrics=MetricsRegistry())
    cgen = ContinuousGenerator(cfg, max_new_tokens=max_new, pad_id=0,
                               eos_id=None, prompt_buckets=(32,),
                               slots=rows, block_size=8,
                               decode_chunk=chunk, metrics=MetricsRegistry())

    def serve_bucketed(trace):
        for i in range(0, len(trace), rows):
            batch = [p for p, _ in trace[i:i + rows]]
            bgen.generate(batch, jax.random.PRNGKey(i), params, greedy=True)
            # batch-synchronous: every row decoded max_new steps; the caller
            # trims to its budget — the waste this bench meters

    def serve_continuous(trace):
        for i, (p, b) in enumerate(trace):
            cgen.submit(p, max_new=b, key=jax.random.fold_in(
                jax.random.PRNGKey(0), i), no_shed=True)
        cgen.run_until_drained(params, greedy=True)

    warm = make_trace(7)  # distinct seed: warms all programs incl. the
    serve_bucketed(warm)  # prefix-hit block copy, donates no cache help
    serve_continuous(warm)
    traces = [make_trace(100 + r) for r in range(repeats)]
    best = {}
    for name, serve in (("bucketed", serve_bucketed),
                        ("continuous", serve_continuous)):
        gen = bgen if name == "bucketed" else cgen
        for trace in traces:
            gen.metrics = reg = MetricsRegistry()
            delivered = sum(b for _, b in trace)
            t0 = time.perf_counter()
            serve(trace)
            tps = delivered / (time.perf_counter() - t0)
            if name not in best or tps > best[name][0]:
                best[name] = (tps, gen.latency_summary())
    b_tps, b_sum = best["bucketed"]
    c_tps, c_sum = best["continuous"]
    speedup = c_tps / max(b_tps, 1e-9)
    log(f"bench_serving: bucketed {b_tps:.0f} vs continuous {c_tps:.0f} "
        f"delivered tokens/s ({speedup:.2f}x), p95 TTFT "
        f"{b_sum['ttft_s']['p95']:.4f}s vs {c_sum['ttft_s']['p95']:.4f}s")
    emit({
        "metric": ("serving-tier delivered tokens/sec, continuous+paged vs "
                   f"batch-synchronous ({n_reqs} ragged requests, budgets "
                   f"{budgets_cycle}; vs_baseline = speedup over "
                   "BucketedGenerator)"),
        "value": round(c_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(speedup, 3),
        "bucketed_tokens_per_sec": round(b_tps, 1),
        "continuous_tokens_per_sec": round(c_tps, 1),
        "p95_ttft_s": {"bucketed": round(b_sum["ttft_s"]["p95"], 5),
                       "continuous": round(c_sum["ttft_s"]["p95"], 5)},
        # the SLO readout the admission controller keys on — shed/queue-wait
        # visibility required by the serving acceptance gate
        "continuous_latency_summary": {
            "queue_wait_s_p95": round(c_sum["queue_wait_s"]["p95"], 5),
            "shed_requests_total": c_sum["shed_requests_total"],
            "prefix_cache_hits_total": c_sum["prefix_cache_hits_total"],
            "tokens_decoded_total": c_sum["tokens_decoded_total"],
        },
        "backend": backend,
        "error": None,
    })

    # ---- speculation on/off A/B on the GRPO-repeat / prefix-skew trace --
    # The speculative sweet spot: group_size repeats of each prompt land
    # AFTER the first completion finished (wave-ordered, like GRPO group
    # rollouts draining through a fleet), so the completion cache drafts
    # whole continuations and verify retires K+1 tokens per forward where
    # the chunk path pays one forward per token.
    spec_k = int(os.environ.get("BENCH_SPEC_K", 8))
    n_prompts = int(os.environ.get("BENCH_SPEC_PROMPTS", 6))
    n_waves = int(os.environ.get("BENCH_SPEC_WAVES", 4))
    spec_budget = 32

    def make_waves(seed):
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(3, 500, size=int(rng.integers(8, 28)))
                   .astype(np.int32) for _ in range(n_prompts)]
        return [[(p, spec_budget) for p in prompts]
                for _ in range(n_waves)]

    def spec_gen(speculate):
        return ContinuousGenerator(
            cfg, max_new_tokens=spec_budget, pad_id=0, eos_id=None,
            prompt_buckets=(32,), slots=rows, block_size=8,
            decode_chunk=chunk, metrics=MetricsRegistry(),
            speculate=speculate)

    def serve_waves(gen, waves, seed):
        out, i = [], 0
        for wave in waves:
            tickets = []
            for p, b in wave:
                tickets.append(gen.submit(
                    p, max_new=b,
                    key=jax.random.fold_in(jax.random.PRNGKey(seed), i),
                    no_shed=True))
                i += 1
            gen.run_until_drained(params, greedy=True)
            out.extend(gen.result(t)[0] for t in tickets)
        return out

    g_off = spec_gen(None)
    g_on = spec_gen({"k": spec_k})
    warm_waves = make_waves(7)
    serve_waves(g_off, warm_waves, 7)
    serve_waves(g_on, warm_waves, 7)
    spec_traces = [make_waves(200 + r) for r in range(repeats)]
    best_spec = {}
    for name, gen in (("off", g_off), ("on", g_on)):
        for r, waves in enumerate(spec_traces):
            gen.metrics = MetricsRegistry()
            delivered = sum(b for wave in waves for _, b in wave)
            t0 = time.perf_counter()
            toks = serve_waves(gen, waves, 200 + r)
            tps = delivered / (time.perf_counter() - t0)
            if name not in best_spec or tps > best_spec[name][0]:
                best_spec[name] = (tps, gen.latency_summary(), toks)
    off_tps, _off_sum, off_toks = best_spec["off"]
    on_tps, on_sum, on_toks = best_spec["on"]
    # greedy speculation is a pure perf knob: token-identical or the A/B
    # is meaningless (tier-1 pins this; cheap to re-assert here)
    for a, b in zip(off_toks[:n_prompts], on_toks[:n_prompts]):
        np.testing.assert_array_equal(a, b)
    spec_speedup = on_tps / max(off_tps, 1e-9)
    proposed = on_sum["spec_proposed_tokens_total"]
    accepted = on_sum["spec_accepted_tokens_total"]
    log(f"bench_serving[spec]: off {off_tps:.0f} vs on {on_tps:.0f} "
        f"delivered tokens/s ({spec_speedup:.2f}x), accept rate "
        f"{accepted / max(proposed, 1):.2f}")
    emit({
        "metric": ("serving-tier delivered tokens/sec, speculative decoding "
                   f"on vs off (GRPO-repeat/prefix-skew trace: {n_prompts} "
                   f"prompts x {n_waves} waves, budget {spec_budget}; "
                   "vs_baseline = speedup over speculation off)"),
        "value": round(on_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(spec_speedup, 3),
        "spec_off_tokens_per_sec": round(off_tps, 1),
        "spec_on_tokens_per_sec": round(on_tps, 1),
        "spec_accepted_len": on_sum["spec_accepted_len"],
        "spec_proposed_tokens_total": proposed,
        "spec_accepted_tokens_total": accepted,
        "spec_rejected_tokens_total": on_sum["spec_rejected_tokens_total"],
        "proposer_accept_rate": round(accepted / max(proposed, 1), 4),
        # provenance: what was measured, under which speculation recipe
        "provenance": {
            "speculate": {"k": spec_k},
            "trace": {"prompts": n_prompts, "waves": n_waves,
                      "budget": spec_budget, "slots": rows,
                      "decode_chunk": chunk},
            "greedy_token_identical": True,
        },
        "backend": backend,
        "error": None,
    })


def bench_trace():
    """CPU-backend tracing-overhead A/B (docs/observability.md): the SAME
    ragged serving trace replayed on two warmed ContinuousGenerators — one
    with tracing unconfigured (the no-op default), one with a live tracer
    at anomaly-only sampling (sample_rate=0: per-request root spans are
    created with real ids, but nothing records except forced anomalies) —
    and the overhead %% in the provenance JSON. The acceptance target is
    <= ~2%% (tracing disabled must be a true hot-path no-op, and
    anomaly-only sampling close to one). Run with BENCH_MODE=trace; knobs
    BENCH_TRACE_REQS / BENCH_TRACE_REPEATS."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.serving import ContinuousGenerator
    from agilerl_tpu.observability import JsonlSink, MetricsRegistry, Tracer

    backend = jax.default_backend()
    n_reqs = int(os.environ.get("BENCH_TRACE_REQS", 24))
    repeats = int(os.environ.get("BENCH_TRACE_REPEATS", 3))
    d_model = int(os.environ.get("BENCH_TRACE_DMODEL", 256))
    cfg = M.GPTConfig(vocab_size=512, n_layer=4, n_head=4, n_kv_head=2,
                      d_model=d_model, max_seq_len=256, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    max_new, chunk, rows = 64, 8, 8
    budgets_cycle = (4, 8, 16, 64)

    def make_trace(seed):
        rng = np.random.default_rng(seed)
        trace = []
        for i in range(n_reqs):
            prompt = rng.integers(
                3, 500, size=int(rng.integers(4, 28))).astype(np.int32)
            trace.append((prompt, budgets_cycle[i % len(budgets_cycle)]))
        return trace

    def make_gen(tracer=None):
        return ContinuousGenerator(
            cfg, max_new_tokens=max_new, pad_id=0, eos_id=None,
            prompt_buckets=(32,), slots=rows, block_size=8,
            decode_chunk=chunk, metrics=MetricsRegistry(), tracer=tracer)

    span_path = os.path.join(tempfile.mkdtemp(prefix="bench_trace_"),
                             "spans.jsonl")
    tracer_on = Tracer(sink=JsonlSink(span_path), sample_rate=0.0,
                       pod="bench", metrics=MetricsRegistry())
    # a DISABLED tracer object (no sink) pins the no-op path explicitly —
    # identical to leaving tracing unconfigured
    gens = {"off": make_gen(Tracer()), "on": make_gen(tracer_on)}

    def serve(gen, trace):
        for i, (p, b) in enumerate(trace):
            gen.submit(p, max_new=b, key=jax.random.fold_in(
                jax.random.PRNGKey(0), i), no_shed=True)
        gen.run_until_drained(params, greedy=True)

    warm = make_trace(7)
    for gen in gens.values():
        serve(gen, warm)
    traces = [make_trace(100 + r) for r in range(repeats)]
    best = {}
    for name, gen in gens.items():
        for trace in traces:
            delivered = sum(b for _, b in trace)
            t0 = time.perf_counter()
            serve(gen, trace)
            tps = delivered / (time.perf_counter() - t0)
            best[name] = max(best.get(name, 0.0), tps)
    overhead_pct = 100.0 * (1.0 - best["on"] / max(best["off"], 1e-9))
    spans_recorded = int(tracer_on.metrics.counter(
        "trace/spans_total").value)
    log(f"bench_trace: tracing-off {best['off']:.0f} vs anomaly-only "
        f"{best['on']:.0f} delivered tokens/s "
        f"(overhead {overhead_pct:+.2f}%, {spans_recorded} spans recorded)")
    emit({
        "metric": ("serving delivered tokens/sec, tracing-off vs "
                   f"tracing-on at anomaly-only sampling ({n_reqs} ragged "
                   "requests; vs_baseline = on/off ratio, overhead_pct = "
                   "the acceptance number, target <= ~2%)"),
        "value": round(best["on"], 1),
        "unit": "tokens/sec",
        "vs_baseline": round(best["on"] / max(best["off"], 1e-9), 4),
        "overhead_pct": round(overhead_pct, 3),
        "tracing_off_tokens_per_sec": round(best["off"], 1),
        "tracing_on_sampled_tokens_per_sec": round(best["on"], 1),
        # anomaly-only sampling on a healthy trace records NOTHING — a
        # nonzero count here means steady spans leaked past the sampler
        "spans_recorded": spans_recorded,
        "backend": backend,
        "error": None,
    })


def bench_fleet():
    """CPU-backend A/B for the serving fleet (docs/serving.md): the SAME
    ragged request trace — mixed prompt lengths, spread output budgets,
    periodic repeated prompts — served by a 1-replica vs a 2-replica
    ``ServingFleet``. On one CPU core the replicas timeshare, so this A/B
    meters the COMPOSITION COST of the fleet layer (routing, affinity,
    per-replica scheduling) and its affinity hit rate — the scale-out win
    itself needs real parallel devices. Run with BENCH_MODE=fleet; knobs
    BENCH_FLEET_REQS / BENCH_FLEET_REPEATS."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.fleet import ServingFleet
    from agilerl_tpu.observability import MetricsRegistry

    backend = jax.default_backend()
    n_reqs = int(os.environ.get("BENCH_FLEET_REQS", 24))
    repeats = int(os.environ.get("BENCH_FLEET_REPEATS", 2))
    d_model = int(os.environ.get("BENCH_FLEET_DMODEL", 256))
    n_layer = int(os.environ.get("BENCH_FLEET_LAYERS", 4))
    cfg = M.GPTConfig(vocab_size=512, n_layer=n_layer, n_head=4, n_kv_head=2,
                      d_model=d_model, max_seq_len=256, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    max_new, chunk, slots = 64, 8, 4
    budgets_cycle = (4, 8, 16, 64)

    def make_trace(seed):
        rng = np.random.default_rng(seed)
        base_prompt = rng.integers(3, 500, size=14).astype(np.int32)
        trace = []
        for i in range(n_reqs):
            if i % 4 == 3:  # periodic repeat: the affinity/prefix-cache case
                prompt = base_prompt
            else:
                prompt = rng.integers(
                    3, 500, size=int(rng.integers(4, 28))).astype(np.int32)
            trace.append((prompt, budgets_cycle[i % len(budgets_cycle)]))
        return trace

    kw = dict(max_new_tokens=max_new, pad_id=0, eos_id=None,
              prompt_buckets=(32,), slots=slots, block_size=8,
              decode_chunk=chunk)
    fleets = {
        "1-replica": ServingFleet(cfg, 1, metrics=MetricsRegistry(), **kw),
        "2-replica": ServingFleet(cfg, 2, metrics=MetricsRegistry(), **kw),
    }

    def serve(fleet, trace):
        tickets = []
        for i, (p, b) in enumerate(trace):
            tickets.append(fleet.submit(
                p, max_new=b,
                key=jax.random.fold_in(jax.random.PRNGKey(0), i),
                no_shed=True))
        fleet.run_until_drained(params, greedy=True)
        for t in tickets:
            fleet.result(t)

    # warm every program (compile-once model) outside the timed region;
    # fresh traces per timed repeat so only within-trace repeats may hit
    for fleet in fleets.values():
        serve(fleet, make_trace(7))
    traces = [make_trace(100 + r) for r in range(repeats)]
    counter_keys = ("fleet/affinity_hits_total",
                    "fleet/routed_requests_total",
                    "fleet/rebalanced_requests_total",
                    "fleet/torn_kv_transfers_total",
                    "serving/shed_requests_total")
    best = {}
    for name, fleet in fleets.items():
        reg = fleet.metrics
        for trace in traces:
            # per-trace counter DELTAS: the headline is best-of-repeats, so
            # cumulative (warmup-spanning) counters would disagree with it
            before = {k: reg.counter(k).value for k in counter_keys}
            delivered = sum(b for _, b in trace)
            t0 = time.perf_counter()
            serve(fleet, trace)
            tps = delivered / (time.perf_counter() - t0)
            deltas = {k.split("/")[-1]: reg.counter(k).value - before[k]
                      for k in counter_keys}
            if name not in best or tps > best[name][0]:
                best[name] = (tps, deltas)
    one_tps, one_d = best["1-replica"]
    two_tps, two_d = best["2-replica"]
    one_hit = one_d["affinity_hits_total"] / max(one_d["routed_requests_total"], 1)
    two_hit = two_d["affinity_hits_total"] / max(two_d["routed_requests_total"], 1)
    ratio = two_tps / max(one_tps, 1e-9)
    log(f"bench_fleet: 1-replica {one_tps:.0f} vs 2-replica {two_tps:.0f} "
        f"delivered tokens/s ({ratio:.2f}x on one core), affinity hit rate "
        f"{two_hit:.2f}, shed {two_d['shed_requests_total']:.0f}")
    emit({
        "metric": ("serving-fleet delivered tokens/sec, 2-replica vs "
                   f"1-replica ServingFleet ({n_reqs} ragged requests, "
                   f"budgets {budgets_cycle}, repeated prompts; replicas "
                   "TIMESHARE one CPU core, so vs_baseline meters fleet-"
                   "layer composition cost, not scale-out)"),
        "value": round(two_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(ratio, 3),
        "one_replica_tokens_per_sec": round(one_tps, 1),
        "two_replica_tokens_per_sec": round(two_tps, 1),
        "affinity_hit_rate": {"1-replica": round(one_hit, 3),
                              "2-replica": round(two_hit, 3)},
        # counters for the SAME best trace the headline reports
        "best_trace_counters": {"1-replica": one_d, "2-replica": two_d},
        "replica_count": fleets["2-replica"].latency_summary()[
            "fleet"]["replica_count"],
        "backend": backend,
        "error": None,
    })


def bench_flywheel():
    """CPU-backend A/B for the online GRPO flywheel (docs/flywheel.md): the
    SAME model/env/recipe trained by (a) the interleaved single-process
    loop (generate -> learn in lockstep, the finetune_llm_reasoning shape)
    and (b) the disaggregated flywheel (rollout pod + learner pod
    exchanging commit-dir stores, staleness budget 2, importance-corrected
    learn). On one CPU core the pods timeshare, so this meters the
    FLYWHEEL LAYER's cost (store round-trips, behavior-logprob capture,
    rho correction) via rollout-tokens/s and learner steps/s — the decode-
    never-blocks win itself needs separate hosts. Run with
    BENCH_MODE=flywheel; knobs BENCH_FLY_STEPS / BENCH_FLY_DMODEL."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from agilerl_tpu.algorithms.grpo import GRPO
    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.flywheel import (
        LearnerPod, OnlineGRPOFlywheel, RolloutPod, TrajectoryStore,
        WeightStore,
    )
    from agilerl_tpu.observability import MetricsRegistry
    from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym

    backend = jax.default_backend()
    n_steps = int(os.environ.get("BENCH_FLY_STEPS", 6))
    d_model = int(os.environ.get("BENCH_FLY_DMODEL", 128))
    tok = CharTokenizer()
    cfg = M.GPTConfig(vocab_size=tok.vocab_size, n_layer=2, n_head=4,
                      d_model=d_model, max_seq_len=128, dtype=jnp.float32)

    def rows(n, seed):
        rng = np.random.default_rng(seed)
        return [{"question": f"{a}+{b}=", "answer": str(a + b)}
                for a, b in rng.integers(0, 9, (n, 2))]

    def make():
        env = ReasoningGym(
            rows(64, 0), rows(8, 1), tok,
            reward_fn=lambda c, a, p: 0.1 * len(c)
            + float(c.startswith(str(a))),
            data_batch_size=4)
        agent = GRPO(config=cfg, pad_token_id=tok.pad_token_id,
                     eos_token_id=tok.eos_token_id, group_size=4,
                     batch_size=16, max_output_tokens=16, seed=0)
        return env, agent

    # A: interleaved single-process loop (generate blocks learn and vice
    # versa — the finetune_llm_reasoning shape)
    env, agent = make()
    prompts = env.reset()

    def interleaved_step(prompts):
        agent.set_reference_policy(env.num_epochs)
        comp, cmask = agent.get_action(prompts)
        ids, am = env.assemble_learn_batch(comp, cmask)
        nxt, rewards = env.step(comp, cmask)
        agent.learn((ids, am, rewards))
        return nxt, int(np.asarray(cmask).sum())

    prompts, _ = interleaved_step(prompts)  # warm the compile caches
    t0 = time.perf_counter()
    inter_tokens = 0
    for _ in range(n_steps):
        prompts, toks = interleaved_step(prompts)
        inter_tokens += toks
    inter_dt = time.perf_counter() - t0
    inter_tps = inter_tokens / inter_dt
    inter_sps = n_steps / inter_dt

    # B: disaggregated flywheel (colocated emulation, staleness budget 2)
    env2, agent2 = make()
    reg = MetricsRegistry()
    with tempfile.TemporaryDirectory() as d:
        ws = WeightStore(os.path.join(d, "w"), metrics=reg)
        ts = TrajectoryStore(os.path.join(d, "t"), metrics=reg)
        learner = LearnerPod(agent2, ws, ts, max_staleness_epochs=2,
                             metrics=reg)
        rollout = RolloutPod(agent2, env2, ws, ts, metrics=reg)
        fly = OnlineGRPOFlywheel(rollout, learner, metrics=reg)
        fly.run(1)  # warm the compile caches
        tok0 = reg.counter("flywheel/rollout_tokens_total").value
        t0 = time.perf_counter()
        fly.run(1 + n_steps)
        fly_dt = time.perf_counter() - t0
        fly_tokens = reg.counter("flywheel/rollout_tokens_total").value - tok0
        fly_tps = fly_tokens / fly_dt
        fly_sps = n_steps / fly_dt
        stalls = reg.counter("flywheel/decode_stalls_total").value
        dropped = reg.counter(
            "flywheel/trajectories_dropped_stale_total").value
    ratio = fly_tps / max(inter_tps, 1e-9)
    log(f"bench_flywheel: interleaved {inter_tps:.0f} rollout-tokens/s "
        f"{inter_sps:.2f} learn-steps/s vs flywheel {fly_tps:.0f} tok/s "
        f"{fly_sps:.2f} steps/s ({ratio:.2f}x on one core; stalls "
        f"{stalls:.0f}, dropped {dropped:.0f})")
    emit({
        "metric": ("online-flywheel rollout tokens/sec, disaggregated "
                   f"(staleness 2) vs interleaved GRPO ({n_steps} learn "
                   "steps, group 4, colocated pods TIMESHARE one CPU core "
                   "— vs_baseline meters flywheel-layer cost, not the "
                   "decode-never-blocks win)"),
        "value": round(fly_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(ratio, 3),
        "interleaved_tokens_per_sec": round(inter_tps, 1),
        "interleaved_learn_steps_per_sec": round(inter_sps, 3),
        "flywheel_tokens_per_sec": round(fly_tps, 1),
        "flywheel_learn_steps_per_sec": round(fly_sps, 3),
        "decode_stalls": stalls,
        "trajectories_dropped_stale": dropped,
        "backend": backend,
        "error": None,
    })


_LAUNCH_ROLES_SRC = '''\
"""Factories the bench's launch-role child processes import by entry
point (written into the bench tmpdir, PYTHONPATH'd into every child)."""
import numpy as np
import jax.numpy as jnp

from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.utils.llm_utils import CharTokenizer, ReasoningGym

TOK = CharTokenizer()


def _rows(n, seed):
    rng = np.random.default_rng(seed)
    return [{"question": f"{a}+{b}=", "answer": str(a + b)}
            for a, b in rng.integers(0, 9, (n, 2))]


def make_env(seed=0):
    return ReasoningGym(
        _rows(64, 0), _rows(8, 1), TOK,
        reward_fn=lambda c, a, p: 0.1 * len(c) + float(c.startswith(str(a))),
        data_batch_size=4)


def make_agent(seed=0, d_model=64):
    cfg = M.GPTConfig(vocab_size=TOK.vocab_size, n_layer=2, n_head=4,
                      d_model=int(d_model), max_seq_len=128,
                      dtype=jnp.float32)
    return GRPO(config=cfg, pad_token_id=TOK.pad_token_id,
                eos_token_id=TOK.eos_token_id, group_size=4, batch_size=16,
                max_output_tokens=16, seed=seed)
'''


def bench_launch():
    """CPU A/B for the multi-process pod launcher (docs/launch.md): the
    SAME flywheel recipe run (a) in-process (OnlineGRPOFlywheel, pods
    timesharing one interpreter) and (b) as REAL OS processes (1 learner +
    2 rollout children supervised by PodLauncher over one root), staleness
    budget 2 both sides. The N-process run also injects one kill -9 into a
    rollout child mid-run and meters kill->respawn (pid-probe detection)
    and kill->next-published-batch MTTR. On one host the processes
    timeshare cores, so vs_baseline meters the PROCESS-BOUNDARY cost
    (store round-trips + per-child compile); the decode-never-blocks win
    needs separate hosts. Run with BENCH_MODE=launch; knobs
    BENCH_LAUNCH_EPOCHS / BENCH_LAUNCH_DMODEL."""
    import signal as _signal
    import tempfile

    import jax

    from agilerl_tpu.llm.flywheel import (
        LearnerPod, OnlineGRPOFlywheel, RolloutPod, TrajectoryStore,
        WeightStore,
    )
    from agilerl_tpu.observability import MetricsRegistry
    from agilerl_tpu.training.launch import CURSORS_DIR, PodLauncher

    backend = jax.default_backend()
    n_epochs = int(os.environ.get("BENCH_LAUNCH_EPOCHS", 8))
    d_model = int(os.environ.get("BENCH_LAUNCH_DMODEL", 64))

    with tempfile.TemporaryDirectory() as d:
        roles_py = os.path.join(d, "bench_launch_roles.py")
        with open(roles_py, "w") as f:
            f.write(_LAUNCH_ROLES_SRC)
        sys.path.insert(0, d)
        try:
            import bench_launch_roles as roles

            # A: in-process flywheel (one interpreter, pods timeshare)
            reg = MetricsRegistry()
            ws = WeightStore(os.path.join(d, "inproc", "w"), metrics=reg)
            ts = TrajectoryStore(os.path.join(d, "inproc", "t"), metrics=reg)
            agent = roles.make_agent(0, d_model)
            learner = LearnerPod(agent, ws, ts, max_staleness_epochs=2,
                                 metrics=reg)
            rollout = RolloutPod(agent, roles.make_env(), ws, ts, metrics=reg)
            fly = OnlineGRPOFlywheel(rollout, learner, metrics=reg)
            fly.run(1)  # warm the compile caches
            tok0 = reg.counter("flywheel/rollout_tokens_total").value
            t0 = time.perf_counter()
            fly.run(1 + n_epochs)
            inproc_dt = time.perf_counter() - t0
            inproc_tokens = (reg.counter("flywheel/rollout_tokens_total")
                             .value - tok0)
            inproc_tps = inproc_tokens / inproc_dt
            inproc_sps = n_epochs / inproc_dt

            # B: the same recipe as real OS processes + one injected kill
            root = os.path.join(d, "nproc")
            child_env = {
                "PYTHONPATH": os.pathsep.join(
                    p for p in (d, os.path.dirname(os.path.abspath(__file__)),
                                os.environ.get("PYTHONPATH")) if p),
                "JAX_PLATFORMS": "cpu",
            }
            launcher = PodLauncher(root, lease_timeout=5.0, grace_s=30.0)
            # actor 1 is capped at 3 seqs, so with kill at epoch>=2 the
            # learner can only reach n_steps if actor 0 keeps publishing
            # AFTER its kill -9 + respawn — otherwise the surviving actor
            # could finish the learner alone during the respawn recompile,
            # the learner would exit, the pending gate would fill, and the
            # respawned actor would idle forever (the recovery wait would
            # then burn its whole deadline and poison the throughput
            # window). Same arithmetic as the rollout-kill launch test.
            n_steps = max(12, 1 + n_epochs)
            launcher.add_role(
                "learner", "agilerl_tpu.training.launch:learner_role",
                kwargs={"make_agent": "bench_launch_roles:make_agent",
                        "agent_kwargs": {"seed": 0, "d_model": d_model},
                        "max_epochs": n_steps,
                        "max_staleness_epochs": 2},
                env=child_env, poll_interval=0.01)
            for i, seqs in enumerate((10_000, 3)):
                launcher.add_role(
                    f"rollout_{i}",
                    "agilerl_tpu.training.launch:rollout_role",
                    kwargs={"make_agent": "bench_launch_roles:make_agent",
                            "agent_kwargs": {"seed": i, "d_model": d_model},
                            "make_env": "bench_launch_roles:make_env",
                            "actor_id": i, "max_seqs": seqs,
                            "max_staleness_epochs": 2},
                    replica=i, env=child_env, poll_interval=0.01)
            t_spawn = time.perf_counter()
            launcher.start(join_timeout=300.0)
            nws = WeightStore(os.path.join(root, "weights"),
                              metrics=MetricsRegistry())

            def _epoch():
                return nws.latest_epoch() or 0

            def _wait(cond, timeout_s):
                deadline = time.monotonic() + timeout_s
                while time.monotonic() < deadline and not cond():
                    launcher.poll()
                    time.sleep(0.02)
                return cond()

            _wait(lambda: _epoch() >= 1, 600.0)
            t_first = time.perf_counter()

            # kill -9 one rollout mid-run; meter detection + recovery
            _wait(lambda: _epoch() >= 2, 600.0)
            cursor = os.path.join(root, CURSORS_DIR, "actor_000.json")

            def _cursor_seq():
                try:
                    with open(cursor) as f:
                        return int(json.load(f)["seq"])
                except (OSError, ValueError, KeyError):
                    return 0

            seq_at_kill = _cursor_seq()
            victim = launcher.supervisor.procs["rollout_0"].pid
            t_kill = time.monotonic()
            os.kill(victim, _signal.SIGKILL)
            restarted = []

            def _saw_restart():
                restarted.extend(
                    e for e in launcher.supervisor.poll()
                    if e["role"] == "rollout_0"
                    and e["action"] == "restarted")
                return bool(restarted)

            _wait(_saw_restart, 120.0)
            mttr_detect = time.monotonic() - t_kill
            _wait(lambda: _cursor_seq() > seq_at_kill, 600.0)
            mttr_recover = time.monotonic() - t_kill

            done = lambda: (launcher.statuses().get("learner", {})  # noqa: E731
                            .get("state") == "done")
            summary = launcher.run(timeout=900.0, until=done)
            t_done = time.perf_counter()
            agg = launcher.aggregate_telemetry()
            nproc_tokens = agg["counters"].get(
                "flywheel/rollout_tokens_total", 0.0)
            nproc_dt = t_done - t_first
            nproc_tps = nproc_tokens / max(nproc_dt, 1e-9)
            nproc_sps = _epoch() / max(nproc_dt, 1e-9)
            startup_s = t_first - t_spawn
            err = None
            if not done() or summary["orphans"]:
                err = f"launch bench fleet did not drain clean: {summary}"
        finally:
            sys.path.remove(d)

    ratio = nproc_tps / max(inproc_tps, 1e-9)
    log(f"bench_launch: in-process {inproc_tps:.0f} rollout-tokens/s "
        f"{inproc_sps:.2f} learn-steps/s vs N-process {nproc_tps:.0f} tok/s "
        f"{nproc_sps:.2f} steps/s ({ratio:.2f}x, 3 children timesharing; "
        f"startup {startup_s:.1f}s, kill->respawn {mttr_detect:.2f}s, "
        f"kill->recovered {mttr_recover:.1f}s)")
    emit({
        "metric": ("pod-launcher rollout tokens/sec, 1 learner + 2 rollout "
                   f"OS processes vs in-process flywheel ({n_steps} vs "
                   f"{n_epochs} learn steps, staleness 2, one kill -9 "
                   "injected into a rollout child mid-run — processes "
                   "TIMESHARE one host, so "
                   "vs_baseline meters the process-boundary cost; MTTR is "
                   "SIGKILL->pid-probe-respawn and SIGKILL->next published "
                   "batch from the respawned actor)"),
        "value": round(nproc_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(ratio, 3),
        "inproc_tokens_per_sec": round(inproc_tps, 1),
        "inproc_learn_steps_per_sec": round(inproc_sps, 3),
        "nproc_tokens_per_sec": round(nproc_tps, 1),
        "nproc_learn_steps_per_sec": round(nproc_sps, 3),
        "nproc_startup_s": round(startup_s, 2),
        "mttr_kill_to_respawn_s": round(mttr_detect, 3),
        "mttr_kill_to_recovered_s": round(mttr_recover, 2),
        "backend": backend,
        "error": err,
    })


def bench_anakin():
    """CPU-backend A/B for the scan-native generation engine
    (docs/performance.md): per-algorithm env-steps/sec of the SCAN-RESIDENT
    program (env step + ring write + fused sample/learn inside one
    lax.scan, ~0 dispatches/env-step) vs the best INTEROP off-policy hot
    loop (PR-2 chunked staging + fused learn_from_buffer, ≤2
    dispatches/env-step) on the same env / net / batch / learn cadence.
    Run with BENCH_MODE=anakin; knobs BENCH_ANAKIN_ENVS / _STEPS / _REPEATS
    / _ALGOS (comma list from {dqn, ddpg})."""
    import jax
    import numpy as np
    import optax

    from agilerl_tpu.envs import CartPole, JaxVecEnv, Pendulum
    from agilerl_tpu.modules.mlp import MLPConfig
    from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config

    backend = jax.default_backend()
    num_envs = int(os.environ.get("BENCH_ANAKIN_ENVS", 8))
    steps = int(os.environ.get("BENCH_ANAKIN_STEPS", 256))
    repeats = int(os.environ.get("BENCH_ANAKIN_REPEATS", 2))
    algos = [a.strip() for a in
             os.environ.get("BENCH_ANAKIN_ALGOS", "dqn,ddpg").split(",") if a]
    learn_every = 4
    batch_size = 64
    latent, hidden = 32, 64

    def net_cfg(env, outputs, **head_kw):
        kind, enc = default_encoder_config(
            env.observation_space, latent_dim=latent,
            encoder_config={"hidden_size": (hidden,)})
        return NetworkConfig(
            encoder_kind=kind, encoder=enc,
            head=MLPConfig(num_inputs=head_kw.pop("num_inputs", latent),
                           num_outputs=outputs, hidden_size=(hidden,),
                           **head_kw),
            latent_dim=latent)

    # ---- interop loops (the PR-2 best path: staging + fused learn) -------
    def _interop_sps(make_env_agent, act, action_dtype=None) -> float:
        """One benchmark protocol for every interop algorithm (warmup
        formula, flush cadence and learn gating included) so the
        per-algorithm A/B numbers stay comparable."""
        from agilerl_tpu.components.replay_buffer import ReplayBuffer

        env, agent = make_env_agent()
        memory = ReplayBuffer(max_size=10_000, seed=0, flush_every=8)

        def loop(n_steps):
            obs, _ = env.reset()
            obs = np.asarray(obs)
            pending = None
            for t in range(n_steps):
                action = act(agent, obs)
                next_obs, reward, term, trunc, _ = env.step(np.asarray(action))
                next_obs = np.asarray(next_obs)
                memory.stage({"obs": obs,
                              "action": np.asarray(action, action_dtype),
                              "reward": np.asarray(reward, np.float32),
                              "next_obs": next_obs,
                              "done": np.asarray(term, np.float32)},
                             batched=True)
                obs = next_obs
                if t % learn_every == 0:
                    memory.flush()
                    if len(memory) >= batch_size:
                        pending = agent.learn_from_buffer(memory)
            if pending is not None:
                jax.block_until_ready(pending)

        loop(max(steps // 4, 2 * learn_every * batch_size // num_envs))
        t0 = time.perf_counter()
        loop(steps)
        return steps * num_envs / (time.perf_counter() - t0)

    def interop_dqn_sps() -> float:
        from agilerl_tpu.algorithms.dqn import DQN

        def make():
            env = JaxVecEnv(CartPole(), num_envs=num_envs, seed=0)
            agent = DQN(env.single_observation_space, env.single_action_space,
                        batch_size=batch_size, lr=1e-3,
                        net_config={"latent_dim": latent,
                                    "encoder_config": {"hidden_size": (hidden,)}})
            return env, agent

        return _interop_sps(make, lambda a, obs: a.get_action(obs, epsilon=0.1))

    def interop_ddpg_sps() -> float:
        from agilerl_tpu.algorithms.ddpg import DDPG

        def make():
            env = JaxVecEnv(Pendulum(), num_envs=num_envs, seed=0)
            agent = DDPG(env.single_observation_space, env.single_action_space,
                         batch_size=batch_size, O_U_noise=False,
                         net_config={"latent_dim": latent,
                                     "encoder_config": {"hidden_size": (hidden,)}})
            return env, agent

        return _interop_sps(make, lambda a, obs: a.get_action(obs),
                            action_dtype=np.float32)

    # ---- scan-resident programs (pop=1 vmap: same workload, ~0 dispatches)
    def scan_dqn_sps() -> float:
        from agilerl_tpu.parallel.off_policy import EvoDQN

        env = CartPole()
        evo = EvoDQN(env, net_cfg(env, 2), optax.adam(1e-3),
                     num_envs=num_envs, steps_per_iter=steps,
                     buffer_size=10_000, batch_size=batch_size,
                     learn_every=learn_every)
        pop = evo.init_population(jax.random.PRNGKey(0), 1)
        gen = evo.make_vmap_generation()
        pop, f = gen(pop, jax.random.PRNGKey(1))  # compile+warm
        jax.block_until_ready(f)
        gens = 4
        t0 = time.perf_counter()
        for i in range(gens):
            pop, f = gen(pop, jax.random.PRNGKey(2 + i))
        jax.block_until_ready(f)
        return gens * steps * num_envs / (time.perf_counter() - t0)

    def scan_ddpg_sps() -> float:
        from agilerl_tpu.parallel.off_policy import EvoDDPG

        env = Pendulum()
        actor = net_cfg(env, 1, output_activation="Tanh")
        critic = net_cfg(env, 1, num_inputs=latent + 1)
        evo = EvoDDPG(env, actor, critic,
                      num_envs=num_envs, steps_per_iter=steps,
                      buffer_size=10_000, batch_size=batch_size,
                      learn_every=learn_every)
        pop = evo.init_population(jax.random.PRNGKey(0), 1)
        gen = evo.make_vmap_generation()
        pop, f = gen(pop, jax.random.PRNGKey(1))
        jax.block_until_ready(f)
        gens = 4
        t0 = time.perf_counter()
        for i in range(gens):
            pop, f = gen(pop, jax.random.PRNGKey(2 + i))
        jax.block_until_ready(f)
        return gens * steps * num_envs / (time.perf_counter() - t0)

    runners = {
        "dqn": (interop_dqn_sps, scan_dqn_sps),
        "ddpg": (interop_ddpg_sps, scan_ddpg_sps),
    }
    per_algo = {}
    for algo in algos:
        interop_fn, scan_fn = runners[algo]
        # best-of-N per path: single-shot A/Bs on a shared host are noise
        interop = max(interop_fn() for _ in range(repeats))
        scan = max(scan_fn() for _ in range(repeats))
        per_algo[algo] = {
            "interop_env_steps_per_sec": round(interop),
            "scan_env_steps_per_sec": round(scan),
            "speedup": round(scan / max(interop, 1e-9), 2),
        }
        log(f"bench_anakin: {algo} interop {interop:.0f} vs scan {scan:.0f} "
            f"env-steps/s ({per_algo[algo]['speedup']}x)")

    head = per_algo.get("dqn") or per_algo[algos[0]]
    emit({
        "metric": ("scan-resident generation engine env-steps/sec "
                   f"(DQN CartPole, {num_envs} envs, learn_every="
                   f"{learn_every}; vs_baseline = speedup over the interop "
                   "off-policy hot loop, same env/net/batch/cadence)"),
        "value": head["scan_env_steps_per_sec"],
        "unit": "env-steps/sec",
        "vs_baseline": head["speedup"],
        "per_algorithm": per_algo,
        "backend": backend,
        "error": None,
    })


def bench_sharding():
    """Sharding-plan engine bench (docs/sharding.md): times (a) rule
    resolution — regex rules -> PartitionSpec trees for the llama3-8b
    params/lora/optimizer/batch pytrees — and (b) the 7B fsdp16xtp4 plan
    loaded from configs/sharding/*.yaml driving the production GRPO update
    through compile_step_with_plan (AOT lower on 64 virtual CPU devices;
    BENCH_SHARDING_COMPILE=1 adds the full GSPMD compile). Run with
    BENCH_MODE=sharding."""
    import subprocess
    import sys

    import jax

    from agilerl_tpu.algorithms.core.optimizer import OptimizerWrapper
    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.presets import preset
    from agilerl_tpu.parallel.plan import make_grpo_plan

    backend = jax.default_backend()
    repo = os.path.dirname(os.path.abspath(__file__))

    # ---- (a) rule resolution timing (the pure-host cost a new mesh pays) -
    cfg = preset("llama3-8b", max_seq_len=2048, use_flash_attention=False)
    plan = make_grpo_plan(fsdp=16, tp=4)
    base_shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                 jax.random.PRNGKey(0))
    lora_shapes = jax.eval_shape(lambda k: M.init_lora(k, cfg, 16),
                                 jax.random.PRNGKey(0))
    opt_shapes = jax.eval_shape(
        OptimizerWrapper(optimizer="adamw", lr=5e-6, max_grad_norm=0.1).tx.init,
        lora_shapes)
    n_leaves = sum(
        len(jax.tree_util.tree_leaves(t))
        for t in (base_shapes, lora_shapes, opt_shapes))
    reps = int(os.environ.get("BENCH_SHARDING_REPEATS", 5))
    t0 = time.perf_counter()
    for _ in range(reps):
        plan.resolve("params", base_shapes)
        plan.resolve("lora", lora_shapes)
        plan.resolve("optimizer", opt_shapes)
    resolve_ms = (time.perf_counter() - t0) / reps * 1e3
    log(f"bench_sharding: resolved {n_leaves} leaves in {resolve_ms:.1f}ms")

    # ---- (b) the 7B plan end to end (subprocess: it must own XLA_FLAGS
    # before the first backend touch to fake the 64-device topology). This
    # process holds the accelerator, if there is one: the child is told to
    # stay on the CPU backend, which is all it needs ----------------------
    compile_ = os.environ.get("BENCH_SHARDING_COMPILE") == "1"
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    args = [sys.executable,
            os.path.join(repo, "benchmarking", "grpo_7b_plan.py")]
    if compile_:
        args.append("--compile")
    proc = subprocess.run(
        args, env=env, cwd=repo, text=True, timeout=float(
            os.environ.get("BENCH_SHARDING_7B_TIMEOUT", 600)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    if proc.returncode != 0:
        raise RuntimeError(
            f"grpo_7b_plan.py exited {proc.returncode}: {proc.stderr[-1500:]}")
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    plan7b = {
        "sharding_plan": rep.get("sharding_plan"),
        "plan_source": rep.get("sharding_plan_source"),
        "mesh": rep.get("mesh"),
        "train_lower_seconds": rep.get("train_lower_seconds"),
        "train_compile_seconds": rep.get("train_compile_seconds"),
        "train_step_pflops": rep.get("train_step_pflops"),
        "sharding_annotations": rep.get("train_sharding_annotations"),
    }
    log(f"bench_sharding: 7B plan {rep.get('sharding_plan')} lowered in "
        f"{rep.get('train_lower_seconds')}s "
        f"({rep.get('train_sharding_annotations')} annotations)")

    emit({
        "metric": ("sharding-plan engine: rule-resolution ms for the "
                   f"llama3-8b param/lora/optimizer trees ({n_leaves} "
                   "leaves) + 7B plan lowering through "
                   "compile_step_with_plan"),
        "value": round(resolve_ms, 1),
        "unit": "ms/resolution",
        "vs_baseline": None,
        "plan_7b": plan7b,
        "backend": backend,
        "error": None,
    })


def bench_elastic():
    """Elastic preemption-native PBT bench (docs/resilience.md): on the CPU
    pod emulation (2 emulated hosts x 2 virtual devices, pop=4 EvoDQN),
    measures (a) the steady-state overhead of the heartbeat/membership layer
    — elastic controller with snapshots disabled vs the raw pod generation
    loop on the same mesh — and (b) MTTR: a scripted FaultInjector host kill
    at a generation boundary to the first COMPLETED post-recovery generation
    (lease expiry + snapshot-restore of the lost members + mesh re-form +
    recompile for the survivor layout included). Run with BENCH_MODE=elastic;
    knobs BENCH_ELASTIC_GENS / BENCH_ELASTIC_ENVS / BENCH_ELASTIC_STEPS."""
    import shutil
    import tempfile

    import jax
    import numpy as np
    import optax

    from agilerl_tpu.envs import CartPole
    from agilerl_tpu.modules.mlp import MLPConfig
    from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
    from agilerl_tpu.observability.registry import MetricsRegistry
    from agilerl_tpu.parallel import (
        ElasticPBTController,
        EvoDQN,
        make_emulated_hosts,
    )
    from agilerl_tpu.resilience import FaultInjector

    backend = jax.default_backend()
    gens = int(os.environ.get("BENCH_ELASTIC_GENS", 6))
    num_envs = int(os.environ.get("BENCH_ELASTIC_ENVS", 4))
    steps = int(os.environ.get("BENCH_ELASTIC_STEPS", 32))
    heartbeat = float(os.environ.get("BENCH_ELASTIC_HEARTBEAT", 0.25))
    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise RuntimeError(
            f"need 4 devices, have {len(devices)}: on the CPU backend set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4")

    def engine():
        env = CartPole()
        kind, enc = default_encoder_config(
            env.observation_space, latent_dim=32,
            encoder_config={"hidden_size": (32,)})
        cfg = NetworkConfig(
            encoder_kind=kind, encoder=enc,
            head=MLPConfig(num_inputs=32, num_outputs=2, hidden_size=(32,)),
            latent_dim=32)
        return EvoDQN(env, cfg, optax.adam(1e-3), num_envs=num_envs,
                      steps_per_iter=steps, buffer_size=32 * num_envs,
                      batch_size=16)

    work = tempfile.mkdtemp(prefix="bench_elastic_")
    try:
        # ---- (a) steady-state heartbeat overhead: controller (snapshots
        # off, heartbeat+poll on) vs the raw pod generation loop ----------
        reg = MetricsRegistry()
        ctl = ElasticPBTController(
            engine(), 4, os.path.join(work, "steady"), seed=0,
            hosts=make_emulated_hosts(2, devices),
            heartbeat_timeout=heartbeat, snapshot_every=0, registry=reg)
        ctl.run(1)  # compile + warmup
        t0 = time.perf_counter()
        ctl.run(gens)
        ctl_dt = (time.perf_counter() - t0) / gens

        evo = engine()
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devices), ("pop",))
        gen = evo.make_pod_generation(mesh)
        pop = evo.init_population(jax.random.PRNGKey(1), 4)
        # TWO warmup calls: the first compiles for host-resident inputs, the
        # second for the sharded donated outputs it hands itself — only the
        # second executable is the steady-state one (the controller pre-
        # places its population, so it never pays the first)
        pop, f = gen(pop, jax.random.PRNGKey(2))
        jax.block_until_ready(f)
        pop, f = gen(pop, jax.random.PRNGKey(2))
        jax.block_until_ready(f)
        t0 = time.perf_counter()
        for i in range(gens):
            pop, f = gen(pop, jax.random.PRNGKey(3 + i))
        jax.block_until_ready(f)
        raw_dt = (time.perf_counter() - t0) / gens
        overhead = (ctl_dt - raw_dt) / raw_dt if raw_dt > 0 else None
        log(f"bench_elastic: steady-state {ctl_dt*1e3:.1f}ms/gen with "
            f"heartbeat vs {raw_dt*1e3:.1f}ms/gen raw "
            f"({overhead:+.1%} overhead)")

        # ---- (b) MTTR: scripted host kill at a generation boundary ------
        reg2 = MetricsRegistry()
        kill_gen = 2
        ctl2 = ElasticPBTController(
            engine(), 4, os.path.join(work, "mttr"), seed=0,
            hosts=make_emulated_hosts(2, devices),
            heartbeat_timeout=heartbeat, snapshot_every=1,
            fault_injector=FaultInjector(kill_host_at={kill_gen: 1}),
            registry=reg2)
        ctl2.run(kill_gen + 2)
        mttr = reg2.gauge("elastic/mttr_s").value
        recovered = reg2.counter("resilience/recoveries_total").value
        restored = reg2.counter("elastic/members_restored_total").value
        log(f"bench_elastic: MTTR {mttr:.2f}s (kill at gen boundary "
            f"{kill_gen}, {int(restored)} members restored, layout "
            f"{ctl2.layout()})")

        # ---- (c) warm-store MTTR A/B (ISSUE 15): identical scripted kill,
        # persistent executable store cold (empty — publishes) vs warm
        # (loads the re-formed layout's pod generation instead of
        # recompiling it). Same seed => bit-identical fitness streams; the
        # delta is pure compile-vs-load.
        cache_dir = os.path.join(work, "exe_store")

        def mttr_run(workdir):
            regn = MetricsRegistry()
            ctl = ElasticPBTController(
                engine(), 4, os.path.join(work, workdir), seed=0,
                hosts=make_emulated_hosts(2, devices),
                heartbeat_timeout=heartbeat, snapshot_every=1,
                fault_injector=FaultInjector(kill_host_at={kill_gen: 1}),
                registry=regn, compile_cache=cache_dir)
            ctl.run(kill_gen + 2)
            return {
                "mttr_s": round(float(regn.gauge("elastic/mttr_s").value), 3),
                "cache_hits": int(regn.counter(
                    "compile_cache/hits_total").value),
                "cache_misses": int(regn.counter(
                    "compile_cache/misses_total").value),
            }

        jax.clear_caches()  # equal in-process footing for both store legs
        cold_store = mttr_run("mttr_cold_store")
        jax.clear_caches()
        warm_store = mttr_run("mttr_warm_store")
        warm_speedup = (cold_store["mttr_s"] / warm_store["mttr_s"]
                        if warm_store["mttr_s"] > 0 else None)
        log(f"bench_elastic: store A/B MTTR {cold_store['mttr_s']:.2f}s cold "
            f"({cold_store['cache_misses']} compiles published) -> "
            f"{warm_store['mttr_s']:.2f}s warm "
            f"({warm_store['cache_hits']} loads, "
            f"{warm_store['cache_misses']} misses)")

        emit({
            "metric": ("elastic PBT on the CPU pod emulation: MTTR "
                       "(scripted host kill -> first post-recovery "
                       "generation) + heartbeat steady-state overhead"),
            "value": round(float(mttr), 3),
            "unit": "s (MTTR)",
            "vs_baseline": None,
            "backend": backend,
            "pop": 4, "hosts": 2, "devices": len(devices),
            "generations": gens,
            "heartbeat_timeout_s": heartbeat,
            "steady_gen_s": round(ctl_dt, 4),
            "raw_gen_s": round(raw_dt, 4),
            "heartbeat_overhead_fraction": (
                None if overhead is None else round(overhead, 4)),
            "recoveries": int(recovered),
            "members_restored": int(restored),
            "post_recovery_layout": ctl2.layout(),
            "compile_cache": {
                "cold_store": cold_store,
                "warm_store": warm_store,
                "mttr_warm_speedup": (round(warm_speedup, 2)
                                      if warm_speedup else None),
            },
            "error": None if np.isfinite(mttr) else "MTTR gauge is not finite",
            "provenance": ("fresh CPU pod-emulation measurement at HEAD; "
                           "MTTR includes lease expiry (heartbeat_timeout), "
                           "best-snapshot member restore, plan-registry mesh "
                           "re-form and the survivor-layout recompile; the "
                           "compile_cache A/B reruns the same scripted kill "
                           "with the persistent executable store empty vs "
                           "warmed — the warm leg LOADS the re-formed "
                           "layout's pod generation (jax.clear_caches "
                           "between legs; same seed, bit-identical fitness "
                           "stream)"),
        })
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_compile_cache():
    """Persistent executable store: serving replica spin-up cold vs warm
    (ISSUE 15). Measures construction + warm_start + first completed
    request for a ContinuousGenerator wired to the store, best-of-N, with
    an EMPTY store (every program compiles and is published) vs the warmed
    store (every program loads). jax.clear_caches() before every rep so
    the in-process jit cache cannot fake a warm start. Run with
    BENCH_MODE=compile_cache; knobs BENCH_CC_REPS / BENCH_CC_DMODEL."""
    import shutil
    import tempfile

    import jax

    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.serving import ContinuousGenerator
    from agilerl_tpu.observability.registry import MetricsRegistry

    backend = jax.default_backend()
    reps = int(os.environ.get("BENCH_CC_REPS", 3))
    d_model = int(os.environ.get("BENCH_CC_DMODEL", 64))
    cfg = M.GPTConfig(vocab_size=256, n_layer=2, n_head=4, n_kv_head=2,
                      d_model=d_model, d_ff=2 * d_model, max_seq_len=128)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompt = list(range(1, 9))
    work = tempfile.mkdtemp(prefix="bench_cc_")

    def spin_up(store_dir):
        reg = MetricsRegistry()
        t0 = time.perf_counter()
        gen = ContinuousGenerator(
            cfg, max_new_tokens=16, decode_chunk=8, slots=4,
            prompt_buckets=(16,), block_size=8, metrics=reg,
            compile_cache=store_dir)
        gen.warm_start(params=params, greedy=True)
        spin_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        gen.generate([prompt], jax.random.PRNGKey(1), params, greedy=True)
        first_req_s = time.perf_counter() - t0
        return {
            "spin_s": round(spin_s, 4),
            "first_req_s": round(first_req_s, 4),
            "total_s": round(spin_s + first_req_s, 4),
            "cache_hits": int(reg.counter("compile_cache/hits_total").value),
            "cache_misses": int(reg.counter(
                "compile_cache/misses_total").value),
        }

    try:
        cold = []
        for i in range(reps):
            jax.clear_caches()
            cold.append(spin_up(os.path.join(work, f"cold_{i}")))
        shared = os.path.join(work, "shared")
        jax.clear_caches()
        seed_rep = spin_up(shared)  # publishes into the shared store
        warm = []
        for i in range(reps):
            jax.clear_caches()
            warm.append(spin_up(shared))
        cold_best = min(r["total_s"] for r in cold)
        warm_best = min(r["total_s"] for r in warm)
        speedup = cold_best / warm_best if warm_best > 0 else None
        log(f"bench_compile_cache: spin-up+first-request best-of-{reps} "
            f"{cold_best:.2f}s cold -> {warm_best:.2f}s warm "
            f"({speedup:.2f}x)")
        emit({
            "metric": ("serving replica spin-up + first request: executable "
                       "store cold (compile+publish) vs warm (load)"),
            "value": round(warm_best, 4),
            "unit": "s (spin-up, warm store)",
            "vs_baseline": None if speedup is None else round(speedup, 2),
            "backend": backend,
            "reps": reps,
            "cold_best_s": round(cold_best, 4),
            "warm_best_s": round(warm_best, 4),
            "cold": cold,
            "warm": warm,
            "store_seed_rep": seed_rep,
            "config": {"d_model": d_model, "n_layer": cfg.n_layer,
                       "slots": 4, "max_new_tokens": 16},
            "error": None,
            "provenance": ("fresh CPU A/B at HEAD; cold reps use an empty "
                           "per-rep store (programs compile and publish), "
                           "warm reps a shared pre-warmed store (programs "
                           "deserialize); jax.clear_caches() before every "
                           "rep so only the on-disk store carries state"),
        })
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_traffic():
    """Traffic harness + SLO engine (docs/serving.md, docs/observability.md):
    drive a 2-replica ``ServingFleet`` through the four standing synthetic-
    load scenarios (steady heavy-tail, diurnal, flash-crowd, prefix-skew;
    ``agilerl_tpu/benchmarking/traffic.py``) with the SLO evaluator
    (``configs/slo/traffic_cpu.yaml``) ticking every scheduler step, then a
    FAULT-INJECTED flash crowd — one replica killed mid-burst with the
    autoscaler live — to show the burn-rate alert fire (forced span), the
    graded scale-up, and the alert clear after recovery. Emits ONE scored
    JSON line: per-scenario SLO grades + degraded-run attribution +
    generation provenance (every trace is regenerable from spec+seed, or
    replayable from BENCH_TRAFFIC_TRACE). Run with BENCH_MODE=traffic;
    knobs BENCH_TRAFFIC_DURATION_S / _RPS / _STEPS_PER_S / _SEED / _SLO."""
    import jax
    import jax.numpy as jnp

    from agilerl_tpu.benchmarking.traffic import (
        ScenarioSpec, TrafficDriver, generate_trace, load_trace,
        scenario_suite)
    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.autoscale import AutoscalePolicy
    from agilerl_tpu.llm.fleet import (SCALE_UP_BUCKETS, ServingFleet)
    from agilerl_tpu.llm.serving import (AdmissionPolicy, DECODE_BUCKETS,
                                         TTFT_BUCKETS)
    from agilerl_tpu.observability import (MemorySink, MetricsRegistry,
                                           SLOEvaluator, aligned_buckets,
                                           attribute_scale_ups,
                                           load_slo_spec)
    from agilerl_tpu.observability.trace import Tracer
    from agilerl_tpu.resilience.faults import FaultInjector

    backend = jax.default_backend()
    duration = float(os.environ.get("BENCH_TRAFFIC_DURATION_S", 10.0))
    rate = float(os.environ.get("BENCH_TRAFFIC_RPS", 5.0))
    steps_per_s = float(os.environ.get("BENCH_TRAFFIC_STEPS_PER_S", 8.0))
    seed = int(os.environ.get("BENCH_TRAFFIC_SEED", 0))
    spec_path = os.environ.get("BENCH_TRAFFIC_SLO",
                               os.path.join(os.path.dirname(
                                   os.path.abspath(__file__)),
                                   "configs", "slo", "traffic_cpu.yaml"))
    slo_spec = load_slo_spec(spec_path)
    # align fleet-wide bucket bounds with the spec's thresholds so every
    # burn-rate fraction is an exact bucket-count delta (satellite contract:
    # identical bounds on every member registry or the telemetry
    # aggregator's exact merge refuses)
    base_bounds = {"serving/ttft_s": TTFT_BUCKETS,
                   "serving/decode_time_per_token_s": DECODE_BUCKETS,
                   "fleet/scale_up_latency_s": SCALE_UP_BUCKETS}
    overrides = {name: aligned_buckets(base_bounds.get(name, ()), edges)
                 for name, edges in slo_spec.bucket_overrides().items()}
    cfg = M.GPTConfig(vocab_size=128, n_layer=2, n_head=4, n_kv_head=2,
                      d_model=64, max_seq_len=256, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(max_new_tokens=16, pad_id=0, eos_id=None, prompt_buckets=(32,),
              slots=4, block_size=8, decode_chunk=4)

    class VClock:
        """Virtual-time clock fed by the driver — burn windows and
        autoscale cooldowns run on scenario time, not host speed."""
        t = 0.0

        def __call__(self):
            return self.t

    def run_one(name, trace, *, fault=None, autoscale=None,
                max_queue=256, member_queue=None):
        sink = MemorySink()
        kw_run = dict(kw)
        if member_queue is not None:
            kw_run["max_queue"] = member_queue
        fleet = ServingFleet(
            cfg, 2, metrics=MetricsRegistry(sink=sink),
            admission=AdmissionPolicy(max_queue=max_queue),
            bucket_overrides=overrides,
            tracer=Tracer(sink=MemorySink(), sample_rate=0.0), **kw_run)
        # warm the compile cache outside the graded run
        t = fleet.submit(trace[0].tokens, max_new=2, no_shed=True)
        fleet.run_until_drained(params, greedy=True)
        fleet.result(t)
        vclock = VClock()
        tracer = Tracer(sink=MemorySink(), sample_rate=0.0,
                        metrics=fleet.metrics, clock=vclock)
        policy = None
        if autoscale:
            policy = AutoscalePolicy(
                min_replicas=2, max_replicas=4, backlog_high=6.0,
                shed_rate_high=1.0, up_cooldown_s=3.0, down_cooldown_s=1e9,
                clock=vclock, metrics=fleet.metrics)
        # fleet-wide source: filtered merged dump (fleet registry + every
        # member registry + departed bank), so the per-step read only
        # touches the instruments the spec grades
        cnames, hnames = slo_spec.metric_names()

        def source():
            return fleet.merged_dump(counters=cnames, histograms=hnames)

        ev = SLOEvaluator(slo_spec, source, clock=vclock,
                          metrics=fleet.metrics, tracer=tracer)
        ev_s = [0.0]

        def on_step(step, vnow):
            vclock.t = vnow
            t0 = time.perf_counter()
            ev.evaluate(now=vnow)
            ev_s[0] += time.perf_counter() - t0

        drv = TrafficDriver(fleet, mode="open", steps_per_s=steps_per_s,
                            seed=seed, autoscale=policy,
                            fault_injector=fault, on_step=on_step)
        res = drv.run(trace, params, scenario=name)
        ev.evaluate(now=vclock.t + 1.0 / steps_per_s)  # final tick
        report = ev.grade(scenario=name, extra={
            "run": res.to_dict(),
            "replicas_end": len(fleet.replica_ids),
            # per-step continuous evaluation cost attributed against the
            # run's wall clock — the ~1% overhead budget, measured
            "slo_eval_overhead_frac": round(ev_s[0] / max(res.wall_s, 1e-9),
                                            5),
            "forced_spans": sum(
                1 for s in tracer.sink.events
                if str(s.get("name", "")).startswith("slo.")),
            "attribution": attribute_scale_ups(sink.events),
        })
        return res, report

    trace_path = os.environ.get("BENCH_TRAFFIC_TRACE")
    reports = {}
    if trace_path:
        trace = load_trace(trace_path)
        res, rep = run_one("replayed_trace", trace)
        reports["replayed_trace"] = rep
    else:
        for spec in scenario_suite(vocab=cfg.vocab_size, duration_s=duration,
                                   base_rate_rps=rate, max_prompt=28,
                                   max_new=kw["max_new_tokens"]):
            trace = generate_trace(spec, seed)
            res, rep = run_one(spec.name, trace)
            reports[spec.name] = rep
            log(f"bench_traffic: {spec.name} score {rep['score']} "
                f"({res.completed}/{res.n_requests} served, {res.shed} shed, "
                f"{res.wall_s:.1f}s wall)")

    # the degraded run: flash crowd + replica kill mid-burst + autoscaler —
    # small admission queues (router AND member) make the burst actually
    # shed, which is the burn-rate breach the alert must catch
    deg_spec = ScenarioSpec(
        name="degraded_burst", kind="flash_crowd", duration_s=duration,
        base_rate_rps=rate, burst_start_s=0.3 * duration,
        burst_duration_s=0.25 * duration, burst_x=8.0,
        vocab=cfg.vocab_size, max_prompt=28, max_new=kw["max_new_tokens"])
    deg_trace = generate_trace(deg_spec, seed + 2)
    kill_at = int(0.35 * duration) + 1
    res_deg, rep_deg = run_one(
        "degraded_burst", deg_trace,
        fault=FaultInjector(kill_host_at={kill_at: 1}),
        autoscale=True, max_queue=8, member_queue=4)
    fires = [a for a in rep_deg["alerts"] if a["phase"] == "fire"]
    clears = [a for a in rep_deg["alerts"] if a["phase"] == "clear"]
    scale_ups = [e for e in res_deg.scale_events if e["action"] == "up"]
    log(f"bench_traffic: degraded_burst score {rep_deg['score']}, "
        f"{len(fires)} alert(s) fired / {len(clears)} cleared, "
        f"{len(scale_ups)} scale-up(s), kill at t={kill_at}s, "
        f"shed {res_deg.shed}")

    scores = [r["score"] for r in reports.values()]
    mean_score = sum(scores) / max(len(scores), 1)
    overheads = [r["slo_eval_overhead_frac"]
                 for r in list(reports.values()) + [rep_deg]]
    overhead = sum(overheads) / len(overheads)
    emit({
        "metric": ("traffic-harness SLO score, mean over synthetic-load "
                   "scenarios (steady heavy-tail / diurnal / flash-crowd / "
                   "prefix-skew) on a 2-replica ServingFleet; vs_baseline "
                   "is the fault-injected flash-crowd (replica kill "
                   "mid-burst, autoscaler live) relative to the healthy "
                   "mean"),
        "value": round(mean_score, 1),
        "unit": "slo-score",
        "vs_baseline": round(rep_deg["score"] / max(mean_score, 1e-9), 3),
        "scenarios": reports,
        "degraded": rep_deg,
        "degraded_alert_fired": bool(fires),
        "degraded_alert_cleared": bool(clears),
        "degraded_scale_ups": scale_ups,
        "slo_eval_overhead_frac": round(overhead, 4),
        "provenance": {
            "seed": seed, "slo_spec": slo_spec.name,
            "slo_spec_path": spec_path, "steps_per_s": steps_per_s,
            "duration_s": duration, "base_rate_rps": rate,
            "replayed_trace": trace_path,
            "bucket_overrides": {k: list(v) for k, v in overrides.items()},
        },
        "backend": backend,
        "error": None,
    })


MODES = {
    "evoppo": bench_evoppo,
    "grpo": bench_grpo,
    "pipeline": bench_pipeline,
    "serving": bench_serving,
    "trace": bench_trace,
    "fleet": bench_fleet,
    "flywheel": bench_flywheel,
    "launch": bench_launch,
    "anakin": bench_anakin,
    "sharding": bench_sharding,
    "elastic": bench_elastic,
    "compile_cache": bench_compile_cache,
    "traffic": bench_traffic,
}


def main():
    mode = os.environ.get("BENCH_MODE", "evoppo")
    if mode not in MODES:
        raise SystemExit(
            f"bench: unknown BENCH_MODE {mode!r}; one of {sorted(MODES)}")
    if mode == "elastic":
        # the pod emulation runs on virtual CPU devices; the flag has to be
        # in place before JAX starts its CPU backend, and it touches no other
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    from agilerl_tpu.parallel.compile_cache import enable_jax_cache

    enable_jax_cache()
    MODES[mode]()


if __name__ == "__main__":
    main()
