"""Dense vs bucketed ragged-decode benchmark (VERDICT r3 next #3 'measured
tokens/sec gain vs dense').

Serves a stream of ragged GRPO-style prompt batches twice:
- dense: llm/generate.generate — one compiled program PER DISTINCT (B, P),
  full max_new_tokens decode for every batch;
- bucketed: llm/serving.BucketedGenerator — bounded compile set + host
  early-exit between decode chunks.

Prints one JSON line with wall-clock (including compiles — that's the point),
steady-state decode throughput, compile counts, and decode steps executed.

Run (CPU):   JAX_PLATFORMS=cpu python benchmarking/bucketed_decode_bench.py
Run (TPU):   python benchmarking/bucketed_decode_bench.py   # via playbook
"""

from __future__ import annotations

import json
import os
import sys
import time

# run as a script, sys.path[0] is benchmarking/, not the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agilerl_tpu.llm import model as M
    from agilerl_tpu.llm.generate import generate, left_pad
    from agilerl_tpu.llm.serving import BucketedGenerator

    on_cpu = jax.default_backend() == "cpu"
    # BENCH_DECODE_LAYERS: depth knob (with the stacked KV cache the decode
    # path scans too, so compile cost is ~depth-independent)
    cfg = M.GPTConfig(
        vocab_size=32_000,
        n_layer=int(os.environ.get("BENCH_DECODE_LAYERS",
                                   2 if on_cpu else 12)),
        n_head=12, n_kv_head=4, d_model=768,
        max_seq_len=2048, dtype=jnp.float32 if on_cpu else jnp.bfloat16,
    )
    max_new = 32 if on_cpu else 128
    eos = 5  # a token random sampling emits often enough to finish early
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    # a GRPO-like stream: varying batch sizes and prompt lengths
    batches = []
    for i in range(6):
        n = int(rng.integers(3, 9))
        lens = rng.integers(8, 120, size=n)
        batches.append([rng.integers(6, 31_000, size=l).astype(np.int32)
                        for l in lens])

    # --- dense path: per-(B, P) programs, full-length decode --------------
    t0 = time.perf_counter()
    dense_tokens = 0
    dense_shapes = set()
    for i, seqs in enumerate(batches):
        toks, mask = left_pad(seqs, 0)
        dense_shapes.add(toks.shape)
        comp, cmask = generate(
            cfg, params, jnp.asarray(toks), jnp.asarray(mask),
            jax.random.PRNGKey(i), max_new_tokens=max_new, temperature=1.0,
            eos_id=eos, pad_id=0,
        )
        jax.block_until_ready(comp)
        dense_tokens += int(np.asarray(cmask).sum())
    dense_s = time.perf_counter() - t0

    # --- bucketed path ----------------------------------------------------
    gen = BucketedGenerator(
        cfg, max_new_tokens=max_new, pad_id=0, eos_id=eos,
        prompt_buckets=(128,), row_buckets=(8,), decode_chunk=8,
        temperature=1.0,
    )
    t0 = time.perf_counter()
    bucket_tokens = 0
    decode_steps = 0
    for i, seqs in enumerate(batches):
        comp, cmask, info = gen.generate(seqs, jax.random.PRNGKey(i), params)
        bucket_tokens += int(cmask.sum())
        decode_steps += info["decode_steps"]
    bucket_s = time.perf_counter() - t0

    out = {
        "metric": "bucketed vs dense ragged decode wall-clock speedup",
        "value": round(dense_s / bucket_s, 2),
        "unit": "x",
        "backend": jax.default_backend(),
        "n_layer": cfg.n_layer,  # depth is tunable (BENCH_DECODE_LAYERS) —
        # a reduced-depth capture must be distinguishable from the headline
        "dense_seconds": round(dense_s, 2),
        "bucketed_seconds": round(bucket_s, 2),
        "dense_programs": len(dense_shapes),  # jit: one program per (B, P)
        "bucketed_programs": gen.compiled_programs,
        "decode_steps_executed": decode_steps,
        "decode_steps_dense": max_new * len(batches),
        "emitted_tokens": {"dense": dense_tokens, "bucketed": bucket_tokens},
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
