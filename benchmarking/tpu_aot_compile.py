"""Compile-only TPU AOT validation of the Pallas kernels and the fused GRPO
step (VERDICT r4 next #1b): prove Mosaic lowering, VMEM/block-shape validity,
and the real TPU compiler's memory layout WITHOUT a chip.

How: libtpu (in-image, pip `libtpu`) exposes PJRT compile-only device
topologies — ``jax.experimental.topologies.get_topology_desc("v5p:2x2x1",
platform="tpu")`` loads the real TPU compiler and returns compile-only
devices. ``jax.jit(...).lower(abstract args with topology shardings)
.compile()`` then runs the full XLA:TPU + Mosaic pipeline (the same one a
real v5p would run) and yields cost/memory analysis plus a serializable
executable. No TPU hardware is touched.

Validated targets (each records compile seconds, XLA cost analysis, per-chip
memory stats, and a sha256 fingerprint of the serialized TPU executable):

- ``fused_loss_fwd`` / ``fused_loss_grad`` — the Liger-role Pallas kernel
  (ops/fused_loss.py; parity ref: liger fused losses at
  agilerl/algorithms/grpo.py:558) at llama3-8b lm-head dims (D=4096,
  V=128256), forward and custom-VJP backward (dH + dW kernels).
- ``flash_fwd`` / ``flash_grad`` — Pallas flash attention
  (ops/flash_attention_vjp.py), forward and custom VJP, at llama3 head dims (H=32, d=128, T=2048).
- ``decode_chunk`` — one BucketedGenerator decode chunk (llm/serving.py, the
  vLLM-role path, ref core/base.py:3101) for the llama3-8b preset.
- ``paged_verify`` — the speculative-decoding verify step
  (llm/speculate.paged_verify_step through ContinuousGenerator._verify):
  K drafts per slot scored in one forward over the paged pool.
- ``grpo_step_small`` — the PRODUCTION fused GRPO update
  (algorithms/grpo.make_update_fn with flash + fused-loss Pallas kernels ON)
  compiled natively for one v5p core.
- ``grpo_7b_gspmd`` — the 7B GRPO update GSPMD-partitioned by the REAL TPU
  compiler for a v5p 4x4x4 (64-chip) topology, fsdp16xtp4; its
  memory_analysis is the hardware-grade per-chip HBM number for
  benchmarking/grpo_7b_plan.md.
- ``grpo_7b_flash`` — same, with the Pallas kernels ON under GSPMD
  (outcome recorded either way; pallas_call under GSPMD partitioning is the
  open question this target answers).

Run:  python benchmarking/tpu_aot_compile.py [--targets a,b,...] [--quick]
Writes benchmarking/aot_executable_store/report.{json,md} (git-ignored: a
run's output, not a record). The test tier runs tiny dims
via tests/test_ops/test_tpu_aot.py.

Executable store (ISSUE 15): every target the sweep compiles is PUBLISHED
into the persistent executable registry (``parallel/compile_cache``,
``--cache DIR``, default ``$AGILERL_TPU_COMPILE_CACHE`` or
``benchmarking/aot_executable_store``) — one sweep
doubles as warm-up for LATER SWEEP RUNS: re-running against the warm
store loads instead of compiling and reports per-target load-vs-compile
seconds under each record's ``cache`` key (on a compile-only topology
without loadable devices the deserialize falls back to
compile-and-republish, recorded as ``loaded: false``). Runtime consumers
(serving replicas, elastic recovery, layout search) fingerprint their OWN
names/plans/signatures and warm their stores through their own cold runs
— the strict fingerprint deliberately never matches across different
programs. ``--no-cache`` disables the store entirely.
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import json
import os
import sys
import time
import traceback


def _force_cpu_default() -> None:
    # The default backend stays CPU, so the sweep never takes a chip; the TPU
    # compiler is reached only through the compile-only topology below.
    os.environ["JAX_PLATFORMS"] = "cpu"
    # compile-only topologies never touch devices: skip libtpu's
    # multi-process lockfile so concurrent compiles don't collide
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "true")


def _fingerprint(compiled) -> str:
    """sha256 of the serialized TPU executable (fallback: optimized HLO)."""
    try:
        raw = compiled.runtime_executable().serialize()
    except Exception:
        raw = compiled.as_text().encode()
    return hashlib.sha256(raw).hexdigest()


def _record(compiled, lowered, t_lower, t_compile, topology, n_devices,
            analytic_flops=None):
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    rec = {
        "ok": True,
        "topology": topology,
        "n_devices": n_devices,
        "lower_seconds": round(t_lower, 1),
        "compile_seconds": round(t_compile, 1),
        # XLA cost analysis counts a lax.scan body ONCE: with
        # scan-over-layers (llm/model.py) this under-reports model targets
        # ~n_layer-fold. flops_analytic (PaLM-style 6N+attention accounting,
        # utils/profiling.py) is the faithful per-step total for those.
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "fingerprint_sha256": _fingerprint(compiled),
    }
    if analytic_flops is not None:
        rec["flops_analytic"] = float(analytic_flops)
    mem = compiled.memory_analysis()
    if mem is not None:
        rec.update(
            generated_code_bytes=int(mem.generated_code_size_in_bytes),
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
        )
    return rec


#: set by main(): the persistent executable store the sweep publishes into,
#: and the current target name/devices (set by run()) keying its fingerprint
_STORE = None
_TARGET_NAME = None
_TARGET_DEVICES = None


def _compile(fn, args, topology, n_devices, kwargs=None, analytic_flops=None):
    t0 = time.time()
    lowered = fn.lower(*args, **(kwargs or {}))
    t_lower = time.time() - t0

    fp = parts = None
    cache_rec = None
    if _STORE is not None and _TARGET_NAME is not None:
        from agilerl_tpu.parallel.compile_cache import (
            _sha256_text, deserialize_payload, fingerprint_digest,
            fingerprint_parts,
        )

        parts = fingerprint_parts(
            _TARGET_NAME, args=args, kwargs=kwargs,
            devices=_TARGET_DEVICES,
            extra={"topology": topology, "n_devices": int(n_devices)},
            lowered_sha256=_sha256_text(lowered.as_text()))
        fp = fingerprint_digest(parts)
        payload = _STORE.get_payload(fp)
        if payload is not None:
            t0 = time.time()
            try:
                deserialize_payload(payload)
            except Exception as e:
                # compile-only topologies have no loadable devices (and a
                # toolchain drift the fingerprint missed lands here too):
                # fall back to compile-and-republish, recorded honestly
                cache_rec = {
                    "hit": True, "loaded": False, "fingerprint": fp,
                    "deserialize_error": f"{type(e).__name__}: {str(e)[:200]}",
                }
            else:
                load_s = time.time() - t0
                manifest = _STORE.read_manifest(fp) or {}
                rec = dict(manifest.get("record") or {})
                if rec.get("ok"):
                    rec["cache"] = {
                        "hit": True, "loaded": True, "fingerprint": fp,
                        "load_seconds": round(load_s, 3),
                        "stored_compile_seconds": rec.get("compile_seconds"),
                    }
                    return rec
                cache_rec = {"hit": True, "loaded": True, "fingerprint": fp,
                             "manifest_record_missing": True}

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    rec = _record(compiled, lowered, t_lower, t_compile, topology, n_devices,
                  analytic_flops=analytic_flops)
    if _STORE is not None and fp is not None:
        from agilerl_tpu.parallel.compile_cache import serialize_compiled

        try:
            payload = serialize_compiled(compiled)
            _STORE.publish(fp, payload, manifest_extra={
                "record": rec, "fingerprint": parts,
                "published_by": f"tpu_aot_compile/{_TARGET_NAME}",
            })
        except Exception as e:
            # an unserializable target (or a full store) still VALIDATED —
            # the sweep's purpose; it just can't warm the cache
            rec["cache"] = dict(cache_rec or {"hit": False},
                                published=False,
                                publish_error=f"{type(e).__name__}: "
                                              f"{str(e)[:200]}")
        else:
            rec["cache"] = dict(cache_rec or {"hit": False},
                                published=True, fingerprint=fp)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--targets", default=None,
                    help="comma list (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="shrink dims for a fast smoke pass")
    ap.add_argument("--topology", default="v5p:2x2x1",
                    help="single-core targets compile for devices[0] of this")
    ap.add_argument("--pod", default="v5p:4x4x4",
                    help="64-chip topology for the GSPMD targets")
    ap.add_argument("--write", default=None,
                    help="report path prefix (default "
                         "benchmarking/aot_executable_store/report)")
    ap.add_argument("--cache", default=None,
                    help="executable store dir (default: "
                         "$AGILERL_TPU_COMPILE_CACHE or "
                         "benchmarking/aot_executable_store)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the executable store")
    args = ap.parse_args(argv)

    _force_cpu_default()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from agilerl_tpu.ops.kernel_mode import native_kernels

    global _STORE, _TARGET_DEVICES
    if not args.no_cache:
        from agilerl_tpu.parallel.compile_cache import ExecutableStore

        cache_dir = args.cache or os.environ.get(
            "AGILERL_TPU_COMPILE_CACHE", "").strip() or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "aot_executable_store")
        _STORE = ExecutableStore(cache_dir)
        print(f"[aot] executable store: {cache_dir}", file=sys.stderr,
              flush=True)

    report = {"libtpu": True, "targets": {}}
    try:
        topo = topologies.get_topology_desc(args.topology, platform="tpu")
    except Exception as e:  # no libtpu / unsupported — record and bail
        report["libtpu"] = False
        report["error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(report))
        return report
    dev0 = topo.devices[0]
    s1 = SingleDeviceSharding(dev0)
    _TARGET_DEVICES = [dev0]
    report["device_kind"] = dev0.device_kind

    want = set(args.targets.split(",")) if args.targets else None

    def run(name, builder):
        global _TARGET_NAME
        if want is not None and name not in want:
            return
        print(f"[aot] {name} ...", file=sys.stderr, flush=True)
        _TARGET_NAME = name
        try:
            with native_kernels():
                report["targets"][name] = builder()
            rec = report["targets"][name]
            cache = rec.get("cache") or {}
            # hit-but-record-missing recompiles: loaded is True with no
            # load_seconds — key on the timing field itself
            took = (f"{cache['load_seconds']}s load (compiled once at "
                    f"{cache.get('stored_compile_seconds')}s)"
                    if cache.get("load_seconds") is not None
                    else f"{rec.get('compile_seconds')}s compile")
            print(f"[aot] {name} ok ({took})", file=sys.stderr, flush=True)
        except Exception as e:
            report["targets"][name] = {
                "ok": False,
                "error": f"{type(e).__name__}: {str(e)[:2000]}",
                "traceback": traceback.format_exc()[-4000:],
            }
            print(f"[aot] {name} FAILED: {type(e).__name__}: {str(e)[:200]}",
                  file=sys.stderr, flush=True)

    # ---- kernel micro-targets (llama3-8b dims) --------------------------
    from agilerl_tpu.ops.fused_loss import (
        fused_token_logprob, fused_token_logprob_diff,
    )
    from agilerl_tpu.ops.flash_attention_vjp import flash_attention_diff

    N, D, V = (256, 512, 4096) if args.quick else (2048, 4096, 128256)
    B, H, T, hd = (2, 4, 256, 128) if args.quick else (4, 32, 2048, 128)

    def fused_fwd():
        h = jax.ShapeDtypeStruct((N, D), jnp.bfloat16, sharding=s1)
        w = jax.ShapeDtypeStruct((D, V), jnp.bfloat16, sharding=s1)
        t = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=s1)
        fn = jax.jit(functools.partial(fused_token_logprob, interpret=False))
        return _compile(fn, (h, w, t), args.topology, 1)

    def fused_grad():
        h = jax.ShapeDtypeStruct((N, D), jnp.bfloat16, sharding=s1)
        w = jax.ShapeDtypeStruct((D, V), jnp.bfloat16, sharding=s1)
        t = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=s1)

        def loss(hh, ww, tt):
            return fused_token_logprob_diff(hh, ww, tt, 1.0).sum()

        fn = jax.jit(jax.grad(loss, argnums=(0, 1)))
        return _compile(fn, (h, w, t), args.topology, 1)

    def flash_fwd():
        q = jax.ShapeDtypeStruct((B, H, T, hd), jnp.bfloat16, sharding=s1)
        m = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=s1)
        fn = jax.jit(functools.partial(
            flash_attention_diff, causal=True, interpret=False))
        return _compile(fn, (q, q, q, m), args.topology, 1)

    def flash_grad():
        q = jax.ShapeDtypeStruct((B, H, T, hd), jnp.bfloat16, sharding=s1)
        m = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=s1)

        def loss(qq, kk, vv, mm):
            return flash_attention_diff(
                qq, kk, vv, mm, interpret=False).astype(jnp.float32).sum()

        fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return _compile(fn, (q, q, q, m), args.topology, 1)

    run("fused_loss_fwd", fused_fwd)
    run("fused_loss_grad", fused_grad)
    run("flash_fwd", flash_fwd)
    run("flash_grad", flash_grad)

    # ---- decode chunk (the vLLM-role serving path) ----------------------
    from agilerl_tpu.llm import model as Mod
    from agilerl_tpu.llm.presets import preset
    from agilerl_tpu.llm.serving import BucketedGenerator

    def decode_chunk():
        cfg = preset("llama3-8b" if not args.quick else "llama3-8b",
                     max_seq_len=2048, use_flash_attention=False)
        if args.quick:
            cfg = Mod.GPTConfig(
                vocab_size=1024, n_layer=2, n_head=4, n_kv_head=2,
                d_model=128, d_ff=256, max_seq_len=512)
        gen = BucketedGenerator(cfg, max_new_tokens=64, decode_chunk=32,
                                eos_id=2)
        rows, pb = (8, 64) if args.quick else (32, 1024)
        params_abs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s1),
            jax.eval_shape(lambda k: Mod.init_params(k, cfg),
                           jax.random.PRNGKey(0)))
        carry_abs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s1),
            jax.eval_shape(
                lambda p: gen._prefill_impl(
                    p, None,
                    jnp.zeros((rows, pb), jnp.int32),
                    jnp.zeros((rows, pb), jnp.int32),
                    jnp.zeros((rows,), bool),
                    jax.random.PRNGKey(0)),
                params_abs)[0])
        step_abs = jax.ShapeDtypeStruct((), jnp.int32, sharding=s1)
        return _compile(gen._decode, (params_abs, None, carry_abs, step_abs),
                        args.topology, 1)

    run("decode_chunk", decode_chunk)

    # ---- paged verify (speculative decoding, llm/speculate.py) ----------
    from agilerl_tpu.llm.serving import ContinuousGenerator

    def paged_verify():
        cfg = preset("llama3-8b", max_seq_len=2048,
                     use_flash_attention=False)
        if args.quick:
            cfg = Mod.GPTConfig(
                vocab_size=1024, n_layer=2, n_head=4, n_kv_head=2,
                d_model=128, d_ff=256, max_seq_len=512)
        slots, bsz, pb = (8, 16, 64) if args.quick else (32, 32, 1024)
        gen = ContinuousGenerator(
            cfg, max_new_tokens=64, decode_chunk=32, eos_id=2, slots=slots,
            block_size=bsz, prompt_buckets=(pb,), speculate=True)
        a = jax.ShapeDtypeStruct

        def _abs(l):
            return a(l.shape, l.dtype, sharding=s1)

        params_abs = jax.tree_util.tree_map(
            _abs, jax.eval_shape(lambda k: Mod.init_params(k, cfg),
                                 jax.random.PRNGKey(0)))
        pool_abs = jax.tree_util.tree_map(
            _abs, jax.eval_shape(
                lambda: Mod.init_paged_cache(cfg, gen.n_blocks,
                                             gen.block_size)))
        S = gen.max_blocks * gen.block_size
        # the decode-chunk carry plus the [slots, K] draft block — the ONE
        # verify program every accept outcome reuses (CompileGuard bound)
        vargs = (
            a((slots, gen.max_blocks), jnp.int32),       # tables
            a((slots, S), jnp.int32),                    # slot mask
            a((slots,), jnp.int32),                      # lengths
            a((slots,), jnp.int32),                      # prev_tok
            a((slots,), jnp.bool_),                      # prev_ok
            a((slots,), jnp.int32),                      # pos
            a((slots,), jnp.int32),                      # step_idx
            a((slots,), jnp.bool_),                      # done
            a((slots, 2), jnp.uint32),                   # keys
            a((slots, gen.speculate.k), jnp.int32),      # drafts
            a((slots,), jnp.int32),                      # draft_len
        )
        return _compile(gen._verify, (params_abs, None, pool_abs) + vargs,
                        args.topology, 1, kwargs={"greedy": True})

    run("paged_verify", paged_verify)

    # ---- fused GRPO step, single core, Pallas kernels ON ----------------
    from agilerl_tpu.algorithms.grpo import make_update_fn
    from agilerl_tpu.algorithms.core.optimizer import OptimizerWrapper

    def grpo_step_small():
        cfg = Mod.GPTConfig(
            vocab_size=32768, n_layer=4, n_head=8, n_kv_head=4,
            d_model=512, d_ff=1408, max_seq_len=512,
            use_flash_attention=True)
        if args.quick:
            cfg = Mod.GPTConfig(
                vocab_size=1024, n_layer=2, n_head=4, n_kv_head=2,
                d_model=256, d_ff=512, max_seq_len=256,
                use_flash_attention=True)
        Bt, Tt = (2, 128) if args.quick else (8, 512)
        opt = OptimizerWrapper(optimizer="adamw", lr=5e-6, max_grad_norm=0.1)
        base_abs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s1),
            jax.eval_shape(lambda k: Mod.init_params(k, cfg),
                           jax.random.PRNGKey(0)))
        lora_abs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s1),
            jax.eval_shape(lambda k: Mod.init_lora(k, cfg, 8),
                           jax.random.PRNGKey(0)))
        opt_abs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s1),
            jax.eval_shape(
                opt.tx.init,
                jax.eval_shape(lambda k: Mod.init_lora(k, cfg, 8),
                               jax.random.PRNGKey(0))))
        batch_abs = {
            "tokens": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32, sharding=s1),
            "mask": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32, sharding=s1),
            "loss_mask": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32, sharding=s1),
            "old_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32, sharding=s1),
            "ref_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32, sharding=s1),
            "advantage": jax.ShapeDtypeStruct((Bt,), jnp.float32, sharding=s1),
        }
        scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=s1)
        update = make_update_fn(cfg, opt.tx, lora_scale=2.0, use_flash=True)
        from agilerl_tpu.utils.profiling import transformer_flops_per_token
        return _compile(update, (base_abs, lora_abs, opt_abs, batch_abs,
                                 scalar, scalar), args.topology, 1,
                        analytic_flops=(transformer_flops_per_token(cfg)
                                        * Bt * Tt))

    run("grpo_step_small", grpo_step_small)

    # ---- 7B GSPMD for the v5p pod topology ------------------------------
    # shardings resolve through the DECLARATIVE plan engine: the same
    # (regex -> PartitionSpec) rule set the whole repo uses, loaded from
    # configs/sharding/*.yaml when a committed plan matches the topology.
    from agilerl_tpu.parallel.plan import (
        ShardingPlan, compile_step_with_plan, make_grpo_plan,
    )

    def _grpo_plan_for(fsdp, tp):
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "configs", "sharding", f"grpo_7b_fsdp{fsdp}xtp{tp}.yaml")
        if os.path.exists(path):
            return ShardingPlan.from_yaml(path), os.path.basename(path)
        return make_grpo_plan(fsdp=fsdp, tp=tp), "builtin rules"

    def _pod_target(use_flash: bool):
        ptopo = topologies.get_topology_desc(args.pod, platform="tpu")
        n = len(ptopo.devices)
        tp = 4 if n % 4 == 0 else 1
        fsdp = n // tp
        plan, plan_src = _grpo_plan_for(fsdp, tp)
        mesh = plan.build_mesh(list(ptopo.devices))
        cfg = preset("llama3-8b", max_seq_len=2048,
                     use_flash_attention=use_flash,
                     flash_shard_axes=((("dp", "fsdp"), "tp")
                                       if use_flash else None))
        Bt, Tt = (16, 512) if args.quick else (64, 2048)

        base_shapes = jax.eval_shape(lambda k: Mod.init_params(k, cfg),
                                     jax.random.PRNGKey(0))
        lora_shapes = jax.eval_shape(lambda k: Mod.init_lora(k, cfg, 16),
                                     jax.random.PRNGKey(0))
        opt = OptimizerWrapper(optimizer="adamw", lr=5e-6, max_grad_norm=0.1)
        opt_shapes = jax.eval_shape(opt.tx.init, lora_shapes)
        batch_shapes = {
            "tokens": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32),
            "mask": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32),
            "old_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32),
            "ref_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32),
            "advantage": jax.ShapeDtypeStruct((Bt,), jnp.float32),
        }
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        # flash attention stays Pallas at pod scale (custom partitioning over
        # batch x heads); the lm-head loss deliberately uses XLA's chunked
        # tp-sharded path — see make_update_fn's use_fused_loss note
        update = make_update_fn(cfg, opt.tx, lora_scale=2.0,
                                use_flash=use_flash, use_fused_loss=False)
        step = compile_step_with_plan(
            update, plan,
            ("params", "lora", "optimizer", "batch", None, None),
            mesh=mesh, constrain_inputs=False)
        abs_args = step.abstract_args(base_shapes, lora_shapes, opt_shapes,
                                      batch_shapes, scalar, scalar)
        from agilerl_tpu.utils.profiling import transformer_flops_per_token
        with mesh:
            rec = _compile(step._jit_fn, abs_args, args.pod, n,
                           analytic_flops=(transformer_flops_per_token(cfg)
                                           * Bt * Tt))
        rec["mesh"] = f"fsdp{fsdp}xtp{tp}"
        rec["batch"], rec["seq"] = Bt, Tt
        rec["sharding_plan"], rec["sharding_plan_source"] = plan.name, plan_src
        return rec

    run("grpo_7b_gspmd", lambda: _pod_target(use_flash=False))
    run("grpo_7b_flash", lambda: _pod_target(use_flash=True))

    # fsdp-only mesh with the FULL Pallas tier on: flash (shard_map over
    # batch x heads) AND the row-sharded fused loss (shard_map over batch,
    # dW cotangent psummed by the transpose) — the single-slice recipe
    def grpo_fsdp_fused():
        n = len(topo.devices)
        plan = make_grpo_plan(fsdp=n)
        mesh = plan.build_mesh(list(topo.devices))
        cfg = Mod.GPTConfig(
            vocab_size=32768, n_layer=4, n_head=8, n_kv_head=4,
            d_model=512, d_ff=1408, max_seq_len=512,
            use_flash_attention=True,
            flash_shard_axes=(("dp", "fsdp"), "tp"),
            fused_loss_shard_axes=("dp", "fsdp"))
        Bt, Tt = (n, 128) if args.quick else (2 * n, 512)
        opt = OptimizerWrapper(optimizer="adamw", lr=5e-6, max_grad_norm=0.1)

        base_shapes = jax.eval_shape(lambda k: Mod.init_params(k, cfg),
                                     jax.random.PRNGKey(0))
        lora_shapes = jax.eval_shape(lambda k: Mod.init_lora(k, cfg, 8),
                                     jax.random.PRNGKey(0))
        opt_shapes = jax.eval_shape(opt.tx.init, lora_shapes)
        batch_shapes = {
            "tokens": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32),
            "mask": jax.ShapeDtypeStruct((Bt, Tt), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32),
            "old_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32),
            "ref_lp": jax.ShapeDtypeStruct((Bt, Tt - 1), jnp.float32),
            "advantage": jax.ShapeDtypeStruct((Bt,), jnp.float32),
        }
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        update = make_update_fn(cfg, opt.tx, lora_scale=2.0, use_flash=True,
                                use_fused_loss=True)
        # NB: the plan's optimizer rules shard the adam moments like their
        # params (the production layout); the pre-plan harness left the opt
        # state replicated here, so this target's fingerprint moved once
        step = compile_step_with_plan(
            update, plan,
            ("params", "lora", "optimizer", "batch", None, None),
            mesh=mesh, constrain_inputs=False)
        abs_args = step.abstract_args(base_shapes, lora_shapes, opt_shapes,
                                      batch_shapes, scalar, scalar)
        from agilerl_tpu.utils.profiling import transformer_flops_per_token
        with mesh:
            rec = _compile(step._jit_fn, abs_args, args.topology, n,
                           analytic_flops=(transformer_flops_per_token(cfg)
                                           * Bt * Tt))
        rec["mesh"] = f"fsdp{n}"
        rec["batch"], rec["seq"] = Bt, Tt
        rec["sharding_plan"] = plan.name
        return rec

    run("grpo_fsdp_fused", grpo_fsdp_fused)

    # ring attention with the Pallas per-block engine over an sp axis:
    # shard_map + ppermute + flash_attention_with_lse compile for TPU
    def ring_flash():
        from jax.sharding import Mesh, PartitionSpec as P

        from agilerl_tpu.ops.ring_attention import make_ring_attention

        n = len(topo.devices)
        mesh = Mesh(np.array(topo.devices), ("sp",))
        B, T, Hh, dd = (2, 64 * n, 4, 64) if args.quick else (4, 512 * n, 8, 128)
        ring = make_ring_attention(mesh, causal=True, use_flash=True)
        spec = NamedSharding(mesh, P(None, "sp", None, None))
        x = jax.ShapeDtypeStruct((B, T, Hh, dd), jnp.bfloat16, sharding=spec)

        def loss(q, k, v):
            return (ring(q, k, v).astype(jnp.float32) ** 2).sum()

        with mesh:
            return _compile(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                            (x, x, x), args.topology, n)

    run("ring_flash", ring_flash)

    prefix = args.write or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "aot_executable_store",
        "report")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    with open(prefix + ".json", "w") as fh:
        json.dump(report, fh, indent=1)
    with open(prefix + ".md", "w") as fh:
        fh.write(_render_md(report))
    print(json.dumps({k: (v if k != "targets" else {
        n: {kk: r.get(kk) for kk in ("ok", "compile_seconds", "flops",
                                     "temp_bytes", "error")}
        for n, r in v.items()}) for k, v in report.items()}))
    return report


def _render_md(report):
    lines = [
        "# TPU AOT compile report (compile-only topology, no chip)",
        "",
        f"Device kind: **{report.get('device_kind', '?')}** — real XLA:TPU + "
        "Mosaic pipeline via libtpu's compile-only PJRT topology "
        "(`benchmarking/tpu_aot_compile.py`). Every `ok` row below is a "
        "TPU-backend-compiled executable: Mosaic lowering, VMEM fit, and "
        "block-shape validity are checked by the chip's own compiler with "
        "no chip attached. Nothing ran: a row here is not a chip run.",
        "",
        "| target | topology | ok | compile s | GFLOPs | temp MiB | fingerprint |",
        "|---|---|---|---|---|---|---|",
    ]
    for name, r in report.get("targets", {}).items():
        if r.get("ok"):
            cache = r.get("cache") or {}
            took = (f"{cache['load_seconds']} (load)"
                    if cache.get("load_seconds") is not None
                    else f"{r['compile_seconds']}")
            lines.append(
                f"| {name} | {r['topology']} ({r['n_devices']}d) | yes | "
                f"{took} | {r['flops'] / 1e9:.1f} | "
                f"{r.get('temp_bytes', 0) / 2**20:.1f} | "
                f"`{r['fingerprint_sha256'][:16]}` |")
        else:
            lines.append(f"| {name} | — | **no** | — | — | — | "
                         f"{r.get('error', '')[:80]} |")
    lines += [
        "",
        "Fingerprints are sha256 of the serialized TPU executable "
        "(fallback: optimized HLO text).",
    ]
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    main()
