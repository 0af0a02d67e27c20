"""Persistent executable store (ISSUE 15): strict-fingerprint unit tests
(every skew is a MISS, never a wrong executable), commit-dir durability
(torn entries skipped and recompiled, GC keeps newest-per-fingerprint),
and the CPU-backend acceptance gates — a pod generation and a plan-compiled
step LOADED from the store are bit-identical to the fresh compile, with
CompileGuard proving the warm path compiles zero new XLA programs across
elastic re-form and layout-search candidate eval."""

import os
import pathlib

import jax
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from agilerl_tpu.analysis.runtime import CompileGuard
from agilerl_tpu.envs import CartPole
from agilerl_tpu.modules.mlp import MLPConfig
from agilerl_tpu.networks import distributions as D
from agilerl_tpu.networks.base import NetworkConfig, default_encoder_config
from agilerl_tpu.observability.registry import MetricsRegistry
from agilerl_tpu.parallel import plan as PL
from agilerl_tpu.parallel.compile_cache import (
    CachedFunction,
    ExecutableStore,
    enable_jax_cache,
    fingerprint_digest,
    fingerprint_parts,
    load_or_compile,
    resolve_cache,
)
from agilerl_tpu.parallel.layout_search import search_layouts
from agilerl_tpu.resilience import FaultInjector

pytestmark = pytest.mark.compile_cache


def _mesh4():
    return Mesh(np.array(jax.devices()[:4]), ("pop",))


def _leaves_equal(a, b):
    la = [np.asarray(x) for x in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(x) for x in jax.tree_util.tree_leaves(b)]
    return len(la) == len(lb) and all(
        x.tobytes() == y.tobytes() for x, y in zip(la, lb))


# --------------------------------------------------------------------------- #
# fingerprint: every contract component skews to a MISS
# --------------------------------------------------------------------------- #


class TestFingerprint:
    def _base(self, **over):
        kw = dict(args=(np.ones((4, 3), np.float32),), donate_argnums=(0,),
                  lowered_sha256="abc")
        kw.update(over)
        return fingerprint_digest(fingerprint_parts("t", **kw))

    def test_identical_parts_identical_digest(self):
        assert self._base() == self._base()

    def test_shape_skew_misses(self):
        assert self._base() != self._base(
            args=(np.ones((4, 4), np.float32),))

    def test_dtype_skew_misses(self):
        assert self._base() != self._base(
            args=(np.ones((4, 3), np.float64),))

    def test_donation_skew_misses(self):
        assert self._base() != self._base(donate_argnums=())

    def test_version_skew_misses(self):
        assert self._base() != self._base(
            versions={"jax": "99.0", "jaxlib": "99.0", "libtpu": None})

    def test_topology_skew_misses(self):
        m42 = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
        m24 = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
        assert self._base(mesh=m42) != self._base(mesh=m24)

    def test_sharding_skew_misses(self):
        mesh = _mesh4()
        a = jax.device_put(np.ones((4, 4), np.float32),
                           NamedSharding(mesh, P("pop")))
        b = jax.device_put(np.ones((4, 4), np.float32),
                           NamedSharding(mesh, P(None, "pop")))
        assert self._base(args=(a,)) != self._base(args=(b,))

    def test_plan_rule_skew_misses(self):
        p1 = PL.make_grpo_plan(fsdp=4, name="fp-skew")
        p2 = PL.ShardingPlan(name="fp-skew", axes=dict(p1.axes),
                             rules={"params": [(r".*", P())]})
        # same NAME, different resolved rules -> different plan hash
        assert self._base(plan=p1) != self._base(plan=p2)

    def test_static_and_hlo_skew_miss(self):
        assert self._base(static_args={"greedy": True}) != self._base(
            static_args={"greedy": False})
        assert self._base(lowered_sha256="abc") != self._base(
            lowered_sha256="def")

    def test_host_and_single_device_args_key_identically(self):
        """An abstract ShapeDtypeStruct, a numpy array and an uncommitted
        single-device array lower to ONE program — warm_start's prepared
        signature must equal the runtime call's."""
        host = self._base(args=(np.ones((4, 3), np.float32),))
        dev = self._base(args=(jax.device_put(np.ones((4, 3), np.float32)),))
        abstract = self._base(
            args=(jax.ShapeDtypeStruct((4, 3), np.float32),))
        assert host == dev == abstract


# --------------------------------------------------------------------------- #
# the store: durability semantics over the commit-dir protocol
# --------------------------------------------------------------------------- #


def _jit_double():
    return jax.jit(lambda x, k: (x * 2 + jax.random.uniform(k), x.sum()))


class TestStore:
    def test_load_equals_compile_bit_for_bit(self, tmp_path, key):
        reg = MetricsRegistry()
        store = ExecutableStore(tmp_path, metrics=reg)
        x = np.ones((8, 8), np.float32)
        cold, info = load_or_compile(_jit_double(), (x, key), name="t",
                                     store=store)
        assert not info["hit"] and info.get("published")
        warm, winfo = load_or_compile(_jit_double(), (x, key), name="t",
                                      store=ExecutableStore(tmp_path,
                                                            metrics=reg))
        assert winfo["hit"] and winfo["fingerprint"] == info["fingerprint"]
        with CompileGuard(label="warm-load"):
            out_w = warm(x, key)
        assert _leaves_equal(cold(x, key), out_w)
        assert reg.counter("compile_cache/hits_total").value == 1
        assert reg.counter("compile_cache/misses_total").value == 1

    def test_torn_entry_skipped_and_recompiled(self, tmp_path, key):
        """FaultInjector truncates the payload as it lands (silent disk
        corruption): the sha-validated read SKIPS the torn entry (counted),
        the call falls back to compile-and-republish, and the store heals."""
        reg = MetricsRegistry()
        store = ExecutableStore(tmp_path, metrics=reg)
        x = np.ones((4, 4), np.float32)
        with FaultInjector(truncate_at_ops=[0], match=("wrote",),
                           path_match="payload.pkl"):
            _, info = load_or_compile(_jit_double(), (x, key), name="torn",
                                      store=store)
        fp = info["fingerprint"]
        assert store.has(fp)  # committed, but its payload is torn
        reg2 = MetricsRegistry()
        warm, winfo = load_or_compile(
            _jit_double(), (x, key), name="torn",
            store=ExecutableStore(tmp_path, metrics=reg2))
        assert not winfo["hit"]  # torn entry never loads
        assert reg2.counter("compile_cache/torn_entries_total").value >= 1
        assert winfo.get("published")
        # ... and the republished entry now loads
        _, w2 = load_or_compile(
            _jit_double(), (x, key), name="torn",
            store=ExecutableStore(tmp_path, metrics=MetricsRegistry()))
        assert w2["hit"]

    @pytest.mark.parametrize("bad", ["junk", "device_this_process_lacks"])
    def test_deserialize_failure_falls_back_and_republishes(self, tmp_path,
                                                            key, bad):
        reg = MetricsRegistry()
        store = ExecutableStore(tmp_path, metrics=reg)
        x = np.ones((4, 4), np.float32)
        _, info = load_or_compile(_jit_double(), (x, key), name="bad",
                                  store=store)
        fp = info["fingerprint"]
        payload = store.get_payload(fp)
        assert payload["device_ids"] == [jax.devices()[0].id]
        # a VALID commit whose payload is not a loadable executable here
        if bad == "junk":
            payload = {"exe": b"junk", "in_tree": None, "out_tree": None}
        else:  # compiled for a device id no device of this process has
            payload = dict(payload, device_ids=[10 ** 6])
        store.publish(fp, payload)
        fn, winfo = load_or_compile(_jit_double(), (x, key), name="bad",
                                    store=store)
        assert not winfo["hit"] and winfo.get("published")
        assert reg.counter(
            "compile_cache/deserialize_failures_total").value == 1
        # the republished (newest) entry loads on the next walk
        _, w2 = load_or_compile(_jit_double(), (x, key), name="bad",
                                store=store)
        assert w2["hit"]

    def test_gc_keeps_newest_per_fingerprint(self, tmp_path):
        store = ExecutableStore(tmp_path, keep_last=1)
        store.publish("aa", {"v": 1})
        store.publish("aa", {"v": 2})
        store.publish("bb", {"v": 3})
        assert store.get_payload("aa") == {"v": 2}  # newest wins
        assert store.get_payload("bb") == {"v": 3}  # other fp untouched
        assert len(store._entry_store("aa").entries()) == 1

    def test_resolve_cache_env_opt_in(self, tmp_path, monkeypatch):
        monkeypatch.delenv("AGILERL_TPU_COMPILE_CACHE", raising=False)
        assert resolve_cache(None) is None
        monkeypatch.setenv("AGILERL_TPU_COMPILE_CACHE", str(tmp_path))
        store = resolve_cache(None)
        assert isinstance(store, ExecutableStore)
        assert store.directory == tmp_path
        assert resolve_cache(False) is None  # explicit off beats the env
        passthrough = ExecutableStore(tmp_path)
        assert resolve_cache(passthrough) is passthrough


# --------------------------------------------------------------------------- #
# CachedFunction semantics
# --------------------------------------------------------------------------- #


class TestCachedFunction:
    def test_static_kwarg_variants_are_distinct_programs(self, tmp_path, key):
        def f(x, greedy=False):
            return x + 1 if greedy else x - 1

        store = ExecutableStore(tmp_path)
        cf = CachedFunction(jax.jit(f, static_argnames=("greedy",)),
                            name="static", store=store,
                            static_argnames=("greedy",))
        x = np.ones((4,), np.float32)
        np.testing.assert_array_equal(np.asarray(cf(x, greedy=True)), x + 1)
        np.testing.assert_array_equal(np.asarray(cf(x, greedy=False)), x - 1)
        assert cf._cache_size() == 2
        assert len(store.fingerprints()) == 2

    def test_prepare_matches_concrete_call(self, tmp_path, key):
        store = ExecutableStore(tmp_path)
        cf = CachedFunction(_jit_double(), name="prep", store=store)
        cf.prepare(jax.ShapeDtypeStruct((4, 4), np.float32),
                   jax.ShapeDtypeStruct((2,), np.uint32))
        fp = cf.last_info["fingerprint"]
        cf2 = CachedFunction(_jit_double(), name="prep", store=store)
        cf2(np.ones((4, 4), np.float32), key)
        assert cf2.last_info["hit"]
        assert cf2.last_info["fingerprint"] == fp


# --------------------------------------------------------------------------- #
# acceptance gate 1: EvoPPO pod step — load ≡ compile, zero new programs
# --------------------------------------------------------------------------- #


def _net(env, outputs, latent=16, hidden=32):
    kind, enc = default_encoder_config(
        env.observation_space, latent_dim=latent,
        encoder_config={"hidden_size": (hidden,)},
    )
    return NetworkConfig(
        encoder_kind=kind, encoder=enc,
        head=MLPConfig(num_inputs=latent, num_outputs=outputs,
                       hidden_size=(hidden,)),
        latent_dim=latent,
    )


def _ppo():
    from agilerl_tpu.parallel import EvoPPO

    env = CartPole()
    dist = D.dist_config_from_space(env.action_space)
    return EvoPPO(env, _net(env, 2), _net(env, 1), dist, optax.adam(3e-4),
                  num_envs=2, rollout_len=8, update_epochs=1,
                  num_minibatches=2)


class TestPodStepGate:
    def test_evoppo_pod_step_load_equals_compile(self, tmp_path):
        """The tier-1 CPU gate: an EvoPPO pod generation loaded from the
        store produces BIT-identical populations and fitness to the fresh
        compile, and the warm path compiles zero new XLA programs."""
        mesh = _mesh4()
        evo = _ppo()
        gen = evo.make_pod_generation(mesh, donate=False)
        store = ExecutableStore(tmp_path)
        pop = evo.init_population(jax.random.PRNGKey(7), 4)
        k = jax.random.PRNGKey(8)

        cold = CachedFunction(gen, name="pod/evoppo", store=store, mesh=mesh)
        pop_c, fit_c = cold(pop, k)
        assert cold.last_info["hit"] is False

        # fresh wrapper over a fresh jit == a fresh process's first call
        gen2 = _ppo().make_pod_generation(mesh, donate=False)
        warm = CachedFunction(gen2, name="pod/evoppo", store=store, mesh=mesh)
        with CompileGuard(label="warm-pod-step"):
            pop_w, fit_w = warm(pop, k)
        assert warm.last_info["hit"] is True
        assert _leaves_equal(pop_c, pop_w)
        assert np.asarray(fit_c).tobytes() == np.asarray(fit_w).tobytes()


# --------------------------------------------------------------------------- #
# acceptance gate 2: plan-compiled step + layout search
# --------------------------------------------------------------------------- #


def _loss_step(params, batch):
    y = batch["x"] @ params["w"]
    return (y ** 2).mean()


def _loss_args(plan, mesh):
    return ({"w": np.ones((16, 8), np.float32)},
            {"x": np.ones((32, 16), np.float32)})


class TestPlanStepAndLayoutSearch:
    def test_plan_compiled_step_loads_bit_identical(self, tmp_path):
        plan = PL.make_grpo_plan(fsdp=4, tp=2, name="cc-fsdp4tp2")
        store = ExecutableStore(tmp_path)
        step = PL.compile_step_with_plan(
            _loss_step, plan, ("lora", "batch"), cache=store)
        args = step.place_args(*_loss_args(plan, step.mesh))
        out_c = step(*args)
        assert step.cache_info["hit"] is False

        step2 = PL.compile_step_with_plan(
            _loss_step, plan, ("lora", "batch"), cache=store)
        args2 = step2.place_args(*_loss_args(plan, step2.mesh))
        with CompileGuard(label="warm-plan-step"):
            out_w = step2(*args2)
        assert step2.cache_info["hit"] is True
        assert np.asarray(out_c).tobytes() == np.asarray(out_w).tobytes()

    def test_layout_search_pays_compile_once_per_layout(self, tmp_path):
        plans = [PL.make_grpo_plan(fsdp=8, name="cc-ls-fsdp8"),
                 PL.make_grpo_plan(fsdp=4, tp=2, name="cc-ls-fsdp4tp2")]
        reg = MetricsRegistry()
        res = search_layouts(_loss_step, ("lora", "batch"), _loss_args,
                             plans=plans, cache=tmp_path, steps=2,
                             warmup=1, registry=reg)
        assert [c.cache_hit for c in res.candidates] == [False, False]
        assert res.best is not None

        # the second sweep — a new process, a mutated member — loads every
        # candidate: compile once per layout EVER
        reg2 = MetricsRegistry()
        with CompileGuard(label="warm-layout-sweep"):
            res2 = search_layouts(_loss_step, ("lora", "batch"), _loss_args,
                                  plans=plans, cache=tmp_path, steps=2,
                                  warmup=1, registry=reg2)
        assert [c.cache_hit for c in res2.candidates] == [True, True]
        assert reg2.counter("compile_cache/hits_total").value == 2
        assert reg2.counter("compile_cache/misses_total").value == 0
        assert {c.plan.name for c in res2.ranked} == {
            c.plan.name for c in res.ranked}


# --------------------------------------------------------------------------- #
# acceptance gate 3: elastic re-form loads the re-formed layout's step
# --------------------------------------------------------------------------- #


def _dqn():
    from agilerl_tpu.parallel import EvoDQN

    env = CartPole()
    return EvoDQN(env, _net(env, 2), optax.adam(1e-3), num_envs=2,
                  steps_per_iter=8, buffer_size=64, batch_size=4)


class TestElasticWarmRecovery:
    def test_recovery_loads_instead_of_recompiling(self, tmp_path):
        """Scripted host kill, run twice against one executable store: the
        cold run publishes both layouts' pod generations; the warm run
        LOADS them (hits==2, misses==0), recovers inside a CompileGuard
        (zero new XLA programs from the kill boundary on), and reproduces
        the cold run's fitness stream bit-for-bit."""
        from agilerl_tpu.parallel import (
            ElasticPBTController, make_emulated_hosts)

        cache = tmp_path / "exe_store"

        def run_controller(workdir, reg, guard_from_kill=False):
            ctl = ElasticPBTController(
                _dqn(), 4, tmp_path / workdir, seed=3,
                hosts=make_emulated_hosts(2, jax.devices()[:4]),
                heartbeat_timeout=0.15, snapshot_every=1,
                fault_injector=FaultInjector(kill_host_at={2: 1}),
                registry=reg, compile_cache=cache)
            hist = [list(map(float, ctl.step_generation()))
                    for _ in range(2)]
            if guard_from_kill:
                with CompileGuard(label="elastic-warm-recovery"):
                    hist += [list(map(float, ctl.step_generation()))
                             for _ in range(2)]
            else:
                hist += [list(map(float, ctl.step_generation()))
                         for _ in range(2)]
            return hist

        reg_cold = MetricsRegistry()
        hist_cold = run_controller("cold", reg_cold)
        assert reg_cold.counter("compile_cache/misses_total").value == 2
        assert reg_cold.counter("compile_cache/hits_total").value == 0

        reg_warm = MetricsRegistry()
        hist_warm = run_controller("warm", reg_warm, guard_from_kill=True)
        assert reg_warm.counter("compile_cache/hits_total").value == 2
        assert reg_warm.counter("compile_cache/misses_total").value == 0
        assert hist_warm == hist_cold


# --------------------------------------------------------------------------- #
# agent jit_fn wiring (the sharding= mutation's recompile path)
# --------------------------------------------------------------------------- #


class TestAgentJitFnWiring:
    def test_agent_jit_fn_routes_through_store(self, tmp_path):
        from agilerl_tpu.algorithms.core.base import EvolvableAlgorithm

        class Agent:
            _wrap_compile_cache = EvolvableAlgorithm._wrap_compile_cache
            jit_fn = EvolvableAlgorithm.jit_fn

            def __init__(self, cache):
                self._jit_cache = {}
                self.compile_cache = cache

        agent = Agent(ExecutableStore(tmp_path))
        # cacheable is an explicit CONTRACT (no baked statics); the default
        # keeps plain jit even with a store configured — a jit's statics
        # are not introspectable, so uncached is the only safe default
        assert not isinstance(agent.jit_fn("plain", _jit_double),
                              CachedFunction)
        fn = agent.jit_fn("double", _jit_double, cacheable=True)
        assert isinstance(fn, CachedFunction)
        x, k = np.ones((3, 3), np.float32), jax.random.PRNGKey(0)
        out_c = fn(x, k)
        assert fn.last_info["hit"] is False

        agent2 = Agent(ExecutableStore(tmp_path))
        fn2 = agent2.jit_fn("double", _jit_double, cacheable=True)
        out_w = fn2(x, k)
        assert fn2.last_info["hit"] is True
        assert _leaves_equal(out_c, out_w)

    def test_mesh_placed_agent_skips_store(self, tmp_path):
        """Agent factories bake donation; persisting donating multi-device
        programs is unsafe on this jaxlib — a mesh-placed agent must get
        the RAW jit fn back (warn-once), never a cached one."""
        from agilerl_tpu.algorithms.core.base import EvolvableAlgorithm

        class Agent:
            _wrap_compile_cache = EvolvableAlgorithm._wrap_compile_cache
            jit_fn = EvolvableAlgorithm.jit_fn

            def __init__(self, cache, mesh):
                self._jit_cache = {}
                self.compile_cache = cache
                self.mesh = mesh

        agent = Agent(ExecutableStore(tmp_path), _mesh4())
        fn = agent.jit_fn("double", _jit_double, cacheable=True)
        assert not isinstance(fn, CachedFunction)


# --------------------------------------------------------------------------- #
# the AOT sweep doubles as cache warm-up (CPU-backend unit of the satellite)
# --------------------------------------------------------------------------- #


class TestAotSweepStore:
    def test_compile_then_load_reports_cache_provenance(self, tmp_path,
                                                        monkeypatch):
        import importlib.util
        import pathlib
        import sys

        root = pathlib.Path(__file__).resolve().parents[2]
        spec = importlib.util.spec_from_file_location(
            "tpu_aot_compile", root / "benchmarking" / "tpu_aot_compile.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules.setdefault("tpu_aot_compile", mod)
        spec.loader.exec_module(mod)

        monkeypatch.setattr(mod, "_STORE", ExecutableStore(tmp_path))
        monkeypatch.setattr(mod, "_TARGET_NAME", "unit_target")
        monkeypatch.setattr(mod, "_TARGET_DEVICES", jax.devices()[:1])

        fn = jax.jit(lambda x: (x * 3).sum())
        x = jax.ShapeDtypeStruct((8, 8), np.float32)
        rec = mod._compile(fn, (x,), "cpu:test", 1)
        assert rec["ok"] and rec["cache"] == {
            "hit": False, "published": True,
            "fingerprint": rec["cache"]["fingerprint"]}

        rec2 = mod._compile(fn, (x,), "cpu:test", 1)
        assert rec2["cache"]["hit"] and rec2["cache"]["loaded"]
        assert rec2["cache"]["stored_compile_seconds"] == rec[
            "compile_seconds"]
        assert rec2["fingerprint_sha256"] == rec["fingerprint_sha256"]


# --------------------------------------------------------------------------- #
# speculative verify program: every knob that changes semantics skews the
# fingerprint to a MISS (ISSUE 17 — K via the drafts arg shape, prompt
# bucket via the pool/table shapes, sampler knobs via the lowered-HLO sha)
# --------------------------------------------------------------------------- #


@pytest.mark.spec_decode
class TestPagedVerifyFingerprint:
    def _verify_fp(self, tmp_path, *, k=4, bucket=32, **sampler):
        from agilerl_tpu.llm import model as M
        from agilerl_tpu.llm.serving import ContinuousGenerator

        cfg = M.GPTConfig(vocab_size=64, n_layer=1, n_head=2, n_kv_head=2,
                          d_model=16, max_seq_len=256)
        gen = ContinuousGenerator(
            cfg, max_new_tokens=8, pad_id=0, prompt_buckets=(bucket,),
            slots=2, block_size=8, decode_chunk=4,
            metrics=MetricsRegistry(), speculate={"k": k},
            compile_cache=ExecutableStore(tmp_path), **sampler)
        # only_cached probe: lowers (which is what the fingerprint hashes)
        # without paying a backend compile per parametrization
        infos = gen.warm_start(greedy=False, only_cached=True)
        fps = [i["fingerprint"] for i in infos
               if i["name"] == "serving/paged_verify"]
        assert len(fps) == 1
        return fps[0]

    def test_same_knobs_same_fingerprint(self, tmp_path):
        assert (self._verify_fp(tmp_path)
                == self._verify_fp(tmp_path))

    def test_k_skew_misses(self, tmp_path):
        assert (self._verify_fp(tmp_path, k=4)
                != self._verify_fp(tmp_path, k=6))

    def test_bucket_skew_misses(self, tmp_path):
        assert (self._verify_fp(tmp_path, bucket=32)
                != self._verify_fp(tmp_path, bucket=64))

    def test_sampler_knob_skew_misses(self, tmp_path):
        base = self._verify_fp(tmp_path)
        assert base != self._verify_fp(tmp_path, temperature=0.7)
        assert base != self._verify_fp(tmp_path, top_k=8)
        assert base != self._verify_fp(tmp_path, top_p=0.9)


# --------------------------------------------------------------------------- #
# jax's own persistent compilation cache: where enable_jax_cache puts it
# --------------------------------------------------------------------------- #

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture
def jax_cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield was
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_helper_sets_no_directory_when_the_variable_is_set(
        monkeypatch, tmp_path, jax_cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_jax_cache() is None
    assert jax.config.jax_compilation_cache_dir == jax_cache_dir_restored


def test_cache_helper_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, jax_cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert enable_jax_cache() == want == enable_jax_cache()
    assert jax.config.jax_compilation_cache_dir == want
