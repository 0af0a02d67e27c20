"""The chip-sized programs of the cells, compiled by the TPU's own compiler
for a described ``v5e:2x2`` (no chip attached): the fsdp-4 learn step of
``qwen2-7b-fsdp4`` and the generation program of ``evoppo-cartpole-p64``,
each at the size its configuration file states. This is how the depth, the
rows and the environments a member were chosen; the numbers are bytes the
compiler plans, not a chip run. Nothing runs.

The topology is described inside a fixture, never at import. Run with
``-s`` to see the bytes. Skipped where libtpu cannot describe the topology.
"""

import dataclasses
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

ROOT = Path(__file__).resolve().parents[2]
V5E_BYTES_LIMIT = 16.91e9  # memory_stats()["bytes_limit"] of a v5e chip (PR 21)
KERNEL = "tpu_custom_call"


def load(name, kind="configs"):
    return json.loads((ROOT / "perfbench" / kind / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2, with JAX's persistent compilation cache off: an
    entry written for a described device cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def planned_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def compile_mesh_learn_step(topo, config, mix, depth=None, rows=None):
    """``make_update_fn`` as ``GRPO._update_fn`` builds it on the chip
    (kernels on), lowered from shapes placed by the plan ``GRPO.to_mesh``
    resolves, for the four described chips."""
    import numpy as np

    from agilerl_tpu.algorithms.grpo import make_update_fn
    from agilerl_tpu.llm import model as M
    from agilerl_tpu.ops.kernel_mode import native_kernels
    from agilerl_tpu.parallel import plan as PL
    from perfbench import traffic
    from perfbench.runners import _llm

    cfg = _llm.gpt_config(config)
    if depth is not None:
        cfg = dataclasses.replace(cfg, n_layer=depth)
    rows = rows or int(mix["rows"])
    seq = int(mix["prompt_tokens"][1]) + int(mix["new_tokens"])
    shape = config["mesh"]
    mesh = Mesh(np.asarray(topo.devices).reshape(
        shape["dp"], shape["fsdp"], shape["tp"]), ("dp", "fsdp", "tp"))
    plan = PL.grpo_plan_for_mesh(mesh)
    base = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    agent = _llm.make_agent(
        cfg, base, 0, config, traffic.IdTokenizer(),
        group_size=int(mix["group_size"]), rows=rows,
        new_tokens=int(mix["new_tokens"]))
    everywhere = NamedSharding(mesh, P())
    s = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=everywhere)
    f32 = jnp.float32
    batch = {"tokens": s((rows, seq), jnp.int32), "mask": s((rows, seq), jnp.int32),
             "loss_mask": s((rows, seq - 1), f32), "old_lp": s((rows, seq - 1), f32),
             "ref_lp": s((rows, seq - 1), f32), "advantage": s((rows,), f32)}
    with native_kernels(), mesh:
        update = make_update_fn(cfg, agent.optimizer.tx, agent.lora_scale,
                                use_flash=True)
        return update.lower(
            plan.abstract("params", base, mesh),
            plan.abstract("lora", agent.actor.params, mesh),
            plan.abstract("optimizer", agent.optimizer.opt_state, mesh),
            batch, s((), f32), s((), f32)).compile()


def compile_generation(topo, config, num_envs=None):
    from perfbench.runners import evo_generation

    if num_envs is not None:
        config = dict(config, NUM_ENVS=num_envs)
    evo = evo_generation.make_evo(config)
    one_chip = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    size = int(config["POP_SIZE"])
    pop = jax.eval_shape(lambda k: evo.init_population(k, size),
                         jax.random.PRNGKey(0))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return evo.make_vmap_generation().lower(on(pop), on(key)).compile()


def test_fsdp4_learn_step_fits_a_chip_at_the_stated_depth_and_rows(topo):
    config, mix = load("qwen2-7b-fsdp4"), load("learn_only_1024", "traffic")
    compiled = compile_mesh_learn_step(topo, config, mix)
    per_chip = planned_bytes(compiled)
    text = compiled.as_text()
    print(f"\nfsdp4 learn step, depth {config['num_hidden_layers']}, rows "
          f"{mix['rows']}: {per_chip / 1e9:.2f} GB planned per chip")
    assert per_chip < V5E_BYTES_LIMIT
    assert text.count(KERNEL) >= 5  # flash fwd/dQ/dKV, fused loss fwd/dH
    assert "all-gather" in text  # the base is gathered, not replicated


@pytest.mark.slow  # the generation program takes minutes to compile
def test_evoppo_generation_fits_a_chip_at_the_stated_size(topo):
    config = load("evoppo-cartpole-p64")
    per_chip = planned_bytes(compile_generation(topo, config))
    print(f"\nevoppo generation, pop {config['POP_SIZE']} x "
          f"{config['NUM_ENVS']} envs: {per_chip / 1e9:.2f} GB planned")
    assert 0.25 * 16e9 < per_chip < 0.75 * V5E_BYTES_LIMIT
