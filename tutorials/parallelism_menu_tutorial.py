"""Tutorial 4 — The full parallelism menu on one virtual pod: fsdp/tp for a
dense GPT, ep for a Mixture-of-Experts, pp for a GPipe pipeline, all on an
8-device CPU mesh (the same code runs unchanged on a TPU pod slice).

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python tutorials/parallelism_menu_tutorial.py
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
_os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = _os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    _os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from agilerl_tpu.llm import model as M
from agilerl_tpu.parallel.mesh import make_mesh
from agilerl_tpu.parallel.pipeline import pipeline_apply
from agilerl_tpu.parallel.plan import grpo_plan_for_mesh, make_grpo_plan

devices = jax.devices()[:8]
print(f"devices: {len(devices)} x {devices[0].platform}")

tokens = jnp.asarray(np.random.default_rng(0).integers(1, 250, size=(8, 32)), jnp.int32)
targets = jnp.roll(tokens, -1, axis=1)


def ce_loss(cfg, params, aux_weight=0.0):
    if aux_weight:
        logits, _, aux = M.apply(cfg, params, tokens, return_aux=True)
    else:
        (logits, _), aux = M.apply(cfg, params, tokens), 0.0
    lp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(lp, targets[..., None], -1).mean() + aux_weight * aux


# -- 1. Dense GPT on an fsdp x tp mesh (ZeRO + megatron-style TP) ----------- #
mesh = make_mesh(dp=1, fsdp=4, tp=2, devices=devices)
cfg = M.GPTConfig(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                  max_seq_len=32, dtype=jnp.float32)
params = M.init_params(jax.random.PRNGKey(0), cfg)
# declarative: the built-in GRPO rule set resolved for this mesh (regex
# rules -> PartitionSpecs; axes the mesh lacks degrade to replication)
params = grpo_plan_for_mesh(mesh).place("params", params, mesh)
with mesh:
    loss, grads = jax.jit(jax.value_and_grad(lambda p: ce_loss(cfg, p)))(params)
print(f"1. fsdp=4 x tp=2 dense GPT: loss {float(loss):.4f} (grads sharded like params)")

# -- 2. MoE GPT with experts sharded on ep ---------------------------------- #
ep_mesh = make_mesh(dp=1, fsdp=1, tp=1, ep=8, devices=devices)
moe_cfg = M.GPTConfig(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                      max_seq_len=32, dtype=jnp.float32,
                      n_experts=8, expert_top_k=2)
moe_params = M.init_params(jax.random.PRNGKey(1), moe_cfg)
moe_params = make_grpo_plan(ep=8).place("params", moe_params, ep_mesh)
with ep_mesh:
    moe_loss = jax.jit(lambda p: ce_loss(moe_cfg, p, aux_weight=moe_cfg.router_aux_weight))(moe_params)
print(f"2. ep=8 MoE GPT (8 experts, top-2): loss+aux {float(moe_loss):.4f} "
      "(GSPMD inserts the all-to-all pair per layer)")

# -- 3. GPipe pipeline over pp ---------------------------------------------- #
pp_mesh = Mesh(np.asarray(devices), axis_names=("pp",))
pp_cfg = M.GPTConfig(vocab_size=256, n_layer=8, n_head=4, d_model=64,
                     max_seq_len=32, dtype=jnp.float32)
pp_params = M.init_params(jax.random.PRNGKey(2), pp_cfg)
logits = pipeline_apply(pp_cfg, pp_params, tokens, pp_mesh, num_microbatches=4)
print(f"3. pp=8 GPipe (8 stages x 1 layer, 4 microbatches): logits {logits.shape}, "
      f"finite={bool(jnp.isfinite(logits).all())}")

print("done — the same specs scale to real ICI meshes by swapping the device list")
