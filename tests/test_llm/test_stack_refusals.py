"""What each kind of stack refuses (``model.CANNOT``), at the call sites
that read the table: the five tiny stacks of the stack test files and a
dense one, each asked for every feature. A refusal raises its feature's
exception naming the kind that answers and the table's reason, before
anything compiles; where the table lists nothing the call gets past the
refusal to what it does next."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec

from agilerl_tpu import observability
from agilerl_tpu.algorithms.grpo import GRPO
from agilerl_tpu.llm import model as M
from agilerl_tpu.llm.serving import BucketedGenerator, ContinuousGenerator
from agilerl_tpu.parallel import plan as PL
from tests.test_llm import (
    test_cca_moe_stack, test_hybrid_stack, test_mla_moe_stack,
    test_nemotron_h_stack, test_swa_moe_stack)

#: a stack -> its configuration
STACKS = {
    "dense": M.GPTConfig(vocab_size=64, n_layer=2, n_head=2, d_model=16,
                         max_seq_len=64, dtype=jnp.float32),
    "window": test_swa_moe_stack.CFG,
    "cca": test_cca_moe_stack.CFG,
    "latent": test_mla_moe_stack.CFG,
    "hybrid": test_hybrid_stack.CFG,
    "mamba2": test_nemotron_h_stack.CFG,
}

#: a stack -> the kinds the table sees in it, in the order they answer
KINDS = {
    "dense": (), "window": ("window", "dropless"),
    "cca": ("cca", "dropless"), "latent": ("latent", "dropless"),
    "hybrid": ("hybrid",), "mamba2": ("mamba2", "dropless"),
}

#: a feature -> the exception its refusal raises
ERRORS = {
    "speculate": ValueError, "mesh": ValueError, "serving_plan": ValueError,
    "learn_mesh": ValueError, "fleet": NotImplementedError,
    "prefill_transfer": NotImplementedError,
    "sequence_parallel": NotImplementedError,
}

#: a call -> the features it asks the table for, in its order
CALLS = {
    "speculate": ("speculate",),
    "serving_plan": ("mesh", "serving_plan"),
    "bucketed_plan": ("mesh",),
    "fleet": ("fleet", "prefill_transfer"),
    "submit_prefilled": ("prefill_transfer",),
    "sequence_parallel": ("sequence_parallel",),
    "to_mesh": ("mesh", "learn_mesh"),
    # a plan whose 'params' rules place every leaf
    "to_mesh_named": ("mesh", "learn_mesh"),
}

#: (stack, call) -> the (kind, feature) that refuses it; every pair not
#: here gets past the refusal
REFUSES = {
    **{(kind, call): (kind, feature)
       for kind in ("cca", "latent", "hybrid", "mamba2")
       for call, feature in (
           ("speculate", "speculate"), ("serving_plan", "serving_plan"),
           ("fleet", "prefill_transfer"),
           ("submit_prefilled", "prefill_transfer"),
           ("sequence_parallel", "sequence_parallel"),
           ("to_mesh", "learn_mesh"))},
    ("window", "speculate"): ("window", "speculate"),
    ("window", "serving_plan"): ("window", "mesh"),
    ("window", "bucketed_plan"): ("window", "mesh"),
    ("window", "fleet"): ("window", "fleet"),
    ("window", "sequence_parallel"): ("window", "sequence_parallel"),
    ("window", "to_mesh"): ("window", "mesh"),
    ("window", "to_mesh_named"): ("window", "mesh"),
    ("mamba2", "sequence_parallel"): ("dropless", "sequence_parallel"),
    ("mamba2", "to_mesh_named"): ("mamba2", "learn_mesh"),
}

#: (stack, call) -> what the refusal's message names, as the stack's own
#: test file held it
NAMES = {
    **{("window", c): "sliding-window layers" for c in (
        "speculate", "serving_plan", "bucketed_plan", "fleet",
        "sequence_parallel", "to_mesh", "to_mesh_named")},
    ("cca", "speculate"): "rolling state",
    ("cca", "serving_plan"): "rolling-state cache",
    ("cca", "fleet"): "rolling state",
    ("cca", "submit_prefilled"): "rolling state",
    ("cca", "sequence_parallel"): "rolling state",
    ("cca", "to_mesh"): "conv0_w",
    ("latent", "speculate"): "paged_verify_step",
    ("latent", "serving_plan"): "latent cache's pool",
    ("latent", "fleet"): "attach_rollout_fleet",
    ("latent", "submit_prefilled"): "submit_prefilled",
    ("latent", "sequence_parallel"): "sequence_parallel_axis",
    ("latent", "to_mesh"): "wkv_a, wkv_b",
    ("hybrid", "speculate"): "recurrent-state rollback",
    ("hybrid", "fleet"): "attach_rollout_fleet",
    ("hybrid", "submit_prefilled"): "submit_prefilled",
    ("hybrid", "sequence_parallel"): "sequence_parallel_axis",
    ("hybrid", "to_mesh"): "state-space layers' leaves",
    ("mamba2", "speculate"): "speculative decoding over a Mamba-2",
    ("mamba2", "fleet"): "over a Mamba-2 stack",
    ("mamba2", "submit_prefilled"): "over a Mamba-2 stack",
    ("mamba2", "to_mesh"): "to_mesh over a Mamba-2 stack",
}

#: a call -> what it raises past the refusal where nothing refuses
PAST = {
    "submit_prefilled": (ValueError, "ORIGINAL request key"),
    "sequence_parallel": (RuntimeError, "requires to_mesh"),
}


@functools.lru_cache(maxsize=None)
def base(stack):
    """Zeros of the stack's weights: no refusal reads a value."""
    cfg = STACKS[stack]
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(lambda s: np.zeros(s.shape, s.dtype),
                                  shapes)


def mesh():
    return Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "fsdp", "tp"))


def agent(stack, **kw):
    return GRPO(config=STACKS[stack], base_params=base(stack),
                pad_token_id=0, eos_token_id=1, group_size=2, batch_size=2,
                max_output_tokens=8, lora_rank=2, lora_targets=("wq",),
                seed=0, **kw)


def generator(stack, **kw):
    return ContinuousGenerator(
        STACKS[stack], max_new_tokens=12, prompt_buckets=(16, 32),
        slots=3, block_size=8, decode_chunk=4,
        metrics=observability.MetricsRegistry(), **kw)


def named_plan(at):
    plan = PL.grpo_plan_for_mesh(at)
    return dataclasses.replace(
        plan, rules={**plan.rules, "params": [(r".*", PartitionSpec())]})


def call(stack, name):
    """The call site ``name`` over ``stack``, as a user reaches it."""
    if name == "speculate":
        return generator(stack, speculate=True)
    if name == "serving_plan":
        return generator(stack, mesh=mesh())
    if name == "bucketed_plan":
        return BucketedGenerator(STACKS[stack], mesh=mesh())
    if name == "fleet":
        return agent(stack).attach_rollout_fleet(None)
    if name == "submit_prefilled":
        return generator(stack).submit_prefilled(
            np.arange(3, 9, dtype=np.int32), k_prompt=None, v_prompt=None,
            tok0=0, done0=False, key_next=None)
    if name == "sequence_parallel":
        ids = jnp.ones((2, 8), jnp.int32)
        return agent(stack, sequence_parallel_axis="sp")._resolve_learn_fns(
            ids, ids)
    if name == "to_mesh":
        return agent(stack).to_mesh(mesh())
    at = mesh()
    return agent(stack).to_mesh(at, plan=named_plan(at))


@pytest.mark.parametrize("stack", STACKS)
def test_the_table_sees_each_stack_as_its_kinds(stack):
    assert M.stack_kinds(STACKS[stack]) == KINDS[stack]


@pytest.mark.parametrize("name", CALLS)
@pytest.mark.parametrize("stack", STACKS)
def test_a_call_refuses_what_the_table_lists_and_nothing_else(stack, name):
    refused = REFUSES.get((stack, name))
    if refused is None:
        # the table lists none of the call's features for the stack's
        # kinds, or a plan check that the plan at hand passes
        listed = [M.CANNOT[k][f] for k in KINDS[stack] for f in CALLS[name]
                  if f in M.CANNOT[k]]
        assert all(isinstance(why, M.Unplaced) for why in listed)
        error, match = PAST.get(name, (None, None))
        with (pytest.raises(error, match=match) if error
              else contextlib.nullcontext()):
            call(stack, name)
        return
    kind, feature = refused
    assert feature in CALLS[name]
    why = M.CANNOT[kind][feature]
    with pytest.raises(ERRORS[feature]) as err:
        call(stack, name)
    assert type(err.value) is ERRORS[feature]
    message = str(err.value)
    assert NAMES.get((stack, name), "") in message
    assert message.startswith(
        f"{M.FEATURES[feature][1]} over {M.STACK_KINDS[kind][0]}: ")
    if isinstance(why, M.Unplaced):
        assert why.leaves in message and "no partition rule" in message
        assert isinstance(err.value.__cause__, PL.UnmatchedLeafError)
    else:
        assert message.endswith(f"{why}; not implemented")
