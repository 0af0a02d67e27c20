"""On-policy rollout storage with GAE (parity: agilerl/components/rollout_buffer.py
— RolloutBuffer:26, compute_returns_and_advantages:413 (GAE), flat tensor batches
get_tensor_batch:525, BPTT sequence batches prepare_sequence_tensors:722 /
get_minibatch_sequences:845, incl. recurrent hidden-state storage).

TPU-first: storage is a [T, N, ...] pytree pre-allocated on device; per-step
writes are jitted index updates; GAE is one lax.scan over reversed time; flat
and BPTT-sequence minibatching are jitted gathers over permuted indices.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from agilerl_tpu.utils.rng import derive_key

PyTree = Any


class RolloutState(NamedTuple):
    data: Dict[str, PyTree]  # each leaf [T, N, ...]
    t: jax.Array  # int32 step cursor
    advantages: jax.Array  # [T, N]
    returns: jax.Array  # [T, N]


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_step(state: RolloutState, step: Dict[str, PyTree]) -> RolloutState:
    def write(buf, x):
        return buf.at[state.t].set(jnp.asarray(x).astype(buf.dtype))

    data = dict(state.data)
    for k, v in step.items():
        data[k] = jax.tree_util.tree_map(write, data[k], v)
    return state._replace(data=data, t=state.t + 1)


@functools.partial(jax.jit, static_argnames=("gamma", "gae_lambda"))
def _compute_gae(
    rewards: jax.Array,  # [T, N]
    values: jax.Array,  # [T, N]
    dones: jax.Array,  # [T, N] done AFTER step t (the step's own terminal flag)
    last_value: jax.Array,  # [N] V(s_T) — value of the obs after the last step
    last_done: jax.Array,  # [N] unused (kept for API compat; dones[T-1] already
    # carries the final step's terminal flag under this storage convention)
    gamma: float,
    gae_lambda: float,
) -> Tuple[jax.Array, jax.Array]:
    """GAE via reverse lax.scan (parity: rollout_buffer.py:413).

    Storage convention: dones[t] = 1 iff the episode ended AT step t (the env
    autoresets, so obs[t+1] belongs to the next episode). Hence step t's own
    done masks BOTH its bootstrap and the advantage carried from t+1:
        delta_t = r_t + gamma * V(s_{t+1}) * (1 - done_t) - V(s_t)
        A_t     = delta_t + gamma * lambda * (1 - done_t) * A_{t+1}
    (The CleanRL form indexes dones[t+1] because it stores reset flags; using
    it with per-step terminal flags leaks values across episode boundaries.)"""

    def step(carry, xs):
        gae, next_value = carry
        reward, value, done = xs
        nonterminal = 1.0 - done
        delta = reward + gamma * next_value * nonterminal - value
        gae = delta + gamma * gae_lambda * nonterminal * gae
        return (gae, value), gae

    init = (jnp.zeros_like(last_value), last_value)
    _, adv_rev = jax.lax.scan(
        step, init, (rewards[::-1], values[::-1], dones[::-1])
    )
    advantages = adv_rev[::-1]
    returns = advantages + values
    return advantages, returns


@jax.jit
def _flat_gather(data: PyTree, idx: jax.Array) -> PyTree:
    """Gather flattened [T*N, ...] minibatch by flat indices."""

    def g(buf):
        flat = buf.reshape((-1,) + buf.shape[2:])
        return flat[idx]

    return jax.tree_util.tree_map(g, data)


# A row of at most this many elements rides the shuffle's sort, a column an
# element; a wider one is gathered by the index column the same sort carries.
# On a TPU v5e a gather pays ~15 ns an index whatever the row holds, and two
# rounds of the sort move a further column for 1.1-1.4 ns a row, so the two
# cross near 12 columns (PERF.md section 6, PR 26).
MAX_SORT_CARRIED_ROW = 8


def shuffled_minibatches(
    key: jax.Array, flat: PyTree, num_minibatches: int, minibatch_size: int
) -> PyTree:
    """One epoch's minibatches: every ``[total, ...]`` leaf of ``flat`` as
    ``[num_minibatches, minibatch_size, ...]``, rows in the order of
    ``jax.random.permutation(key, total)``, the rows left over dropped. Equals
    ``x[perm[:num_minibatches * minibatch_size]].reshape(...)`` leaf for leaf,
    bit for bit, but moves each narrow row once, as payload of the sorts that
    make the permutation, instead of once a leaf by a gather. Traces under
    ``vmap`` (one key a member) and ``shard_map``."""
    leaves, treedef = jax.tree_util.tree_flatten(flat)
    total = leaves[0].shape[0]
    widths = [math.prod(x.shape[1:]) for x in leaves]
    carried = [w <= MAX_SORT_CARRIED_ROW for w in widths]
    columns = [x.reshape(total, w)[:, j]
               for x, w, c in zip(leaves, widths, carried) if c for j in range(w)]
    if not all(carried):
        columns.append(jnp.arange(total))
    # jax.random.permutation's own rounds (jax._src.random._shuffle): a stable
    # sort by fresh 32-bit keys, repeated until ties are improbable
    rounds = math.ceil(3 * math.log(max(1, total)) / math.log(np.iinfo(np.uint32).max))
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        bits = jax.random.bits(sub, (total,), jnp.uint32)
        _, *columns = jax.lax.sort((bits, *columns), num_keys=1, is_stable=True)
    columns = [c[: num_minibatches * minibatch_size] for c in columns]

    out, at = [], 0
    for x, w, c in zip(leaves, widths, carried):
        if c:
            rows = jnp.stack(columns[at:at + w], axis=1)
            at += w
        else:
            rows = x[columns[-1]]
        out.append(rows.reshape((num_minibatches, minibatch_size) + x.shape[1:]))
    return jax.tree_util.tree_unflatten(treedef, out)


class RolloutBuffer:
    """Fixed-horizon rollout buffer over N vectorised envs."""

    def __init__(
        self,
        capacity: int,
        num_envs: int,
        gamma: float = 0.99,
        gae_lambda: float = 0.95,
        recurrent: bool = False,
    ):
        self.capacity = int(capacity)
        self.num_envs = int(num_envs)
        self.gamma = float(gamma)
        self.gae_lambda = float(gae_lambda)
        self.recurrent = recurrent
        self.state: Optional[RolloutState] = None
        self._key = derive_key()

    @property
    def full(self) -> bool:
        return self.state is not None and int(self.state.t) >= self.capacity

    def reset(self) -> None:
        if self.state is not None:
            self.state = self.state._replace(t=jnp.zeros((), jnp.int32))

    #: backfill value per key when that key first appears AFTER the schema
    #: was frozen (producers override — e.g. action_mask backfills with 1
    #: because unmasked sampling ≡ all-ones mask)
    backfill_fills = {"action_mask": 1}

    def add(self, **step: PyTree) -> None:
        """step keys: obs, action, reward, done, value, log_prob
        (+ hidden_state pytree when recurrent)."""

        def alloc(x, fill=0):
            x = jnp.asarray(x)
            return jnp.full((self.capacity,) + x.shape, fill, x.dtype)

        if self.state is None:
            data = {k: jax.tree_util.tree_map(alloc, v) for k, v in step.items()}
            self.state = RolloutState(
                data=data,
                t=jnp.zeros((), jnp.int32),
                advantages=jnp.zeros((self.capacity, self.num_envs)),
                returns=jnp.zeros((self.capacity, self.num_envs)),
            )
        elif any(k not in self.state.data for k in step):
            # schema grew after the first add (e.g. an env that only publishes
            # action_mask on step infos, latched mid-rollout): allocate the
            # new key, backfilling prior rows per backfill_fills
            data = dict(self.state.data)
            for k, v in step.items():
                if k not in data:
                    fill = self.backfill_fills.get(k, 0)
                    data[k] = jax.tree_util.tree_map(
                        lambda x, _f=fill: alloc(x, _f), v
                    )
            self.state = self.state._replace(data=data)
        self.state = _write_step(self.state, step)

    def compute_returns_and_advantages(
        self, last_value: jax.Array, last_done: jax.Array
    ) -> None:
        s = self.state
        adv, ret = _compute_gae(
            s.data["reward"].astype(jnp.float32),
            s.data["value"].astype(jnp.float32),
            s.data["done"].astype(jnp.float32),
            jnp.asarray(last_value, jnp.float32),
            jnp.asarray(last_done, jnp.float32),
            self.gamma,
            self.gae_lambda,
        )
        self.state = s._replace(advantages=adv, returns=ret)

    # -- flat minibatches (parity: get_tensor_batch:525) ----------------- #
    def minibatch_indices(
        self, batch_size: int, key: Optional[jax.Array] = None
    ) -> np.ndarray:
        total = self.capacity * self.num_envs
        if key is None:
            self._key, key = jax.random.split(self._key)
        perm = jax.random.permutation(key, total)
        n_batches = max(total // batch_size, 1)
        return np.asarray(perm[: n_batches * batch_size]).reshape(n_batches, batch_size)

    def get_batch(self, idx: jax.Array) -> Dict[str, PyTree]:
        s = self.state
        data = dict(s.data)
        data["advantages"] = s.advantages
        data["returns"] = s.returns
        return _flat_gather(data, jnp.asarray(idx))

    def get_all_flat(self) -> Dict[str, PyTree]:
        s = self.state
        data = dict(s.data)
        data["advantages"] = s.advantages
        data["returns"] = s.returns
        return jax.tree_util.tree_map(
            lambda buf: buf.reshape((-1,) + buf.shape[2:]), data
        )

    # -- BPTT sequence minibatches (parity: get_minibatch_sequences:845) -- #
    def get_sequences(
        self, seq_len: int, key: Optional[jax.Array] = None
    ) -> Dict[str, PyTree]:
        """Chop [T, N] into [num_seqs, seq_len, ...] sequences (time-major
        within each sequence) including the hidden state at each sequence
        start, for truncated-BPTT recurrent PPO."""
        assert self.capacity % seq_len == 0, "capacity must divide by seq_len"
        s = self.state
        n_chunks = self.capacity // seq_len

        def chop(buf):
            # [T, N, ...] -> [n_chunks, seq_len, N, ...] -> [n_chunks*N, seq_len, ...]
            x = buf.reshape((n_chunks, seq_len) + buf.shape[1:])
            x = jnp.moveaxis(x, 2, 1)  # [n_chunks, N, seq_len, ...]
            return x.reshape((n_chunks * self.num_envs, seq_len) + buf.shape[2:])

        data = dict(s.data)
        data["advantages"] = s.advantages
        data["returns"] = s.returns
        seqs = {}
        for k, v in data.items():
            if k == "hidden_state":
                # keep only the hidden state at each sequence start:
                # leaf [T, L, N, H] -> [n_chunks, N, L, H] -> [n_chunks*N, L, H]
                def chop_hidden(buf):
                    x = buf[::seq_len]
                    x = jnp.moveaxis(x, 2, 1)
                    return x.reshape((n_chunks * self.num_envs,) + x.shape[2:])

                seqs[k] = jax.tree_util.tree_map(chop_hidden, v)
            else:
                seqs[k] = jax.tree_util.tree_map(chop, v)
        return seqs
