"""Tutorial 1 — Evolvable networks: configs, mutations, weight preservation.

The core idea (vs the reference's torch-module mutation): a module is a frozen
architecture config + a params pytree. A mutation is a pure config transition;
weights transfer slab-wise. Run: python tutorials/evolvable_networks_tutorial.py
"""

# allow running directly as `python <dir>/<script>.py` from a source checkout
import os as _os, sys as _sys  # noqa: E402
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from agilerl_tpu.modules import EvolvableMLP

mlp = EvolvableMLP(num_inputs=4, num_outputs=2, hidden_size=(64, 64),
                   key=jax.random.PRNGKey(0))
print("config:", mlp.config)
print("forward:", mlp(jnp.ones((1, 4))).shape)

# grow a layer: weights of existing layers are preserved exactly
w0 = mlp.params["layer_0"]["kernel"]
mlp.add_layer()
assert (mlp.params["layer_0"]["kernel"] == w0).all()
print("after add_layer:", mlp.config.hidden_size)

# node mutations keep the overlapping slab
info = mlp.add_node(hidden_layer=0, numb_new_nodes=32)
print("after add_node:", mlp.config.hidden_size, info)

# the HPO engine samples mutations like this:
import numpy as np
print("sampled mutation:", mlp.sample_mutation_method(rng=np.random.default_rng(0)))
