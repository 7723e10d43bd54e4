"""A configuration's weights, routing tables and neuron constants, made
from the seed on the device in one jitted call.

The arrays are the benchmark's, not the program's: the program under test
and the plain reference are both handed the same ones.  The configuration
file's ``connectivity`` group says how they are drawn:

* crossbar weights ``w [chips, inputs, neurons]``: ``weight_scale`` times a
  standard normal;
* routing table ``[chips, neurons, fanout]``: destination chip uniform
  over the chips, destination input row uniform over the rows, delay
  uniform in ``[min_delay, max_delay]``, every entry enabled;
* neuron constants: the ``neuron`` group, one value for every circuit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Streams folded into the seed's key, one per use.
DATA_STREAM, DRIVE_STREAM, VOLLEY_STREAM = 0, 1, 2

INT_NEURON_KEYS = ("refrac",)


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (wider than 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed {seed} must be >= 0")
    lo, hi = seed & 0xFFFFFFFF, seed >> 32
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi & 0xFFFFFFFF)


def make(config: dict, seed: int) -> dict:
    """``{"w", "table": {...}, "neuron": {...}}`` on the default device."""
    comm, conn, neuron = config["comm"], config["connectivity"], config["neuron"]
    c, n, i, k = (comm["n_chips"], comm["neurons_per_chip"],
                  comm["n_inputs_per_chip"], comm["fanout"])

    @jax.jit
    def build(key):
        k_w, k_chip, k_addr, k_delay = jax.random.split(key, 4)
        w = conn["weight_scale"] * jax.random.normal(k_w, (c, i, n), jnp.float32)
        shape = (c, n, k)
        table = {
            "dest_chip": jax.random.randint(k_chip, shape, 0, c, jnp.int32),
            "dest_addr": jax.random.randint(k_addr, shape, 0, i, jnp.int32),
            "delay": jax.random.randint(k_delay, shape, conn["min_delay"],
                                        conn["max_delay"] + 1, jnp.int32),
            "valid": jnp.ones(shape, bool),
        }
        consts = {
            name: jnp.full((c, n), value,
                           jnp.int32 if name in INT_NEURON_KEYS else jnp.float32)
            for name, value in neuron.items()}
        return {"w": w, "table": table, "neuron": consts}

    return build(jax.random.fold_in(seed_key(seed), DATA_STREAM))
