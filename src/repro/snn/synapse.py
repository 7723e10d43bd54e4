"""Synapse crossbar: the HICANN-X 256-row x 512-column synapse array.

Events delivered to a chip carry a 6-bit (here: configurable-width) *input
label* selecting a synapse row; all 512 neurons in that row's columns receive
the row's weight.  On BSS-2 this is an analog crossbar driven event-by-event;
on TPU we densify per time slot: the delay ring yields a per-step input
spike-count vector s[256] and the crossbar is the MXU matmul ``s @ W``.

Weights are 6-bit signed on the chip; :func:`quantize_weights` models that
precision (round-to-nearest with a per-row scale, straight-through gradient).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

WEIGHT_BITS = 6


class Crossbar(NamedTuple):
    """w : f32[n_inputs, n_neurons] signed synaptic weights."""

    w: jax.Array

    @property
    def n_inputs(self) -> int:
        return self.w.shape[0]

    @property
    def n_neurons(self) -> int:
        return self.w.shape[1]


def init_crossbar(
    key: jax.Array, n_inputs: int, n_neurons: int, *, scale: float = 0.3,
    sparsity: float = 0.0,
) -> Crossbar:
    w = scale * jax.random.normal(key, (n_inputs, n_neurons), jnp.float32)
    if sparsity > 0.0:
        mask = jax.random.uniform(jax.random.fold_in(key, 1),
                                  (n_inputs, n_neurons)) >= sparsity
        w = w * mask
    return Crossbar(w=w)


def currents(crossbar: Crossbar, input_spikes: jax.Array) -> jax.Array:
    """Dense delivery: spike counts [*, n_inputs] -> currents [*, n_neurons].

    ``HIGHEST`` keeps the matmul at f32 on a TPU, whose default precision
    is one bf16 pass, so a TPU run computes the f32 currents of a CPU run
    up to summation order."""
    return jnp.matmul(input_spikes.astype(crossbar.w.dtype), crossbar.w,
                      precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _ste_round(x):
    return jnp.round(x)


_ste_round.defvjp(lambda x: (jnp.round(x), None), lambda _, g: (g,))


def quantize_weights(crossbar: Crossbar, bits: int = WEIGHT_BITS) -> Crossbar:
    """Model the chip's signed fixed-point weight precision (per-row scale,
    straight-through estimator for gradients)."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(crossbar.w), axis=1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(_ste_round(crossbar.w / scale), -qmax - 1, qmax)
    return Crossbar(w=q * scale)
