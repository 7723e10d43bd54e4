"""Transport layer: the Extoll/Tourmalet analogue on a TPU mesh.

Extoll routes packets between nodes of a 3D torus by 16-bit node address;
TPU ICI is likewise a torus, and the routed-exchange primitive is
``all_to_all`` (every chip sends one bucket slab to every other chip), while
a point-to-point RDMA *put* is ``ppermute``.  This module hides the
difference between:

* ``ShardMapTransport`` — real collectives over a named mesh axis, for use
  inside ``shard_map`` (this is what the dry-run lowers to ICI collectives);
* ``LocalTransport``   — the same dataflow on a single device with an
  explicit leading chip axis (exchange == transpose of the two chip axes),
  used by CPU tests and small examples.  Both are numerically identical,
  which is property-tested.

A hierarchical two-stage exchange (pod-local all_to_all, then cross-pod)
is provided for the multi-pod mesh — packets cross the slow inter-pod link
exactly once, pre-aggregated, mirroring Extoll's dimension-ordered torus
routing.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Protocol

import jax
import jax.numpy as jnp


class Transport(Protocol):
    n_chips: int

    def all_to_all(self, x: jax.Array) -> jax.Array: ...
    def put(self, x: jax.Array, perm: list[tuple[int, int]]) -> jax.Array: ...
    def psum(self, x: jax.Array) -> jax.Array: ...
    def chip_index(self) -> jax.Array: ...


@dataclasses.dataclass(frozen=True)
class ShardMapTransport:
    """Collectives over mesh axis(es) — call inside shard_map.

    ``axis`` may be a single axis name or a tuple (e.g. ("pod", "model")) —
    for tuples, all_to_all is performed hierarchically: innermost axis first
    (cheap pod-local links), then outer (expensive cross-pod), so cross-pod
    traffic is already aggregated.
    """

    axis: str | tuple[str, ...]
    n_chips: int

    def _axes(self) -> tuple[str, ...]:
        return (self.axis,) if isinstance(self.axis, str) else tuple(self.axis)

    def all_to_all(self, x: jax.Array) -> jax.Array:
        # x: [n_chips_local_view, ...] where leading dim == total chips on
        # the exchange axes.  Per-device in shard_map, leading dim is the
        # full n_chips (each device holds one slab per destination).
        return self._a2a(x, self._axes(), 0)

    def _a2a(self, x: jax.Array, axes: tuple[str, ...],
             axis: int) -> jax.Array:
        """One exchange stage per mesh axis, innermost first (cheap local
        links), outermost last (expensive cross-pod, pre-aggregated) —
        recursing so any tuple depth works (a 2-axis tuple reproduces the
        classic pod-local-then-cross-pod two-stage exchange)."""
        if len(axes) == 1:
            return jax.lax.all_to_all(
                x, axes[0], split_axis=axis, concat_axis=axis, tiled=True
            )
        # Split this stage's dim [P * Q, ...] -> [P, Q, ...] for axes
        # (outer, *inner): inner stages exchange each outer-block in place,
        # then the outer stage crosses with one aggregated slab per block.
        p = jax.lax.axis_size(axes[0])
        q = x.shape[axis] // p
        y = x.reshape(x.shape[:axis] + (p, q) + x.shape[axis + 1:])
        y = self._a2a(y, axes[1:], axis + 1)
        y = jax.lax.all_to_all(y, axes[0], split_axis=axis, concat_axis=axis,
                               tiled=True)
        return y.reshape(x.shape)

    def put(self, x: jax.Array, perm: list[tuple[int, int]]) -> jax.Array:
        axes = self._axes()
        if len(axes) != 1:
            raise ValueError("point-to-point put is single-axis")
        return jax.lax.ppermute(x, axes[0], perm)

    def psum(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self._axes())

    def chip_index(self) -> jax.Array:
        axes = self._axes()
        idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx


@dataclasses.dataclass(frozen=True)
class LocalTransport:
    """Single-device emulation with an explicit leading chip axis.

    Arrays are [n_chips, n_chips, ...]: (holder, destination_slab, ...).
    all_to_all == swap of the two leading axes.  Used by CPU tests; equality
    with ShardMapTransport is property-tested in tests/test_transport.py.
    """

    n_chips: int

    def all_to_all(self, x: jax.Array) -> jax.Array:
        return jnp.swapaxes(x, 0, 1)

    def put(self, x: jax.Array, perm: list[tuple[int, int]]) -> jax.Array:
        out = jnp.zeros_like(x)
        for src, dst in perm:
            out = out.at[dst].set(x[src])
        return out

    def psum(self, x: jax.Array) -> jax.Array:
        # Every chip sees the full cross-chip sum — same semantics as
        # ShardMapTransport.psum (each shard holds the reduced value).
        return jnp.broadcast_to(jnp.sum(x, axis=0, keepdims=True), x.shape)

    def chip_index(self) -> jax.Array:
        return jnp.arange(self.n_chips)


# ---------------------------------------------------------------------------
# Collective-cost estimators (used by the roofline harness)
# ---------------------------------------------------------------------------

def all_to_all_bytes(slab_bytes_per_pair: int, n_chips: int) -> int:
    """Bytes each chip injects for a full exchange (one slab per peer)."""
    return slab_bytes_per_pair * (n_chips - 1)


def ring_put_bytes(slab_bytes: int) -> int:
    return slab_bytes


@partial(jax.jit, static_argnames=("n_chips",))
def exchange_matrix(dest_chip: jax.Array, valid: jax.Array, n_chips: int):
    """Traffic matrix [n_chips] of event counts by destination — the
    per-step message-rate observable.

    A single scatter-add (O(E)) rather than the former [E, n_chips] one-hot
    reduction (O(E·n_chips)); out-of-range destinations are dropped, exactly
    as the one-hot comparison never matched them (regression-pinned against
    :func:`_exchange_matrix_onehot` in tests/test_transport.py).  Negative
    indices are pushed past n_chips first — scatter mode="drop" only drops
    after JAX's negative-index normalization, which would otherwise wrap
    them onto real chips.
    """
    dest = jnp.where(dest_chip < 0, n_chips, dest_chip)
    counts = jnp.zeros((n_chips,), jnp.int32)
    return counts.at[dest].add(valid.astype(jnp.int32), mode="drop")


def _exchange_matrix_onehot(dest_chip: jax.Array, valid: jax.Array,
                            n_chips: int):
    """Reference one-hot implementation, kept as the regression oracle."""
    onehot = (
        (dest_chip[:, None] == jnp.arange(n_chips)[None, :]) & valid[:, None]
    )
    return jnp.sum(onehot.astype(jnp.int32), axis=0)
