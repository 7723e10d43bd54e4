"""Pallas TPU kernel: bitonic merge-sort of event lanes (merge stage).

The destination merge buffer must order the concatenated (queue + incoming
packet) lanes by deadline each cycle.  A bitonic sorting network is the
hardware-natural realization — fixed depth log2(L)*(log2(L)+1)/2 stages of
compare-exchange over lane pairs, each stage a handful of VPU select ops,
no data-dependent control flow.

Stability: a bitonic network is not stable on its own, so the comparator
orders lexicographically by ``(key, original lane index)`` — a total order
whose result is exactly the stable argsort permutation the jnp reference
(ref.py) produces, hence bit-exact equality.

Layout: the ``n`` lanes are laid out as an ``(n // 128, 128)`` tile
(one row when n == 128) — Mosaic cannot reshape a lane row into the
``[blocks, 2, j]`` pair view, but it rotates vectors along either axis.
At substage stride ``j`` the partner of flat index ``i`` is ``i ^ j``:
``j`` lanes away inside the row when ``j < 128``, else ``j / 128`` rows
away in the same lane.  Two rotations (``pltpu.roll``, which follows
``jnp.roll``) bring every lane's upper and lower candidate alongside it,
and a select keeps the partner — no gathers (TPU has no fast random VMEM
gather).  Sort direction is read off bit ``k`` of the flat index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INF = 2**30
_LANES = 128


def _compare_exchange(lanes, k: int, j: int):
    """One bitonic substage on the 2-D lane tuple (key, idx, *payloads)."""
    rows, cols = lanes[0].shape
    iota = lambda d: jax.lax.broadcasted_iota(jnp.int32, (rows, cols), d)
    pos = iota(0) * cols + iota(1)
    lower = (pos & j) == 0          # this lane is the pair's first element
    if j < cols:
        axis, shift, size = 1, j, cols
    else:
        axis, shift, size = 0, j // cols, rows

    def partner(x):
        return jnp.where(lower, pltpu.roll(x, size - shift, axis),
                         pltpu.roll(x, shift, axis))

    partners = tuple(partner(x) for x in lanes)
    (key, idx), (pkey, pidx) = lanes[:2], partners[:2]
    # (key, idx) is a strict total order (idx is unique).
    gt = (key > pkey) | ((key == pkey) & (idx > pidx))
    # Ascending iff bit log2(k) of the flat index is clear; the first
    # element of an ascending pair keeps the minimum, so ``flip`` marks
    # the lanes that keep the maximum (xor, not a select: Mosaic cannot
    # select between boolean vectors).
    flip = lower ^ ((pos & k) == 0)
    take = gt ^ flip
    return tuple(jnp.where(take, p, x) for x, p in zip(lanes, partners))


def bitonic_sort(key: jax.Array, *payloads: jax.Array) -> tuple:
    """Sort ``[1, n]`` rows (n a power of two >= 128) ascending by
    ``(key, original lane)``; returns the permuted payload rows."""
    n = key.shape[1]
    cols = min(n, _LANES)
    tile = lambda x: x.reshape(n // cols, cols)
    idx = jax.lax.broadcasted_iota(jnp.int32, (n // cols, cols), 0) * cols \
        + jax.lax.broadcasted_iota(jnp.int32, (n // cols, cols), 1)
    lanes = (tile(key), idx) + tuple(tile(p) for p in payloads)
    k = 2
    while k <= n:          # static network: unrolled at trace time
        j = k // 2
        while j >= 1:
            lanes = _compare_exchange(lanes, k, j)
            j //= 2
        k *= 2
    return tuple(x.reshape(1, n) for x in lanes[2:])


def _kernel_words(key_ref, word_ref, word_out):
    """Word-path kernel: sort packed wire words by a precomputed wrap-aware
    key (see events.word_sort_key), ties broken by original lane index.

    One payload lane instead of three — the sorting network exchanges
    (key, idx, word) tuples, 3 selects per substage vs. the SoA path's 5.
    """
    (word_out[...],) = bitonic_sort(key_ref[...], word_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sort_words_pallas(
    key: jax.Array,
    words: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Raw word-kernel invocation — L must be a power of two (ops.py pads).

    Returns words[L] sorted ascending by (key, original lane).
    """
    n = words.shape[0]
    if n & (n - 1):
        raise ValueError(f"L={n} must be a power of two")
    row_spec = pl.BlockSpec((1, n), lambda: (0, 0))
    as_row = lambda x: x.astype(jnp.int32).reshape(1, n)
    out = pl.pallas_call(
        _kernel_words,
        in_specs=[row_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(as_row(key), as_row(words))
    return out[0]


def _kernel(addr_ref, dead_ref, valid_ref, addr_out, dead_out, valid_out):
    valid = valid_ref[...]
    key = jnp.where(valid != 0, dead_ref[...], _INF)
    addr_out[...], dead_out[...], valid_out[...] = bitonic_sort(
        key, addr_ref[...], dead_ref[...], valid)


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sort_pallas(
    addr: jax.Array,
    deadline: jax.Array,
    valid: jax.Array,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Raw kernel invocation — L must be a power of two (ops.py pads).

    Returns (addr[L], deadline[L], valid_i32[L]) sorted ascending by
    (deadline-if-valid-else-INF, original lane).
    """
    n = addr.shape[0]
    if n & (n - 1):
        raise ValueError(f"L={n} must be a power of two")
    row_spec = pl.BlockSpec((1, n), lambda: (0, 0))
    out_shapes = tuple(
        jax.ShapeDtypeStruct((1, n), jnp.int32) for _ in range(3)
    )
    as_row = lambda x: x.astype(jnp.int32).reshape(1, n)
    a, d, v = pl.pallas_call(
        _kernel,
        in_specs=[row_spec, row_spec, row_spec],
        out_specs=(row_spec, row_spec, row_spec),
        out_shape=out_shapes,
        interpret=interpret,
    )(as_row(addr), as_row(deadline), as_row(valid))
    return a[0], d[0], v[0]
