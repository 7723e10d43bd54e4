"""Pallas TPU kernel: bucket-buffer event aggregation (paper §3.1 hot path).

One grid program per bucket row.  The event stream (bucket ids, packed wire
words — see ``repro.core.events``) sits in VMEM as full blocks; each program

  1. builds its match mask  ``match = (bucket_id == b) & (word >= 0)``
     (validity is the word's sign: the all-ones sentinel is the reserved
     "no event" encoding),
  2. ranks matches with an exclusive prefix sum (a log-step scan of lane
     rotations, ``kernels.common.exclusive_cumsum``: Mosaic has no
     ``cumsum``),
  3. materializes its output row with a slot-selection contraction:
     ``row[c] = sum_e [slot[e] == c] * word[e]`` — a [C, E_tile]
     broadcast-compare contracted with the word row on the MXU
     (``kernels.common.onehot_dot``, exact for 22-bit words) without any
     per-element scatter (TPU has no fast random VMEM scatter; this is the
     hardware-adaptation of the FPGA FIFO insert),
  4. accumulates counts/overflow.

The outputs are whole-array blocks that stay resident across the
sequential grid; program ``b`` stores row ``b`` (a ``(1, C)`` block of a
``(n_buckets, C)`` array would break the TPU's (8, 128) block rule).

Packing the event into one word shrinks the kernel from three payload
accumulators (addr / deadline / valid) to a single int32 accumulator — a
third of the VMEM traffic and VPU reduce work of the SoA version.

The event stream is tiled along E so the [C, E_tile] compare window stays
small; the running per-bucket fill level carries across tiles in a loop
accumulator.  All tensors are kept >= 2-D inside the kernel (TPU vector
layout); E and C should be multiples of 128 for lane alignment (ops.py
pads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import exclusive_cumsum, onehot_dot

E_TILE = 512  # events per inner tile; [C, E_TILE] compare window in VMEM

_SENTINEL = -1  # events.WORD_SENTINEL (kept literal: kernel-local constant)


def _kernel(
    bucket_id_ref, word_ref,
    word_out_ref, count_ref, overflow_ref,
    *, capacity: int,
):
    b = pl.program_id(0)
    e_total = bucket_id_ref.shape[1]
    n_tiles = e_total // E_TILE

    slots_c = jax.lax.broadcasted_iota(jnp.int32, (capacity, E_TILE), 0)
    row_dims = (((1,), (1,)), ((), ()))              # [1, E] . [C, E]^T

    def tile_body(i, carry):
        base, acc_word, acc_hit = carry
        sl = (slice(0, 1), pl.ds(i * E_TILE, E_TILE))
        bid = bucket_id_ref[sl]                      # [1, E_TILE]
        word = word_ref[sl]
        match = jnp.logical_and(bid == b, word >= 0)  # [1, E_TILE]
        m32 = match.astype(jnp.int32)
        # exclusive rank within this bucket, offset by fill level so far
        slot = exclusive_cumsum(m32) + base          # [1, E_TILE]
        # slot-selection contraction: pick[c, e] = (slot[e] == c) & match[e]
        pick = jnp.logical_and(slot == slots_c, match).astype(jnp.int32)
        acc_word = acc_word + onehot_dot(word, pick, row_dims)   # [1, C]
        acc_hit = acc_hit + onehot_dot(m32, pick, row_dims)
        return base + jnp.sum(m32), acc_word, acc_hit

    zero_row = jnp.zeros((1, capacity), jnp.int32)
    n_match, acc_word, acc_hit = jax.lax.fori_loop(
        0, n_tiles, tile_body, (jnp.int32(0), zero_row, zero_row))
    row = pl.ds(b, 1)
    word_out_ref[row, :] = jnp.where(acc_hit > 0, acc_word, _SENTINEL)
    count_ref[row, :] = jnp.full((1, 1), n_match, jnp.int32)
    overflow_ref[row, :] = jnp.full((1, 1), jnp.maximum(n_match - capacity, 0),
                                    jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n_buckets", "capacity", "interpret")
)
def bucket_pack_pallas(
    bucket_id: jax.Array,
    words: jax.Array,
    *,
    n_buckets: int,
    capacity: int,
    interpret: bool = False,
):
    """Raw kernel invocation — inputs must be padded: E % E_TILE == 0.

    ``words`` are the packed wire words (negative = invalid lane).
    Returns (words[B,C], counts[B,1], overflow[B,1]).
    """
    e = bucket_id.shape[0]
    if e % E_TILE != 0:
        raise ValueError(f"E={e} must be padded to a multiple of {E_TILE}")
    kernel = functools.partial(_kernel, capacity=capacity)
    ev_spec = pl.BlockSpec((1, e), lambda b: (0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda b: (0, 0))
    out_shapes = (
        jax.ShapeDtypeStruct((n_buckets, capacity), jnp.int32),
        jax.ShapeDtypeStruct((n_buckets, 1), jnp.int32),
        jax.ShapeDtypeStruct((n_buckets, 1), jnp.int32),
    )
    as_row = lambda x: x.astype(jnp.int32).reshape(1, e)
    return pl.pallas_call(
        kernel,
        grid=(n_buckets,),
        in_specs=[ev_spec, ev_spec],
        out_specs=tuple(whole(o.shape) for o in out_shapes),
        out_shape=out_shapes,
        interpret=interpret,
    )(as_row(bucket_id), as_row(words))
