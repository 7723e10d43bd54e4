"""Pallas TPU megakernel: the whole superstep drain path in one launch.

One single-program ``pallas_call`` (no grid) keeps the delay ring, the
merge queue and the delivered word slab VMEM-resident across all B
substeps and runs, per substep k against clock ``t0 + k``:

  1. the merge stage — ``sort`` mode time-orders substep k's words with
     the same bitonic network the standalone ``merge_sort`` kernel uses
     (``merge_sort.kernel.bitonic_sort``, (key, idx, word) lanes ==
     stable); ``rate`` mode additionally threads the bounded queue
     through the network (concat queue + arrivals + rate sentinels, sort,
     emit the first ``rate`` lanes, keep the [rate, rate+depth) window —
     exactly ``repro.core.merge.merge_split``);
  2. the ring deposit — the shared ``deposit_judgment`` of
     ``repro.core.delays`` evaluated on the emitted row, realized
     scatter-free as an outer-product MXU matmul of the slot one-hot
     against the column one-hot (``ring[d, n] += Σ_e sl[d,e]·co[n,e]``);
  3. per-substep accounting (deposit expiries, merge congestion drops).

The unfused chain re-reads the ring and queue from HBM once per substep;
here both stay in VMEM for the whole block and the ring is written back
once.  ``gate`` (a (1,1) scalar) reproduces the pipelined schedule's
empty-carry masking in-kernel: a gated-off block deposits nothing, emits
sentinels and leaves the queue untouched — no state revert needed outside.

All invalid lanes carry the single ``WORD_SENTINEL`` encoding, so the
sentinel padding ops.py adds is bitwise-invisible: padding sorts after
every real lane (key ties break on the lane index, and every invalid lane
holds the identical -1 word), and sentinel deposits are no-ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import events as ev
from repro.kernels.common import onehot_dot
from repro.kernels.merge_sort.kernel import bitonic_sort

_SENTINEL = ev.WORD_SENTINEL
_TIME_MASK = ev.WORD_TIME_MASK
_HALF = ev.TIME_MOD // 2

# Row layout of the [2, B] per-substep stats output.
STAT_ROWS = ("dep_expired", "dropped")


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _sort_row(row, now_k):
    """Stable ascending bitonic sort of a [1, n] word row by the wrap-aware
    deadline key relative to ``now_k`` (events.word_sort_key semantics);
    n must be a power of two >= 128."""
    key = jnp.where(row >= 0, (row - now_k + _HALF) & _TIME_MASK,
                    jnp.int32(ev.TIME_MOD))
    (out,) = bitonic_sort(key, row)
    return out


def _deposit(ring, row, now_k, min_ahead, depth, n_inputs):
    """deposit_judgment + scatter-free accumulate; returns (ring, expired)
    with expired as a (1, 1) int32."""
    word = row
    valid = word >= 0
    d8 = ((word & _TIME_MASK) - (now_k & _TIME_MASK)) & _TIME_MASK
    ahead = jnp.where(d8 >= _HALF, d8 - ev.TIME_MOD, d8)
    deliverable = valid & (ahead > min_ahead) & (ahead <= depth)
    expired = jnp.sum((valid & ~deliverable).astype(jnp.int32),
                      keepdims=True)
    slot = jnp.where(deliverable, (now_k + ahead) % depth, 0)
    col = jnp.where(deliverable,
                    jnp.clip(word >> ev.WORD_ADDR_SHIFT, 0, n_inputs - 1),
                    0)
    sl = ((_iota((depth, row.shape[1]), 0) == slot)
          & deliverable).astype(jnp.int32)
    co = (_iota((n_inputs, row.shape[1]), 0) == col).astype(jnp.int32)
    acc = onehot_dot(sl, co, (((1,), (1,)), ((), ())))
    return ring + acc, expired


def _kernel(
    delivered_ref, queue_ref, ring_ref, t0_ref, gate_ref,
    ring_out_ref, words_ref, queue_out_ref, stats_ref,
    *, mode, rate, extra_ahead, sort_n,
):
    b, lanes = delivered_ref.shape
    depth, n_inputs = ring_ref.shape
    qdepth = queue_ref.shape[1]
    t0 = t0_ref[0, 0]
    gate = gate_ref[0, 0] != 0
    ring = ring_ref[...]
    queue = queue_ref[...]
    delivered = jnp.where(gate, delivered_ref[...], _SENTINEL)

    for k in range(b):
        now_k = t0 + k
        min_ahead = extra_ahead + (b - 1) - k
        dropped = jnp.zeros((1, 1), jnp.int32)
        if mode == "rate":
            pad = jnp.full((1, sort_n - qdepth - lanes), _SENTINEL,
                           jnp.int32)
            cat = jnp.concatenate(
                [queue, delivered[k:k + 1, :], pad], axis=1)
            srt = _sort_row(cat, now_k)
            row = srt[:, :rate]
            n_valid = jnp.sum((srt >= 0).astype(jnp.int32), keepdims=True)
            emitted = jnp.minimum(n_valid, rate)
            dropped = jnp.maximum(n_valid - emitted - qdepth, 0)
            # A gated-off carry must not advance the queue (its sentinel
            # drain would still emit queued words).
            queue = jnp.where(gate, srt[:, rate:rate + qdepth], queue)
            row = jnp.where(gate, row, _SENTINEL)
            dropped = jnp.where(gate, dropped, 0)
        elif mode == "sort":
            row = _sort_row(delivered[k:k + 1, :], now_k)
        else:
            row = delivered[k:k + 1, :]
        ring, expired = _deposit(ring, row, now_k, min_ahead, depth,
                                 n_inputs)
        words_ref[k:k + 1, :] = row
        stats_ref[:, k:k + 1] = jnp.concatenate([expired, dropped], axis=0)

    ring_out_ref[...] = ring
    queue_out_ref[...] = queue


@functools.partial(jax.jit, static_argnames=(
    "mode", "rate", "extra_ahead", "interpret"))
def fused_drain_pallas(
    delivered,                # int32[B, Lp]
    queue,                    # int32[1, depth] ("rate" mode; dummy else)
    ring,                     # int32[D, n_inputs]
    t0,                       # int32[1, 1]
    gate,                     # int32[1, 1] (1 = live block)
    *,
    mode: str,
    rate: int,
    extra_ahead: int,
    interpret: bool = False,
):
    """Raw kernel invocation (inputs pre-padded by ops.py).

    In ``sort`` mode Lp must be a power of two >= 128; in ``rate`` mode
    the internal sort length ``depth + Lp + rate`` is padded up to the
    next power of two >= 128.  Returns ``(ring_out [D, n_inputs],
    words [B, R], queue_out [1, depth], stats [2, B])`` with R = rate in
    ``rate`` mode and Lp otherwise.
    """
    b, lanes = delivered.shape
    qdepth = queue.shape[1]
    sort_n = 0
    if mode == "rate":
        sort_n = 128
        while sort_n < qdepth + lanes + rate:
            sort_n *= 2
    elif mode == "sort":
        if lanes < 128 or lanes & (lanes - 1):
            raise ValueError(
                f"sort mode needs a power-of-two lane count >= 128, "
                f"got {lanes}")
    out_lanes = rate if mode == "rate" else lanes
    kernel = functools.partial(_kernel, mode=mode, rate=rate,
                               extra_ahead=extra_ahead, sort_n=sort_n)
    out_shape = (
        jax.ShapeDtypeStruct(ring.shape, jnp.int32),
        jax.ShapeDtypeStruct((b, out_lanes), jnp.int32),
        jax.ShapeDtypeStruct((1, qdepth), jnp.int32),
        jax.ShapeDtypeStruct((2, b), jnp.int32),
    )
    return pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret)(
        delivered, queue, ring.astype(jnp.int32), t0.astype(jnp.int32),
        gate.astype(jnp.int32))
