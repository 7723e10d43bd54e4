"""Merge buffers: k-way time-ordered merging of packetized event streams.

At the destination, packets from multiple source streams must be merged back
into a single time-ordered event stream (paper §3.1; deferred in the paper's
scaled-down prototype — grayed out in its Fig. 2 — and implemented here as
the *full* mode).

The merge operates directly on the packed wire words
(``repro.core.events``): the 8-bit deadline lives in the low bits of every
word, so the sort key is derivable without decoding —
``events.word_sort_key(word, now)`` biases the wraparound difference to
``now`` into [0, 256), which is monotone in the true deadline under the
paper's aggregation-window contract (|deadline - now| < 128).  Invalid
lanes (the all-ones sentinel) key above every real event.  Stale words
(deadline already passed) key below every in-window arrival, so they drain
within ceil(depth / rate) steps; PulseCommConfig bounds ``merge_depth <=
128 * merge_rate`` so no queued word can age across the wrap and alias
onto a future deadline.

Three pieces:

* :func:`merge_words` — the functional k-way merge of a word slab:
  stable sort by (wrap key, lane).  On TPU a bitonic sort over a few
  thousand lanes is cheap and is exactly a merge network in hardware terms.
* :class:`MergeBuffer` / :func:`merge_step_words` — the *rate-limited* merge
  buffer that models congestion: per step it can emit at most ``rate``
  events; the rest stay queued (bounded queue → overflow drops).  This gives
  the congestion half of the bucket-size trade-off a measurable quantity
  (queue occupancy / drops vs. packet size).
* :func:`merge_streams` / :func:`merge_step` — SoA-view compatibility
  wrappers over the word path (full-width deadline semantics preserved for
  |deadline| < 128; the fabric hot path never goes through these).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import events as ev

_INF = 2**30


def merge_words(words: jax.Array, now: jax.Array) -> jax.Array:
    """Merge S streams of C words into one time-ordered stream of S*C lanes.

    Input is [..., S, C] (any leading shape collapses); output is [S*C]
    sorted ascending by the wrap-aware deadline key relative to ``now``,
    invalid lanes pushed to the end.  Stable across streams (ties broken by
    stream index then lane — FIFO order within a stream is preserved).
    """
    flat = words.reshape(-1)
    order = jnp.argsort(ev.word_sort_key(flat, now), stable=True)
    return flat[order]


def merge_streams(
    addr: jax.Array, deadline: jax.Array, valid: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """SoA compatibility view of the k-way merge (full-width deadlines).

    Inputs are [S, C]; outputs are [S*C] sorted ascending by deadline with
    invalid lanes pushed to the end, stable in (stream, lane) order.  The
    fabric hot path uses :func:`merge_words` on the wire words instead.
    """
    key = jnp.where(valid, deadline, _INF)
    flat_key = key.reshape(-1)
    order = jnp.argsort(flat_key, stable=True)
    return (
        addr.reshape(-1)[order],
        deadline.reshape(-1)[order],
        valid.reshape(-1)[order],
    )


class MergeBuffer(NamedTuple):
    """Bounded, rate-limited merge queue of packed wire words.

    words : int32[depth] — always kept sorted (earliest wrap deadline first)
    with valid lanes first; empty lanes carry ``events.WORD_SENTINEL``.

    The SoA views (``addr`` / ``deadline`` / ``valid``) decode on demand —
    ``deadline`` is the 8-bit on-wire timestamp.
    """

    words: jax.Array

    @property
    def depth(self) -> int:
        return self.words.shape[-1]

    @property
    def addr(self) -> jax.Array:
        return ev.word_addr(self.words)

    @property
    def deadline(self) -> jax.Array:
        return ev.word_time(self.words)

    @property
    def valid(self) -> jax.Array:
        return ev.word_valid(self.words)

    def occupancy(self) -> jax.Array:
        return jnp.sum(ev.word_valid(self.words).astype(jnp.int32))


def merge_init(depth: int) -> MergeBuffer:
    return MergeBuffer(words=jnp.full((depth,), ev.WORD_SENTINEL, jnp.int32))


def _sorted_words(words: jax.Array, now: jax.Array, use_pallas: bool) -> jax.Array:
    """Stable ascending sort by (wrap key relative to now, lane index)."""
    if use_pallas:
        from repro.kernels.merge_sort import ops as ms_ops

        return ms_ops.merge_sort_words(words, now)
    order = jnp.argsort(ev.word_sort_key(words, now), stable=True)
    return words[order]


def merge_step_words(
    buf: MergeBuffer,
    in_words: jax.Array,
    *,
    now: jax.Array,
    rate: int,
    use_pallas: bool = False,
) -> tuple[MergeBuffer, jax.Array, jax.Array]:
    """One merge-buffer cycle on the wire-word representation.

    1. enqueue incoming words (flattened packets) into the sorted queue;
    2. emit the ``rate`` earliest-deadline words (relative to ``now`` under
       the 8-bit wrap contract);
    3. of the remainder, keep at most ``depth`` queued — the surplus is
       dropped (congestion overflow, returned).

    Conservation holds by construction every cycle::

        incoming + occupancy_before == emitted + occupancy_after + dropped

    ``use_pallas`` selects the bitonic merge_sort word kernel
    (repro.kernels.merge_sort) over the jnp argsort reference; the two are
    bit-identical (tests/test_kernels.py).

    Returns (new_buf, out_words[rate], dropped).
    """
    # Pad with `rate` invalid lanes so the post-emit slice below is always
    # in-bounds regardless of the incoming packet size.
    pad = jnp.full((rate,), ev.WORD_SENTINEL, jnp.int32)
    all_words = jnp.concatenate([buf.words, in_words.reshape(-1), pad])
    all_words = _sorted_words(all_words, now, use_pallas)
    new_words, out_words, dropped = merge_split(
        all_words, rate=rate, depth=buf.depth)
    return MergeBuffer(words=new_words), out_words, dropped


def merge_split(
    all_words_sorted: jax.Array, *, rate: int, depth: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split one sorted merge cycle into (queue[depth], emitted[rate],
    dropped) — the emission/overflow judgment of :func:`merge_step_words`,
    factored out so the fused drain megakernel (repro.kernels.fused_drain)
    shares one definition with the unfused path.

    Valid lanes are compacted to the front of the sorted stream, so the
    first ``rate`` lanes are the earliest-deadline events and everything
    the queue keeps is the window [rate, rate + depth); only occupancy
    beyond the queue depth drops (congestion overflow).
    """
    out_words = all_words_sorted[:rate]
    n_valid = jnp.sum(ev.word_valid(all_words_sorted).astype(jnp.int32))
    emitted = jnp.minimum(n_valid, rate)
    queued = n_valid - emitted
    dropped = jnp.maximum(queued - depth, 0).astype(jnp.int32)
    new_words = jax.lax.dynamic_slice_in_dim(all_words_sorted, rate, depth)
    return new_words, out_words, dropped


def merge_drain_words(
    buf: MergeBuffer,
    in_words: jax.Array,
    *,
    now0: jax.Array,
    rate: int,
    use_pallas: bool = False,
) -> tuple[MergeBuffer, jax.Array, jax.Array]:
    """Drain a B-step superstep batch through the rate-limited queue with
    per-step emission.

    ``in_words`` is ``int32[B, lanes]`` — the delivered word stream of each
    substep of one flush, substep k judged at clock ``now0 + k``.  The
    queue replays exactly the per-step schedule: enqueue substep k's
    arrivals, emit the ``rate`` earliest-deadline words against that
    substep's clock, carry the queue to substep k+1.  Queue contents,
    emission streams and drop counts are therefore bitwise-identical to B
    separate :func:`merge_step_words` calls — which is what pins the
    superstep fabric to the B=1 schedule (tests/test_superstep.py).

    Returns ``(new_buf, out_words[B, rate], dropped[B])``.  The loop is
    unrolled (B is a small static superstep factor), keeping the bitonic
    ``use_pallas`` sort usable with static shapes.
    """
    outs, drops = [], []
    for k in range(in_words.shape[0]):
        buf, out_k, dropped_k = merge_step_words(
            buf, in_words[k], now=now0 + k, rate=rate,
            use_pallas=use_pallas,
        )
        outs.append(out_k)
        drops.append(dropped_k)
    return buf, jnp.stack(outs), jnp.stack(drops)


def merge_step(
    buf: MergeBuffer,
    in_addr: jax.Array,
    in_deadline: jax.Array,
    in_valid: jax.Array,
    *,
    rate: int,
    use_pallas: bool = False,
) -> tuple[MergeBuffer, tuple[jax.Array, jax.Array, jax.Array], jax.Array]:
    """SoA compatibility wrapper over :func:`merge_step_words`.

    Encodes the incoming lanes into wire words (deadlines project through
    ``wrap8``) and decodes the emitted stream back to
    (out_addr[rate], out_deadline8[rate], out_valid[rate]).  Ordering matches
    the historical full-width sort whenever deadlines stay within the 8-bit
    wrap window of each other (|deadline| < 128 relative to the epoch used
    here, now = 0).  The fabric threads the real ``now`` via
    :func:`merge_step_words`.
    """
    in_words = ev.encode_word(in_addr, in_deadline, in_valid)
    new_buf, out_words, dropped = merge_step_words(
        buf, in_words, now=jnp.int32(0), rate=rate, use_pallas=use_pallas
    )
    out_addr, out_dead, out_valid = ev.decode_word(out_words)
    return new_buf, (out_addr, out_dead, out_valid), dropped
