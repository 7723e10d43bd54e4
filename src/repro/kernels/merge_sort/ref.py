"""Pure-jnp oracle for the merge_sort kernel.

The merge stage's sort contract (repro.core.merge): order lanes ascending by
``deadline`` with invalid lanes pushed to the end, stable in the original
lane order.  The Pallas kernel must reproduce this permutation bit-exactly —
it resolves ties by the original lane index, which for a stable sort is the
same total order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_INF = 2**30


def merge_sort_ref(
    addr: jax.Array, deadline: jax.Array, valid: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    key = jnp.where(valid, deadline, _INF)
    order = jnp.argsort(key, stable=True)
    return addr[order], deadline[order], valid[order]


def merge_sort_words_ref(words: jax.Array, now) -> jax.Array:
    """Word-path oracle: stable ascending sort by the wrap-aware deadline
    key relative to ``now`` — the contract of repro.core.merge."""
    from repro.core import events as ev

    order = jnp.argsort(ev.word_sort_key(words, now), stable=True)
    return words[order]
