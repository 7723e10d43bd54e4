"""Shared helpers for the Pallas kernel packages.

Dispatch policy:

* :func:`resolve_interpret` — the one dispatch decision.  ``None`` means
  "interpret off-TPU, compile on TPU" (the kernel body still executes —
  in the Pallas interpreter — so CPU CI validates kernel semantics, not a
  fallback).  On a TPU backend a kernel is always compiled: asking for
  the interpreter there raises instead of quietly running the kernel on
  the host.
* ``REPRO_FORCE_INTERPRET=1`` — environment override that forces the
  interpreter regardless of the caller's argument.  The CI
  ``kernels-interpret`` leg sets it so every ``use_pallas`` code path is
  exercised end-to-end on CPU runners instead of silently skipping the
  kernels.

The env var is read at trace time (the wrappers mark ``interpret``
static), so flipping it mid-process requires clearing jit caches — CI
sets it once per job, which is the intended use.

In-kernel building blocks that Mosaic lowers on every TPU generation:

* :func:`exclusive_cumsum` — Mosaic has no ``cumsum``; a log-step scan of
  lane rotations takes its place.
* :func:`onehot_dot` — the MXU takes no int32 operands; integer one-hot
  contractions run in f32 at ``HIGHEST`` precision, which is exact here.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

FORCE_INTERPRET_ENV = "REPRO_FORCE_INTERPRET"


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def force_interpret() -> bool:
    """True when ``REPRO_FORCE_INTERPRET`` requests the Pallas interpreter."""
    return os.environ.get(FORCE_INTERPRET_ENV, "").strip().lower() not in (
        "", "0", "false", "no")


def resolve_interpret(interpret: bool | None) -> bool:
    """The shared ``interpret=None`` auto-detect of every kernel wrapper.

    Priority: the env override forces the interpreter; an explicit
    ``True``/``False`` is honored otherwise; ``None`` interprets exactly
    when not running on a TPU backend.  Interpreting on a TPU backend
    raises: a kernel there is compiled or not run at all.
    """
    if force_interpret():
        interpret = True
    elif interpret is None:
        interpret = not on_tpu()
    if interpret and on_tpu():
        raise RuntimeError(
            "Pallas interpret mode requested on a TPU backend "
            f"({FORCE_INTERPRET_ENV}={os.environ.get(FORCE_INTERPRET_ENV)!r}"
            "); kernels are compiled for the chip or not run")
    return bool(interpret)


def exclusive_cumsum(x: jax.Array) -> jax.Array:
    """Exclusive prefix sum of an int32 ``[R, L]`` array along its lanes.

    Hillis–Steele scan: ``log2(L)`` rounds, each adding the array rotated
    by ``s`` lanes wherever the lane index is at least ``s``.  Exact for
    any int32 input; ``pltpu.roll`` follows ``jnp.roll``.
    """
    n = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    incl, s = x, 1
    while s < n:
        incl = incl + jnp.where(lane >= s, pltpu.roll(incl, s, 1), 0)
        s *= 2
    return incl - x


def onehot_dot(a: jax.Array, b: jax.Array, dimension_numbers) -> jax.Array:
    """Integer ``dot_general`` on the MXU for one-hot contractions.

    One operand must be 0/1 and every output a sum of integers below
    2**24 (wire words are 22-bit, counts are at most the lane count): at
    ``HIGHEST`` precision each product is then exact in f32, so the int32
    result is bit-equal to the integer contraction.
    """
    out = jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), dimension_numbers,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)
