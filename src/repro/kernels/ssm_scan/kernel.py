"""Pallas TPU kernel: selective-SSM scan (Mamba recurrence hot loop).

Design for the TPU memory hierarchy:

* grid = (B, Din/BD, T/BT); the time axis is the innermost, sequential
  ("arbitrary") dimension — the carried state h [BD, N] lives in VMEM
  scratch across time tiles, so HBM sees each input element exactly once
  (the scan is memory-bound; arithmetic intensity ~ O(N)).
* channel blocks BD=128 match the lane width; h [128, N] (N = 16 for
  Mamba-1, 64 for Mamba-2/SSD) is a few tens of KB — comfortably VMEM
  resident.
* inside a tile the recurrence steps sequentially (a true data dependence),
  but each step is a [BD, N] VPU op — the hardware parallelism is across
  channels/state, exactly how the GPU version parallelizes across the
  d_inner dimension (warp -> lane mapping becomes sublane/lane mapping).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

BLOCK_D = 128
BLOCK_T = 128


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_ref,
            *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]                       # [BD, N]
    dvec = d_ref[...]                    # [1, BD]

    def step(i, h):
        x_t = x_ref[0, i, :]             # [BD]
        dt_t = dt_ref[0, i, :]           # [BD]
        b_t = b_ref[0, i, :]             # [N]
        c_t = c_ref[0, i, :]             # [N]
        decay = jnp.exp(dt_t[:, None] * a)
        h = decay * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y = jnp.sum(h * c_t[None, :], axis=1) + dvec[0] * x_t
        y_ref[0, i, :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret", "block_d", "block_t"))
def ssm_scan_pallas(x, dt, A, Bm, Cm, D, *, interpret: bool = False,
                    block_d: int = BLOCK_D, block_t: int = BLOCK_T):
    """x/dt: [B, T, Din]; A: [Din, N]; Bm/Cm: [B, T, N]; D: [Din].

    Requires T % block_t == 0 and Din % block_d == 0 (ops.py pads).
    """
    b, t, din = x.shape
    n = A.shape[1]
    if t % block_t or din % block_d:
        raise ValueError("T/Din must be multiples of the block sizes")
    grid = (b, din // block_d, t // block_t)

    xdt_spec = pl.BlockSpec((1, block_t, block_d),
                            lambda bi, di, ti: (bi, ti, di))
    bc_spec = pl.BlockSpec((1, block_t, n), lambda bi, di, ti: (bi, ti, 0))
    a_spec = pl.BlockSpec((block_d, n), lambda bi, di, ti: (di, 0))
    d_spec = pl.BlockSpec((1, block_d), lambda bi, di, ti: (0, di))
    y_spec = pl.BlockSpec((1, block_t, block_d),
                          lambda bi, di, ti: (bi, ti, di))

    scratch = [pltpu.VMEM((block_d, n), jnp.float32)]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    kernel = functools.partial(_kernel, block_t=block_t)
    f32 = lambda z: z.astype(jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[xdt_spec, xdt_spec, a_spec, bc_spec, bc_spec, d_spec],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, din), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(f32(x), f32(dt), f32(A), f32(Bm), f32(Cm), f32(D).reshape(1, din))
