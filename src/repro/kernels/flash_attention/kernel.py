"""Pallas TPU kernel: FlashAttention-style fused attention with GQA.

Memory-hierarchy design (TPU, not a CUDA port):

* grid = (batch*q_heads, Sq/BQ, Skv/BK); the Skv axis is the innermost,
  sequential ("arbitrary") dimension so the online-softmax running state
  (m, l, acc) lives in VMEM scratch across k-block iterations.
* q block [BQ, D] stays resident; k/v stream through VMEM [BK, D] blocks —
  O(Sq*D) HBM traffic for q/out, O(Skv*D) per q-row-block for k/v, never an
  [Sq, Skv] score materialization.
* scores [BQ, BK] hit the MXU (f32 accumulation); BQ=BK=128 matches the
  128x128 systolic array.
* GQA is expressed in the BlockSpec index maps: the kv block index maps
  q-head h -> kv-head h // group, so no repeated-KV materialization.
* causal: off-diagonal blocks are skipped with @pl.when (no MXU work); the
  diagonal block applies the triangular mask.  (Grid still visits skipped
  blocks; a trapezoidal grid is a recorded §Perf follow-up.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, kv_len: int, block_q: int,
            block_k: int, n_kb: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [BQ, D]
        k = k_ref[0].astype(jnp.float32)            # [BK, D]
        v = v_ref[0].astype(jnp.float32)            # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # [BQ, BK]

        col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask = jnp.logical_and(mask, row >= col)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # [BQ]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (m == -inf): exp(NEG_INF - NEG_INF) -> nan
        safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
        l_new = alpha * l_ref[...] + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # Causal skip: drop k blocks entirely in the future of every q row.
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kb - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "kv_len", "block_q", "block_k",
                     "q_offset", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_len: int | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if sq % block_q or skv % block_k:
        raise ValueError("Sq/Skv must be multiples of the block sizes (ops pads)")
    group = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    if kv_len is None:
        kv_len = skv
    n_kb = skv // block_k
    grid = (b * hq, sq // block_q, n_kb)

    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, block_k, d),
        lambda bh, qi, ki, _hq=hq, _g=group: (
            (bh // _hq) * (_hq // _g) + (bh % _hq) // _g,
            ki,
            0,
        ),
    )
    o_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, kv_len=kv_len,
        block_q=block_q, block_k=block_k, n_kb=n_kb, q_offset=q_offset,
    )
    scratch = [
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]

    q3 = q.reshape(b * hq, sq, d)
    k3 = k.reshape(b * hkv, skv, d)
    v3 = v.reshape(b * hkv, skv, d)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(q3, k3, v3)
    return out.reshape(b, hq, sq, d)
