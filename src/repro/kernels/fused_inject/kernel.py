"""Pallas TPU megakernel: the whole superstep inject path in one launch.

One single-program ``pallas_call`` (no grid) holds the hot state of all B
substeps resident in VMEM and runs, per substep k against clock ``t0 + k``:

  1. routing-LUT lookup — TPU has no fast random VMEM gather, so the LUT
     read is a one-hot compare ([N, E] ``broadcasted_iota`` match against
     the clamped addresses) contracted with the table on the MXU.  The
     table arrives as the byte planes of its ``[N, 4K]`` field matrix
     (``kernels.common.byte_planes``; four fields per fan-out entry), so
     one single-pass bf16 matmul with f32 accumulation yields every
     field of all K entries exactly;
  2. per fan-out slice j (the K entries, one after another on E-wide
     rows): reachability cull (health mask) and the 8-bit wrap-window
     admission with the remaining deferral ``B-1-k`` as extra slack —
     exactly the judgment of
     :meth:`repro.core.fabric.PulseFabric._inject_block`;
  3. rank within bucket in the lane order of ``routing.route``, which
     flattens ``[E, K]`` e-major: lane (e, j) ranks after every (e' < e,
     any j') and after (e, j' < j) in its bucket, so with
     ``oh_j = onehot(bucket_j) & valid_j`` ([NB, E])
     ``rank(e, j) = exclusive_cumsum(sum_j' oh_j')[b, e]
     + sum_{j' < j} oh_j'[b, e]`` — one log-step lane-rotation scan per
     substep (``kernels.common.exclusive_cumsum``: Mosaic has no
     ``cumsum``), whatever K;
  4. scatter-free slab: ``slab[b, s] = sum_(e, j) [bucket == b] [slot == s]
     word`` is a bucket one-hot ([NB, E], times each byte plane of the
     wire word) contracted with a slot one-hot ([C, E]) on the MXU,
     accumulated over the K slices; a hit plane (the bucket one-hot
     itself) decides sentinel fill, because word value 0 is a *valid*
     word — address 0 at wrap time 0;
  5. per-substep counters (sent / overflow / wrap_expired / lost, summed
     over the K slices, and the bucket counts), stored as block k of
     small VMEM outputs.  The traffic row per destination chip is the sum
     of that chip's bucket counts, so ops.py derives it outside.

The unfused chain (route → cull → window → flush_pack → traffic) walks
about ten batched gathers, scatters and sorts through HBM per substep;
here the event rows, the LUT and the growing slab never leave VMEM.  At
K = 1 every sum over slices is its single term.

The LIF-fronted variant (:func:`fused_lif_inject_pallas`) prepends the
``repro.kernels.lif_step`` membrane dynamics and replaces the compacted
event buffer with the dense spike mask: the lane order of valid events in
the dense mask equals the stable ``events.from_spikes`` compaction order,
and the FPGA-interface capacity truncation is the rank cut
``excl_rank < event_capacity`` — bitwise the same slab/stats as compaction
followed by the event-fronted kernel (property-pinned in
tests/test_kernels.py).

Bitwise caveats faithfully reproduced from the jnp chain:
  * gather clamping — ``route`` indexes the LUT with clamped addresses;
  * negative bucket ids wrap (JAX normalizes negative scatter indices
    *before* ``mode="drop"`` applies), indices past ``n_buckets`` drop;
  * ``deadline`` rides unmasked (``time + delay`` even on invalid lanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import events as ev
from repro.kernels.common import (byte_planes, exclusive_cumsum,
                                  from_byte_planes)

_SENTINEL = ev.WORD_SENTINEL
_ADDR_SENTINEL = ev.ADDR_SENTINEL
_ADDR_MASK = ev.WORD_ADDR_MASK
_TIME_MASK = ev.WORD_TIME_MASK
_HALF_WINDOW = ev.TIME_MOD // 2

# Column layout of the [N, 4K] routing-table matrix: fan-out entry j owns
# columns 4j .. 4j+3, in this order.
TABLE_COLS = ("dest_chip", "dest_addr", "delay", "valid")
# Byte planes of the table matrix (all of int32) and of a wire word
# (22 bits, non-negative).
TABLE_PLANES = 4
WORD_PLANES = 3
# Row layout of the [B, 4, 1] per-substep scalar-stats output.
STAT_ROWS = ("sent", "overflow", "wrap_expired", "lost")


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bf16(mask_or_int):
    """0/1 masks and byte planes as exact bfloat16 MXU operands."""
    return mask_or_int.astype(jnp.float32).astype(jnp.bfloat16)


def _mxu(a, b, contract):
    """Single-pass bf16 contraction with f32 accumulation, back in int32:
    exact while every output is an integer sum below 2**24."""
    out = jax.lax.dot_general(a, b, (contract, ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def table_rows(n_fields: int) -> int:
    """Rows per byte plane of the kernel's table operand: the ``4K``
    fields, padded to a whole sublane tile."""
    return _round_up(n_fields, 8)


def table_planes(tbl: jax.Array) -> jax.Array:
    """``[N, 4K]`` int32 table matrix → the kernel's ``[TABLE_PLANES *
    table_rows(4K), N]`` bf16 operand: the transposed matrix, padded with
    zero rows, one block of rows per byte plane."""
    t = tbl.T
    t = jnp.pad(t, ((0, table_rows(t.shape[0]) - t.shape[0]), (0, 0)))
    return _bf16(jnp.concatenate(byte_planes(t, TABLE_PLANES), axis=0))


def _sum(xs):
    """Sum of a list of arrays; one element is returned as it is."""
    return functools.reduce(jnp.add, xs)


def _inject_substep(
    addr_row, time_row, valid_row,   # int32[1, E] (valid_row: 0/1)
    table, reach_col, now_k, defer_k,
    *, n_real, fanout, n_chips, buckets_per_chip, capacity, mode,
    time_window,
):
    """One substep of the inject chain on VMEM-resident rows.

    ``table`` is the :func:`table_planes` operand, ``reach_col`` an
    int32 ``[n_chips, 1]`` health column or None (no cull).  Returns
    ``(slab_blk [NBp, C], counts_col [NB, 1], stats_col [4, 1])``, with
    NBp the bucket count rounded up to a sublane tile (rows past NB hold
    sentinels) and the counters as column vectors, so the caller stores
    substep k without any in-kernel transpose.
    """
    e = addr_row.shape[1]
    nb = n_chips * buckets_per_chip
    nbp = _round_up(nb, 8)
    plane_rows = table_rows(4 * fanout)

    evalid = valid_row != 0
    # LUT lookup with JAX gather index semantics (negative indices wrap
    # once, then everything clamps), then one-hot match against the
    # (padded) table columns and contract every byte plane on the MXU.
    addr_m = jnp.where(evalid, addr_row, 0)
    addr_m = jnp.where(addr_m < 0, addr_m + n_real, addr_m)
    addr_c = jnp.clip(addr_m, 0, n_real - 1)
    match = _bf16(_iota((table.shape[1], e), 0) == addr_c)
    planes = _mxu(table, match, ((1,), (0,)))        # [P * rows, E]
    fields = from_byte_planes([planes[p * plane_rows:(p + 1) * plane_rows]
                               for p in range(TABLE_PLANES)])

    count = lambda masks: jnp.sum(_sum(masks), keepdims=True)
    as_int = lambda m: m.astype(jnp.int32)
    slices, sent, lost, expired = [], [], [], []
    for j in range(fanout):
        dc, da, dly, tv = (fields[4 * j + f:4 * j + f + 1]
                           for f in range(4))
        valid = (tv != 0) & evalid
        dest_chip = jnp.where(valid, dc, 0)
        dest_addr = jnp.where(valid, da, _ADDR_SENTINEL)
        deadline = time_row + dly                    # unmasked, as route()
        sent.append(as_int(valid))

        if reach_col is not None:
            hot = _iota((n_chips, e), 0) == jnp.clip(dest_chip, 0,
                                                     n_chips - 1)
            reach_g = jnp.sum(jnp.where(hot, reach_col, 0), axis=0,
                              keepdims=True)         # [1, E]
            in_range = (dest_chip >= 0) & (dest_chip < n_chips)
            ok = ~in_range | (reach_g != 0)
            lost.append(as_int(valid & ~ok))
            valid = valid & ok

        # Wrap-window admission with the remaining deferral as extra slack.
        diff = deadline - now_k
        in_window = (diff > defer_k) & (diff < _HALF_WINDOW)
        expired.append(as_int(valid & ~in_window))
        valid = valid & in_window

        if mode == "simplified":
            bid = dest_chip * buckets_per_chip
        else:
            win = (deadline // max(time_window, 1)) % buckets_per_chip
            bid = dest_chip * buckets_per_chip + win
        oh = as_int((_iota((nb, e), 0) == bid) & valid)
        word = ((dest_addr & _ADDR_MASK) << ev.WORD_ADDR_SHIFT
                | (deadline & _TIME_MASK))
        slices.append((valid, bid, oh, word))

    # Rank within bucket in e-major (e, j) order: one prefix sum over the
    # slices' summed one-hots, then the earlier slices of the same event.
    total = _sum([oh for _, _, oh, _ in slices])
    counts_col = jnp.sum(total, axis=1, keepdims=True)   # [NB, 1]
    before = exclusive_cumsum(total)
    acc, overflow = [], []
    for j, (valid, bid, oh, word) in enumerate(slices):
        sel = as_int(_iota((nb, e), 0) == jnp.clip(bid, 0, nb - 1))
        slot = jnp.sum(before * sel, axis=0, keepdims=True)  # [1, E]
        if j + 1 < fanout:
            before = before + oh
        keep = valid & (slot < capacity)
        overflow.append(as_int(valid & (slot >= capacity)))

        # Scatter-free slab: combined (bucket, slot) position with JAX's
        # negative-index wrap, as two one-hots contracted on the MXU.
        b_norm = jnp.where(bid < 0, bid + nb, bid)
        in_slab = keep & (b_norm >= 0) & (b_norm < nb)
        bucket = (_iota((nbp, e), 0) == b_norm) & in_slab    # [NBp, E]
        lhs = jnp.concatenate(
            [jnp.where(bucket, w, 0)
             for w in byte_planes(word, WORD_PLANES)] + [as_int(bucket)],
            axis=0)                                  # [(P+1) * NBp, E]
        slot_oh = _bf16(_iota((capacity, e), 0) == slot)     # [C, E]
        acc.append(_mxu(_bf16(lhs), slot_oh, ((1,), (1,))))
    acc = _sum(acc)                                  # [(P+1) * NBp, C]
    value = from_byte_planes(
        [acc[p * nbp:(p + 1) * nbp] for p in range(WORD_PLANES)])
    hit = acc[WORD_PLANES * nbp:]
    slab_blk = jnp.where(hit > 0, value, _SENTINEL)  # [NBp, C]

    zero = jnp.zeros((1, 1), jnp.int32)
    stats_col = jnp.concatenate(
        [count(sent), count(overflow), count(expired),
         count(lost) if lost else zero], axis=0)     # [4, 1]
    return slab_blk, counts_col, stats_col


def _split_refs(refs, cull):
    """``(reach_ref | None, output refs)`` from the refs after the
    table: the health column is an input only when the kernel culls."""
    return (refs[0], refs[1:]) if cull else (None, refs)


def _store(out_refs, k, slab_blk, counts_col, stats_col):
    """Substep k's outputs, at a leading index (static or traced)."""
    for ref, value in zip(out_refs, (slab_blk, counts_col, stats_col)):
        ref[k] = value


def _out_shapes(b, *, n_chips, buckets_per_chip, capacity):
    nb = n_chips * buckets_per_chip
    return (
        jax.ShapeDtypeStruct((b, _round_up(nb, 8), capacity), jnp.int32),
        jax.ShapeDtypeStruct((b, nb, 1), jnp.int32),
        jax.ShapeDtypeStruct((b, 4, 1), jnp.int32),
    )


def _events_kernel(
    addr_ref, time_ref, valid_ref, t0_ref, table_ref, *refs,
    cull, **kw,
):
    reach_ref, out_refs = _split_refs(refs, cull)
    b = addr_ref.shape[0]
    t0 = t0_ref[0, 0]

    # A loop, not a Python unroll: the traced and lowered kernel stays one
    # substep long whatever B, which keeps the program's set-up short.
    def substep(k, carry):
        row = lambda ref: ref[pl.ds(k, 1), :]
        _store(out_refs, k, *_inject_substep(
            row(addr_ref), row(time_ref), row(valid_ref), table_ref[...],
            reach_ref[...] if cull else None, t0 + k, (b - 1) - k, **kw))
        return carry

    jax.lax.fori_loop(0, b, substep, 0)


_STATIC = ("n_real", "fanout", "n_chips", "buckets_per_chip", "capacity",
           "mode", "time_window", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_inject_pallas(
    addr, time, valid,        # int32[B, E], E % 128 == 0
    table,                    # bf16[TABLE_PLANES * table_rows(4K), Npad]
    reach,                    # int32[n_chips, 1] | None (no cull)
    t0,                       # int32[1, 1]
    *,
    n_real: int,
    fanout: int,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str,
    time_window: int,
    interpret: bool = False,
):
    """Raw kernel invocation (inputs pre-padded by ops.py; ``table`` from
    :func:`table_planes`).

    Returns ``(slab [B, NBp, C], counts [B, NB, 1], stats [B, 4, 1])``,
    one block per substep; ops.py re-orients and trims the slab's padding
    rows.
    """
    b, e = addr.shape
    if e % 128 != 0:
        raise ValueError(f"E={e} must be padded to a multiple of 128")
    cull = reach is not None
    kernel = functools.partial(
        _events_kernel, cull=cull, n_real=n_real, fanout=fanout,
        n_chips=n_chips, buckets_per_chip=buckets_per_chip,
        capacity=capacity, mode=mode, time_window=time_window)
    out_shape = _out_shapes(b, n_chips=n_chips,
                            buckets_per_chip=buckets_per_chip,
                            capacity=capacity)
    inputs = (addr, time, valid.astype(jnp.int32), t0.astype(jnp.int32),
              table) + ((reach,) if cull else ())
    return pl.pallas_call(kernel, out_shape=out_shape,
                          interpret=interpret)(*inputs)


def _lif_kernel(
    v_ref, refrac_ref, cur_ref, pf_ref, refp_ref, t0_ref, table_ref, *refs,
    event_capacity, cull, **kw,
):
    reach_ref, out_refs = _split_refs(refs, cull)
    v_out_ref, refrac_out_ref, spk_ref, volt_ref, *inject_refs = out_refs
    b, n = cur_ref.shape
    table = table_ref[...]
    reach_col = reach_ref[...] if cull else None
    t0 = t0_ref[0, 0]
    v = v_ref[...]
    refrac = refrac_ref[...]
    tau, v_th = pf_ref[0:1, :], pf_ref[1:2, :]
    v_reset, v_rest = pf_ref[2:3, :], pf_ref[3:4, :]
    refp = refp_ref[...]
    decay = jnp.exp(-1.0 / tau)
    lane = _iota((1, n), 1)
    for k in range(b):
        # LIF dynamics (repro.kernels.lif_step, bit-for-bit).
        active = refrac <= 0
        v_int = jnp.where(active, v_rest + decay * (v - v_rest)
                          + cur_ref[k:k + 1, :], v)
        spk = (v_int > v_th) & active
        v = jnp.where(spk, v_reset, v_int)
        refrac = jnp.where(spk, refp, jnp.maximum(refrac - 1, 0))
        spk_ref[k:k + 1, :] = spk.astype(v.dtype)
        volt_ref[k:k + 1, :] = v
        # Dense-mask event front-end: lane order == from_spikes compaction
        # order; the FPGA-interface truncation is the rank cut.
        s32 = spk.astype(jnp.int32)
        rank = exclusive_cumsum(s32)
        evalid = s32 * (rank < event_capacity).astype(jnp.int32)
        now_k = t0 + k
        _store(inject_refs, k, *_inject_substep(
            lane, jnp.zeros((1, n), jnp.int32) + now_k, evalid,
            table, reach_col, now_k, (b - 1) - k, **kw))
    v_out_ref[...] = v
    refrac_out_ref[...] = refrac


@functools.partial(jax.jit, static_argnames=("event_capacity",) + _STATIC)
def fused_lif_inject_pallas(
    v, refrac,                # f32[1, Npad], int32[1, Npad]
    currents,                 # f32[B, Npad]
    params_f,                 # f32[4, Npad]: tau_m, v_th, v_reset, v_rest
    refrac_period,            # int32[1, Npad]
    table,                    # bf16 table_planes operand
    reach,                    # int32[n_chips, 1] | None
    t0,                       # int32[1, 1]
    *,
    event_capacity: int,
    n_real: int,
    fanout: int,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str,
    time_window: int,
    interpret: bool = False,
):
    """LIF-fronted megakernel: membrane update → spikes → flush slab.

    Returns ``(v, refrac, spikes [B, Npad], voltage [B, Npad], slab,
    counts, stats)`` with the inject outputs laid out as in
    :func:`fused_inject_pallas`.
    """
    b, n = currents.shape
    if n % 128 != 0:
        raise ValueError(f"N={n} must be padded to a multiple of 128")
    cull = reach is not None
    kernel = functools.partial(
        _lif_kernel, event_capacity=event_capacity, cull=cull,
        n_real=n_real, fanout=fanout, n_chips=n_chips,
        buckets_per_chip=buckets_per_chip, capacity=capacity, mode=mode,
        time_window=time_window)
    out_shape = (
        jax.ShapeDtypeStruct((1, n), currents.dtype),
        jax.ShapeDtypeStruct((1, n), jnp.int32),
        jax.ShapeDtypeStruct((b, n), currents.dtype),
        jax.ShapeDtypeStruct((b, n), currents.dtype),
    ) + _out_shapes(b, n_chips=n_chips, buckets_per_chip=buckets_per_chip,
                    capacity=capacity)
    inputs = (v, refrac.astype(jnp.int32), currents, params_f,
              refrac_period.astype(jnp.int32), t0.astype(jnp.int32),
              table) + ((reach,) if cull else ())
    return pl.pallas_call(kernel, out_shape=out_shape,
                          interpret=interpret)(*inputs)
