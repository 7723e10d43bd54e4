"""Pallas TPU megakernel: the whole superstep inject path in one launch.

One single-program ``pallas_call`` (no grid) holds the hot state of all B
substeps resident in VMEM and runs, per substep k against clock ``t0 + k``:

  1. routing-LUT lookup — TPU has no fast random VMEM gather, so the LUT
     read is a one-hot compare ([N, E] ``broadcasted_iota`` match against
     the clamped addresses) contracted with the ``[N, 4]`` table matrix in
     a single MXU matmul (f32 at ``HIGHEST`` precision via
     ``kernels.common.onehot_dot`` — exact, and the MXU takes no int32);
  2. reachability cull (health mask) and the 8-bit wrap-window admission
     with the remaining deferral ``B-1-k`` as extra slack — exactly the
     judgment of :meth:`repro.core.fabric.PulseFabric._inject_block`;
  3. wire-word encode + flush-slab scatter: rank-within-bucket via the
     one-hot prefix sum of ``repro.core.buckets.compute_slots`` (a
     log-step lane-rotation scan, ``kernels.common.exclusive_cumsum``:
     Mosaic has no ``cumsum``), then a
     slot-selection reduce onto the combined ``bucket * capacity + slot``
     code (scatter-free: ``slab[r] = Σ_e [code_e == r] · word_e``, with a
     hit count deciding sentinel fill because word value 0 is a *valid*
     word — address 0 at wrap time 0);
  4. per-substep stats accumulation (sent / overflow / wrap_expired /
     lost / counts / traffic), written as column k of small VMEM outputs.

The per-substep unfused chain (route → cull → window → flush_pack) walks
~10 separate XLA kernels through HBM per substep; here the event rows, the
LUT and the growing slab never leave VMEM between substeps.

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
from repro.kernels.common import exclusive_cumsum, onehot_dot

_SENTINEL = ev.WORD_SENTINEL
_ADDR_SENTINEL = ev.ADDR_SENTINEL
_ADDR_MASK = ev.WORD_ADDR_MASK
_TIME_MASK = ev.WORD_TIME_MASK
_HALF_WINDOW = ev.TIME_MOD // 2

# Column layout of the [N, 4] routing-table matrix fed to the kernel.
TABLE_COLS = ("dest_chip", "dest_addr", "delay", "valid")
# Row layout of the [4, B] per-substep scalar-stats output.
STAT_ROWS = ("sent", "overflow", "wrap_expired", "lost")


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _inject_substep(
    addr_row, time_row, valid_row,   # int32[1, E] (valid_row: 0/1)
    table, reach_row, now_k, defer_k,
    *, n_real, n_chips, buckets_per_chip, capacity, mode, time_window,
):
    """One substep of the inject chain on VMEM-resident rows.

    Returns ``(slab_col [NB*C, 1], counts_col [NB, 1], traffic_col
    [n_chips, 1], stats_col [4, 1])`` — everything oriented as column
    vectors so the caller stores substep k without any in-kernel
    transpose.
    """
    e = addr_row.shape[1]
    nb = n_chips * buckets_per_chip

    evalid = valid_row != 0
    # LUT lookup with JAX gather index semantics (negative indices wrap
    # once, then everything clamps), then one-hot match against the
    # (padded) table rows and contract on the MXU.
    addr_m = jnp.where(evalid, addr_row, 0)
    addr_m = jnp.where(addr_m < 0, addr_m + n_real, addr_m)
    addr_c = jnp.clip(addr_m, 0, n_real - 1)
    match = (_iota((table.shape[0], e), 0) == addr_c).astype(jnp.int32)
    fields = onehot_dot(table, match, (((0,), (0,)), ((), ())))  # [4, E]
    dc, da = fields[0:1, :], fields[1:2, :]
    dly, tv = fields[2:3, :], fields[3:4, :]

    valid = (tv != 0) & evalid
    dest_chip = jnp.where(valid, dc, 0)
    dest_addr = jnp.where(valid, da, _ADDR_SENTINEL)
    deadline = time_row + dly                        # unmasked, as route()

    count = lambda m: jnp.sum(m.astype(jnp.int32), keepdims=True)
    sent = count(valid)

    # Reachability cull (all-ones reach row == no health mask: identity).
    dc_clip = jnp.clip(dest_chip, 0, n_chips - 1)
    hot = (_iota((n_chips, e), 0) == dc_clip).astype(jnp.int32)
    reach_g = onehot_dot(reach_row, hot, (((1,), (0,)), ((), ())))  # [1, E]
    in_range = (dest_chip >= 0) & (dest_chip < n_chips)
    ok = ~in_range | (reach_g != 0)
    lost = count(valid & ~ok)
    valid = valid & ok

    # Wrap-window admission with the remaining deferral as extra slack.
    diff = deadline - now_k
    in_window = (diff > defer_k) & (diff < _HALF_WINDOW)
    wrap_expired = count(valid & ~in_window)
    valid = valid & in_window

    if mode == "simplified":
        bid = dest_chip * buckets_per_chip
    else:
        win = (deadline // max(time_window, 1)) % buckets_per_chip
        bid = dest_chip * buckets_per_chip + win

    # Rank within bucket: one-hot prefix sum (compute_slots, transposed).
    oh = ((_iota((nb, e), 0) == bid) & valid).astype(jnp.int32)
    counts_col = jnp.sum(oh, axis=1, keepdims=True)  # [NB, 1]
    sel = (_iota((nb, e), 0) == jnp.clip(bid, 0, nb - 1)).astype(jnp.int32)
    slot = jnp.sum(exclusive_cumsum(oh) * sel, axis=0, keepdims=True)  # [1, E]

    keep = valid & (slot < capacity)
    overflow = count(valid & (slot >= capacity))
    word = (dest_addr & _ADDR_MASK) << ev.WORD_ADDR_SHIFT \
        | (deadline & _TIME_MASK)
    word = jnp.where(keep, word, _SENTINEL)

    # Scatter-free slab column: combined (bucket, slot) code with JAX's
    # negative-index wrap, then a hit-counted selection reduce.
    b_norm = jnp.where(bid < 0, bid + nb, bid)
    in_slab = keep & (b_norm >= 0) & (b_norm < nb)
    code = jnp.where(in_slab, b_norm * capacity + slot, nb * capacity)
    pick = (_iota((nb * capacity, e), 0) == code).astype(jnp.int32)
    value = jnp.sum(pick * word, axis=1, keepdims=True)
    hit = jnp.sum(pick, axis=1, keepdims=True)
    slab_col = jnp.where(hit > 0, value, _SENTINEL)  # [NB*C, 1]

    traffic_col = jnp.sum(
        ((_iota((n_chips, e), 0) == dest_chip) & valid).astype(jnp.int32),
        axis=1, keepdims=True)                       # [n_chips, 1]

    stats_col = jnp.concatenate([sent, overflow, wrap_expired, lost],
                                axis=0)              # [4, 1]
    return slab_col, counts_col, traffic_col, stats_col


def _events_kernel(
    addr_ref, time_ref, valid_ref, table_ref, reach_ref, t0_ref,
    slab_ref, counts_ref, traffic_ref, stats_ref,
    *, n_real, n_chips, buckets_per_chip, capacity, mode, time_window,
):
    b = addr_ref.shape[0]
    table = table_ref[...]
    reach_row = reach_ref[...]
    t0 = t0_ref[0, 0]
    for k in range(b):
        slab_col, counts_col, traffic_col, stats_col = _inject_substep(
            addr_ref[k:k + 1, :], time_ref[k:k + 1, :],
            valid_ref[k:k + 1, :], table, reach_row,
            t0 + k, (b - 1) - k,
            n_real=n_real, n_chips=n_chips,
            buckets_per_chip=buckets_per_chip, capacity=capacity,
            mode=mode, time_window=time_window)
        slab_ref[:, k:k + 1] = slab_col
        counts_ref[:, k:k + 1] = counts_col
        traffic_ref[:, k:k + 1] = traffic_col
        stats_ref[:, k:k + 1] = stats_col


@functools.partial(jax.jit, static_argnames=(
    "n_real", "n_chips", "buckets_per_chip", "capacity", "mode",
    "time_window", "interpret"))
def fused_inject_pallas(
    addr, time, valid,        # int32[B, E], E % 128 == 0
    table,                    # int32[Npad, 4], Npad % 8 == 0
    reach,                    # int32[1, n_chips]
    t0,                       # int32[1, 1]
    *,
    n_real: int,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str,
    time_window: int,
    interpret: bool = False,
):
    """Raw kernel invocation (inputs pre-padded by ops.py).

    Returns ``(slab2 [NB*C, B], countsT [NB, B], trafficT [n_chips, B],
    stats [4, B])`` — substeps on the minor axis so the kernel writes
    column slices; ops.py re-orients.
    """
    b, e = addr.shape
    if e % 128 != 0:
        raise ValueError(f"E={e} must be padded to a multiple of 128")
    nb = n_chips * buckets_per_chip
    kernel = functools.partial(
        _events_kernel, n_real=n_real, n_chips=n_chips,
        buckets_per_chip=buckets_per_chip, capacity=capacity, mode=mode,
        time_window=time_window)
    out_shape = (
        jax.ShapeDtypeStruct((nb * capacity, b), jnp.int32),
        jax.ShapeDtypeStruct((nb, b), jnp.int32),
        jax.ShapeDtypeStruct((n_chips, b), jnp.int32),
        jax.ShapeDtypeStruct((4, b), jnp.int32),
    )
    return pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret)(
        addr, time, valid.astype(jnp.int32), table, reach,
        t0.astype(jnp.int32))


def _lif_kernel(
    v_ref, refrac_ref, cur_ref, pf_ref, refp_ref,
    table_ref, reach_ref, t0_ref,
    v_out_ref, refrac_out_ref, spk_ref, volt_ref,
    slab_ref, counts_ref, traffic_ref, stats_ref,
    *, event_capacity, n_real, n_chips, buckets_per_chip, capacity, mode,
    time_window,
):
    b, n = cur_ref.shape
    table = table_ref[...]
    reach_row = reach_ref[...]
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
        slab_col, counts_col, traffic_col, stats_col = _inject_substep(
            lane, jnp.zeros((1, n), jnp.int32) + now_k, evalid,
            table, reach_row, now_k, (b - 1) - k,
            n_real=n_real, n_chips=n_chips,
            buckets_per_chip=buckets_per_chip, capacity=capacity,
            mode=mode, time_window=time_window)
        slab_ref[:, k:k + 1] = slab_col
        counts_ref[:, k:k + 1] = counts_col
        traffic_ref[:, k:k + 1] = traffic_col
        stats_ref[:, k:k + 1] = stats_col
    v_out_ref[...] = v
    refrac_out_ref[...] = refrac


@functools.partial(jax.jit, static_argnames=(
    "event_capacity", "n_real", "n_chips", "buckets_per_chip", "capacity",
    "mode", "time_window", "interpret"))
def fused_lif_inject_pallas(
    v, refrac,                # f32[1, Npad], int32[1, Npad]
    currents,                 # f32[B, Npad]
    params_f,                 # f32[4, Npad]: tau_m, v_th, v_reset, v_rest
    refrac_period,            # int32[1, Npad]
    table,                    # int32[Tpad, 4]
    reach,                    # int32[1, n_chips]
    t0,                       # int32[1, 1]
    *,
    event_capacity: int,
    n_real: int,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str,
    time_window: int,
    interpret: bool = False,
):
    """LIF-fronted megakernel: membrane update → spikes → flush slab.

    Returns ``(v, refrac, spikes [B, Npad], voltage [B, Npad], slab2,
    countsT, trafficT, stats)`` with the inject outputs laid out as in
    :func:`fused_inject_pallas`.
    """
    b, n = currents.shape
    if n % 128 != 0:
        raise ValueError(f"N={n} must be padded to a multiple of 128")
    nb = n_chips * buckets_per_chip
    kernel = functools.partial(
        _lif_kernel, event_capacity=event_capacity, n_real=n_real,
        n_chips=n_chips, buckets_per_chip=buckets_per_chip,
        capacity=capacity, mode=mode, time_window=time_window)
    out_shape = (
        jax.ShapeDtypeStruct((1, n), currents.dtype),
        jax.ShapeDtypeStruct((1, n), jnp.int32),
        jax.ShapeDtypeStruct((b, n), currents.dtype),
        jax.ShapeDtypeStruct((b, n), currents.dtype),
        jax.ShapeDtypeStruct((nb * capacity, b), jnp.int32),
        jax.ShapeDtypeStruct((nb, b), jnp.int32),
        jax.ShapeDtypeStruct((n_chips, b), jnp.int32),
        jax.ShapeDtypeStruct((4, b), jnp.int32),
    )
    return pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret)(
        v, refrac.astype(jnp.int32), currents, params_f,
        refrac_period.astype(jnp.int32), table, reach,
        t0.astype(jnp.int32))
