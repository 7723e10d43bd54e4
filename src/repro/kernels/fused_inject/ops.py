"""jit'd public wrappers for the fused inject megakernel.

Pads the event lanes to the VPU lane width (invalid lanes can never route:
``valid=0``), stacks the four fields of all K fan-out entries of the
routing table into one ``[N, 4K]`` int32 matrix (padded rows carry
``valid=0``) and hands the kernel its byte planes, invokes the
single-program Pallas kernel (interpret=True off-TPU), and re-orients the
kernel outputs into the :class:`FusedInjectOut` layout the fabric
consumes, deriving the traffic rows from the bucket counts.  Any fan-out
K is fused: K is the table's static shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.core import routing as rt
from repro.kernels.common import resolve_interpret
from repro.kernels.fused_inject.kernel import (fused_inject_pallas,
                                               fused_lif_inject_pallas,
                                               table_planes)
from repro.kernels.fused_inject.ref import FusedInjectOut, FusedLifInjectOut

LANES = 128


def _pad_to(x, m, axis, value):
    pad = (-x.shape[axis]) % m
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _table_matrix(table: rt.RoutingTable) -> tuple[jax.Array, int]:
    """``[Npad, 4K]`` int32: columns ``4j .. 4j+3`` hold fan-out entry j's
    ``kernel.TABLE_COLS``; rows padded to the lane width carry
    ``valid=0`` (the kernel puts N on lanes)."""
    tbl = jnp.stack([
        field[:, j].astype(jnp.int32)
        for j in range(table.fanout)
        for field in (table.dest_chip, table.dest_addr, table.delay,
                      table.valid)
    ], axis=1)                                        # [N, 4K]
    return _pad_to(tbl, LANES, 0, 0), table.n_neurons


def _reach_col(reach, n_chips: int) -> jax.Array | None:
    if reach is None:
        return None
    return jnp.asarray(reach).astype(jnp.int32).reshape(n_chips, 1)


def _reorient(slab, counts, stats, *, n_chips, buckets_per_chip, mode):
    """Kernel outputs → :class:`FusedInjectOut`.  The traffic row per
    destination chip sums that chip's bucket counts: both count the same
    admitted lanes, and chip c owns buckets ``c*bpc .. c*bpc + bpc-1``
    (in simplified mode only the first of them is used)."""
    nb = n_chips * buckets_per_chip
    counts, stats = counts[..., 0], stats[..., 0].T   # [B, NB], [4, B]
    per_chip = counts.reshape(-1, n_chips, buckets_per_chip)
    traffic = (per_chip[..., 0] if mode == "simplified"
               else per_chip.sum(axis=-1))
    return FusedInjectOut(
        slab=slab[:, :nb].transpose(1, 0, 2), counts=counts,
        sent=stats[0], overflow=stats[1], wrap_expired=stats[2],
        lost=stats[3], traffic=traffic)


@functools.partial(jax.jit, static_argnames=(
    "n_chips", "buckets_per_chip", "capacity", "mode", "time_window",
    "interpret"))
def fused_inject(
    events: ev.EventBuffer,        # [B, E]
    table: rt.RoutingTable,
    reach,                         # bool[n_chips] | None
    t0,
    *,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str = "simplified",
    time_window: int = 1,
    interpret: bool | None = None,
) -> FusedInjectOut:
    interpret = resolve_interpret(interpret)
    addr = _pad_to(events.addr.astype(jnp.int32), LANES, 1, 0)
    time = _pad_to(events.time.astype(jnp.int32), LANES, 1, 0)
    valid = _pad_to(events.valid.astype(jnp.int32), LANES, 1, 0)
    tbl, n_real = _table_matrix(table)
    out = fused_inject_pallas(
        addr, time, valid, table_planes(tbl), _reach_col(reach, n_chips),
        jnp.asarray(t0, jnp.int32).reshape(1, 1),
        n_real=n_real, fanout=table.fanout, n_chips=n_chips,
        buckets_per_chip=buckets_per_chip, capacity=capacity, mode=mode,
        time_window=time_window, interpret=interpret)
    return _reorient(*out, n_chips=n_chips,
                     buckets_per_chip=buckets_per_chip, mode=mode)


@functools.partial(jax.jit, static_argnames=(
    "event_capacity", "n_chips", "buckets_per_chip", "capacity", "mode",
    "time_window", "interpret"))
def fused_lif_inject(
    v: jax.Array,                  # f32[N]
    refrac: jax.Array,             # int32[N]
    currents: jax.Array,           # f32[B, N]
    params,                        # repro.snn.neuron.LIFParams
    table: rt.RoutingTable,
    reach,
    t0,
    *,
    event_capacity: int,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str = "simplified",
    time_window: int = 1,
    interpret: bool | None = None,
) -> FusedLifInjectOut:
    interpret = resolve_interpret(interpret)
    n = currents.shape[1]
    # Neuron-lane padding: pad lanes sit at v == v_th == 0 with tau == 1,
    # so the strict threshold can never fire them.
    row = lambda x, val, dt: _pad_to(
        jnp.broadcast_to(jnp.asarray(x, dt), (n,)).reshape(1, n),
        LANES, 1, val)
    params_f = jnp.concatenate([
        row(params.tau_m, 1, jnp.float32), row(params.v_th, 0, jnp.float32),
        row(params.v_reset, 0, jnp.float32),
        row(params.v_rest, 0, jnp.float32)], axis=0)
    tbl, n_real = _table_matrix(table)
    out = fused_lif_inject_pallas(
        row(v, 0, jnp.float32), row(refrac, 0, jnp.int32),
        _pad_to(currents.astype(jnp.float32), LANES, 1, 0),
        params_f, row(params.refrac, 0, jnp.int32),
        table_planes(tbl), _reach_col(reach, n_chips),
        jnp.asarray(t0, jnp.int32).reshape(1, 1),
        event_capacity=event_capacity, n_real=n_real, fanout=table.fanout,
        n_chips=n_chips, buckets_per_chip=buckets_per_chip,
        capacity=capacity, mode=mode, time_window=time_window,
        interpret=interpret)
    v_out, refrac_out, spikes, voltage = out[:4]
    inject = _reorient(*out[4:], n_chips=n_chips,
                       buckets_per_chip=buckets_per_chip, mode=mode)
    return FusedLifInjectOut(
        v=v_out[0, :n], refrac=refrac_out[0, :n], spikes=spikes[:, :n],
        voltage=voltage[:, :n], inject=inject)
