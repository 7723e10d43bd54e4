"""jnp reference for the fused inject path — the composed single-op chain.

This is, op for op, what :meth:`repro.core.fabric.PulseFabric._inject_block`
does per substep on the no-flow-control path: route through the LUT, cull
unreachable destinations, admit into the 8-bit wrap window with the
remaining deferral as extra slack, and flush-pack into column ``k`` of the
``int32[n_buckets, B, capacity]`` slab.  The Pallas megakernel
(kernel.py) must reproduce it bitwise — tests/test_kernels.py drives both
on hypothesis-generated edge cases, and the fabric keeps this chain as its
path off the TPU and for credit-gated fabrics.

The LIF-fronted variant (:func:`fused_lif_inject_ref`) prepends exactly
the phase-1 substep chain of :func:`repro.snn.network._block_impl`:
``neuron.lif_step`` dynamics, spike detect, and the stable
``events.from_spikes`` compaction (capacity truncation included) — so the
full megakernel from membrane update to flush slab has a one-call oracle.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import buckets as bk
from repro.core import events as ev
from repro.core import routing as rt
from repro.core import transport as tp


class FusedInjectOut(NamedTuple):
    """Everything the drain needs from one injected block.

    slab         : int32[n_buckets, B, capacity] filled flush slab
    counts       : int32[B, n_buckets] pre-overflow fill levels
    sent         : int32[B]  fresh routed events offered per substep
    overflow     : int32[B]  bucket-capacity drops
    wrap_expired : int32[B]  admission-window drops
    lost         : int32[B]  culled by the health mask
    traffic      : int32[B, n_chips] destination traffic matrix rows
    """

    slab: jax.Array
    counts: jax.Array
    sent: jax.Array
    overflow: jax.Array
    wrap_expired: jax.Array
    lost: jax.Array
    traffic: jax.Array


def _bucket_ids(dest_chip, deadline, *, n_chips, buckets_per_chip, mode,
                time_window):
    if mode == "simplified":
        return bk.static_bucket_ids(dest_chip, n_chips=n_chips,
                                    streams=buckets_per_chip)
    return bk.dynamic_bucket_ids(dest_chip, deadline, n_chips=n_chips,
                                 pool_per_chip=buckets_per_chip,
                                 window=time_window)


def fused_inject_ref(
    events: ev.EventBuffer,        # [B, E] leading substep axis
    table: rt.RoutingTable,
    reach: jax.Array,              # bool[n_chips] reachable destinations
    t0: jax.Array,
    *,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str = "simplified",
    time_window: int = 1,
) -> FusedInjectOut:
    """Composed single-op reference chain over all B substeps."""
    b = events.addr.shape[0]
    n_buckets = n_chips * buckets_per_chip
    slab = ev.sentinel_words((n_buckets, b, capacity))
    out = {f: [] for f in ("counts", "sent", "overflow", "wrap_expired",
                           "lost", "traffic")}
    for k in range(b):
        now_k = t0 + k
        defer_k = (b - 1) - k
        routed = rt.route(jax.tree.map(lambda x: x[k], events), table)
        out["sent"].append(jnp.sum(routed.valid.astype(jnp.int32)))
        reach_row = (jnp.ones((n_chips,), bool) if reach is None
                     else jnp.asarray(reach).astype(bool))
        in_range = (routed.dest_chip >= 0) & (routed.dest_chip < n_chips)
        ok = ~in_range | jnp.take(reach_row,
                                  jnp.clip(routed.dest_chip, 0, n_chips - 1))
        out["lost"].append(jnp.sum(routed.valid & ~ok).astype(jnp.int32))
        routed = routed._replace(valid=routed.valid & ok)
        diff = routed.deadline - now_k
        in_window = (diff > defer_k) & (diff < ev.TIME_MOD // 2)
        out["wrap_expired"].append(
            jnp.sum(routed.valid & ~in_window).astype(jnp.int32))
        routed = routed._replace(valid=routed.valid & in_window)
        bucket_id = _bucket_ids(routed.dest_chip, routed.deadline,
                                n_chips=n_chips,
                                buckets_per_chip=buckets_per_chip,
                                mode=mode, time_window=time_window)
        slab, counts, overflow = bk.flush_pack(
            bucket_id, routed.dest_addr, routed.deadline, routed.valid,
            slab=slab, capacity=capacity, substep=k)
        out["counts"].append(counts)
        out["overflow"].append(overflow)
        out["traffic"].append(tp.exchange_matrix(routed.dest_chip,
                                                 routed.valid, n_chips))
    stack = lambda f: jnp.stack(out[f])
    return FusedInjectOut(slab=slab, counts=stack("counts"),
                          sent=stack("sent"), overflow=stack("overflow"),
                          wrap_expired=stack("wrap_expired"),
                          lost=stack("lost"), traffic=stack("traffic"))


class FusedLifInjectOut(NamedTuple):
    """LIF-fronted megakernel outputs: neuron trajectory plus the block."""

    v: jax.Array           # f32[N] membrane after the block
    refrac: jax.Array      # int32[N]
    spikes: jax.Array      # f32[B, N] per-substep spike indicators
    voltage: jax.Array     # f32[B, N] post-update membrane trajectory
    inject: FusedInjectOut


def fused_lif_inject_ref(
    v: jax.Array,
    refrac: jax.Array,
    currents: jax.Array,           # f32[B, N] precomputed input currents
    params,                        # repro.snn.neuron.LIFParams
    table: rt.RoutingTable,
    reach: jax.Array,
    t0: jax.Array,
    *,
    event_capacity: int,
    n_chips: int,
    buckets_per_chip: int,
    capacity: int,
    mode: str = "simplified",
    time_window: int = 1,
) -> FusedLifInjectOut:
    """LIF dynamics + spike detect + compaction + the inject chain.

    ``currents`` must be precomputable for the whole block — true under
    the superstep admission contract: no event injected inside the block
    can be delivered inside it, so ring pops (hence crossbar currents)
    never depend on this block's own injections.
    """
    from repro.snn import neuron as nr

    b, n = currents.shape
    state = nr.LIFState(v=v, refrac=refrac)
    ebs, spikes, voltage = [], [], []
    for k in range(b):
        state, spk = nr.lif_step(state, currents[k], params)
        spikes.append(spk)
        voltage.append(state.v)
        eb, _ = ev.from_spikes(spk > 0.5, t0 + k, event_capacity)
        ebs.append(eb)
    events = jax.tree.map(lambda *xs: jnp.stack(xs), *ebs)
    inject = fused_inject_ref(
        events, table, reach, t0, n_chips=n_chips,
        buckets_per_chip=buckets_per_chip, capacity=capacity, mode=mode,
        time_window=time_window)
    return FusedLifInjectOut(v=state.v, refrac=state.refrac,
                             spikes=jnp.stack(spikes),
                             voltage=jnp.stack(voltage), inject=inject)
