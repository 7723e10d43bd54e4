"""PulseFabric — the unified, transport-agnostic pulse-communication engine.

One step implementation for the paper's whole pipeline

    events → routing LUT → bucket aggregation → [credit gate]
           → network exchange → [stateful merge queue] → delay ring

replaces the two hand-duplicated entry points that used to live in
``pulse_comm`` (``comm_step`` for shard_map, ``multi_chip_step`` for a
single device).  The per-chip body is written once against the
:class:`repro.core.transport.Transport` protocol; the single-device "local"
path runs the *same body* under an internal ``jax.vmap`` with a named axis,
where ``jax.lax`` collectives batch to exactly the explicit chip-axis
transpose the old local path performed — so local and shard_map execution
are bitwise identical by construction (tests/test_fabric.py).

Transports are resolved through a small registry::

    PulseFabric(cfg, transport="local")            # single device, chip axis
    PulseFabric(cfg, transport="shard_map")        # inside shard_map("chip")
    PulseFabric(cfg, transport=("pod", "chip"))    # hierarchical 2-stage mesh
    PulseFabric(cfg, transport=my_transport)       # any Transport instance

New transports register via :func:`register_transport`.

The NHTL-Extoll credit protocol (``repro.core.flowcontrol``, paper §2.1) is
wired in as an optional back-pressure stage: with a
:class:`FlowControlConfig`, credits gate how many packed buckets a chip may
inject into the network per step, and the consumer side returns
``drain_rate`` credits per step.  Buckets without credits are withheld at
the source; with ``retransmit_depth > 0`` their events wait in a bounded
send queue and are re-offered next step (only queue overflow drops, into
``CommStats.stalled``), otherwise they are dropped *with explicit
accounting* in ``stalled`` (the same drop-and-account model as bucket
overflow).

The network itself defaults to a dense crossbar, but any
:class:`repro.core.topology.Topology` (ring / torus / switch tree) can be
passed as the transport: the wire-word slabs are then forwarded hop by hop
through the modeled switched fabric, per-link occupancy lands in
``CommStats.link_words`` / ``link_backlog`` and the modeled path latency
shifts the on-wire deadlines.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import buckets as bk
from repro.core import delays as dl
from repro.core import events as ev
from repro.core import flowcontrol as fc
from repro.core import merge as mg
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.core import topology as tpo
from repro.core import transport as tp
from repro.obs.trace import phase_scope

# Axis name used by the internal vmap of the local path.  Deliberately
# obscure so it cannot collide with a user's mesh axis inside shard_map.
LOCAL_AXIS = "_pulse_fabric_chip"


@dataclasses.dataclass(frozen=True)
class FlowControlConfig:
    """Credit-based back-pressure at the injection point (paper §2.1).

    capacity        — ring-buffer slots at the consumer == max packets in
                      flight;
    drain_rate      — packets the consumer retires (credits returned) per
                      step;
    retransmit_depth — when > 0, credit-stalled events are held in a
                      bounded per-chip send queue and re-offered to the
                      routing/aggregation stage next step (the real NHTL
                      producer's send queue) instead of being dropped.
                      Only queue overflow beyond this depth drops into
                      ``CommStats.stalled``, so conservation
                      ``injected == delivered + queued + stalled_dropped``
                      holds (property-pinned in tests/test_fabric.py).
                      0 keeps the historical drop-and-account behavior.
    """

    capacity: int = 8
    drain_rate: int = 2
    retransmit_depth: int = 0


@dataclasses.dataclass(frozen=True)
class TransportBinding:
    """A resolved transport: the instance plus how the fabric drives it.

    ``batched`` — True when step inputs carry an explicit leading chip axis
    and the body must run under the fabric's internal vmap (local path);
    False when the caller already provides per-chip (shard-local) views.
    """

    transport: tp.Transport
    batched: bool = False


TransportFactory = Callable[[pc.PulseCommConfig], TransportBinding]

_REGISTRY: dict[str, TransportFactory] = {}


def register_transport(name: str, factory: TransportFactory) -> None:
    """Register a named transport. ``factory(cfg) -> TransportBinding``."""
    _REGISTRY[name] = factory


def available_transports() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_transport(
    "local",
    lambda cfg: TransportBinding(
        tp.ShardMapTransport(axis=LOCAL_AXIS, n_chips=cfg.n_chips),
        batched=True,
    ),
)
register_transport(
    "shard_map",
    lambda cfg: TransportBinding(
        tp.ShardMapTransport(axis="chip", n_chips=cfg.n_chips)
    ),
)


def _resolve(
    cfg: pc.PulseCommConfig,
    spec: str | tuple[str, ...] | tp.Transport | TransportBinding,
) -> TransportBinding:
    if isinstance(spec, TransportBinding):
        return spec
    if isinstance(spec, str):
        try:
            factory = _REGISTRY[spec]
        except KeyError:
            raise ValueError(
                f"unknown transport {spec!r}; registered: "
                f"{available_transports()}"
            ) from None
        return factory(cfg)
    if isinstance(spec, tpo.Topology):
        # A network topology: route the wire-word slabs hop by hop on the
        # local path (same internal-vmap axis as transport="local", so
        # local ≡ shard_map stays bitwise).  For shard_map use, pass
        # ``topology.transport(axis="chip")`` (an instance) instead.
        if spec.n_chips != cfg.n_chips:
            raise ValueError(
                f"topology has {spec.n_chips} chips, config {cfg.n_chips}")
        return TransportBinding(
            tpo.RoutedTransport(topology=spec, axis=LOCAL_AXIS),
            batched=True,
        )
    if isinstance(spec, tuple) and all(isinstance(a, str) for a in spec):
        # Tuple of mesh-axis names: hierarchical shard_map exchange
        # (innermost axis first — pod-local links, then cross-pod).
        return TransportBinding(
            tp.ShardMapTransport(axis=spec, n_chips=cfg.n_chips)
        )
    if hasattr(spec, "all_to_all"):
        return TransportBinding(spec)
    raise TypeError(f"cannot resolve transport from {spec!r}")


def _chip_major(events: ev.EventBuffer, chip_axis: int) -> ev.EventBuffer:
    """Move the chip axis of a batched event block to the front.

    The local fabric vmaps its per-chip body over chips, and a Pallas
    kernel under vmap gains its batch axis wherever its operand carried
    it; on a TPU a block may not split the last two dimensions of an
    array, so the chip axis must lead.
    """
    return jax.tree.map(lambda x: jnp.moveaxis(x, chip_axis, 0), events)


class FabricResult(NamedTuple):
    """What one fabric step returns.

    ``flow`` is None when flow control is off; ``merge`` is None unless the
    stateful merge stage is active (mode="full" with merge_rate > 0);
    ``sendq`` is None unless the flow config enables the bounded
    retransmit queue (``retransmit_depth > 0``).  All three are carries:
    thread them into the next :meth:`PulseFabric.step`.

    ``pending`` is the pipelined schedule's in-flight carry (a
    :class:`repro.core.pulse_comm.PipelineCarry`): None from the serial
    drivers (:meth:`PulseFabric.step` / :meth:`PulseFabric.superstep`),
    the issued-but-undrained block from :meth:`PulseFabric.
    pipeline_block` — thread it into the next pipelined call and flush it
    with :meth:`PulseFabric.flush_pending` at the end of a run.  Note the
    field is appended: positional construction of pre-pipeline
    FabricResults keeps working, but code that built results positionally
    AND passed ``pending`` must use keywords.
    """

    ring: dl.DelayRing
    delivered: pc.Delivered
    stats: pc.CommStats
    flow: fc.RingState | None
    merge: mg.MergeBuffer | None = None
    sendq: fc.SendQueue | None = None
    pending: pc.PipelineCarry | None = None


class PulseFabric:
    """The engine: one transport-agnostic pulse-communication step.

    ``step(events, table, ring[, flow])`` runs the full pipeline.  With
    ``transport="local"`` all arguments carry a leading chip axis and the
    cross-chip exchange happens inside an internal vmap; with a shard_map /
    instance transport the arguments are shard-local per-chip views and the
    exchange is a real collective.  Semantics (both modes, stats, merge
    rate-limiting, flow control) are defined exactly once, in
    :meth:`_chip_step`.
    """

    def __init__(
        self,
        cfg: pc.PulseCommConfig,
        transport: (str | tuple[str, ...] | tp.Transport
                    | TransportBinding) = "local",
        *,
        flow: FlowControlConfig | None = None,
        healthy=None,
        dead_links=(),
    ):
        self.cfg = cfg
        self.flow = flow
        self._spec = transport
        self.healthy = tpo.normalize_healthy(cfg.n_chips, healthy)
        if self.healthy is not None and len(self.healthy) == cfg.n_chips:
            self.healthy = None
        self.dead_links = tpo.normalize_dead_links(dead_links)
        self._binding = _resolve(cfg, transport)
        # Degraded execution: rebind a routed transport onto the plan
        # recompiled around the failures, and build the static
        # deliverability table the injection stage culls against (events
        # whose source/destination/route is dead never touch the wire —
        # they drop into ``CommStats.lost_to_failure``).
        self._deliverable = None
        if self.healthy is not None or self.dead_links:
            alive = np.ones(cfg.n_chips, bool)
            if self.healthy is not None:
                alive[:] = False
                alive[list(self.healthy)] = True
            tr = self._binding.transport
            if isinstance(tr, tpo.RoutedTransport):
                tr = tr.with_health(self.healthy, self.dead_links)
                self._binding = dataclasses.replace(
                    self._binding, transport=tr)
                reach = tr.plan.hops >= 0
            else:
                if self.dead_links:
                    raise ValueError(
                        "dead_links need a routed topology transport; "
                        "dense transports model no individual links")
                reach = np.ones((cfg.n_chips, cfg.n_chips), bool)
            self._deliverable = reach & alive[:, None] & alive[None, :]
        self._jit_cache: dict[str, Callable] = {}
        self.trace_counts: dict[str, int] = {}
        max_lat = int(getattr(self._binding.transport,
                              "max_path_latency", 0))
        if max_lat >= ev.TIME_MOD // 2:
            # The routed transport shifts the 8-bit on-wire timestamp by
            # the path latency.  Admitted words carry a deadline strictly
            # inside the future half-window (diff < 128); a shift below
            # 128 keeps diff + latency under 256, so an over-delayed word
            # wraps onto a *negative* difference and is counted expired at
            # deposit — it can never alias onto a future deadline.
            raise ValueError(
                f"transport path latency {max_lat} reaches the 8-bit wrap "
                f"half-window ({ev.TIME_MOD // 2}); a delivered word could "
                "alias onto a future deadline")
        if cfg.superstep > 1 and (
                cfg.superstep + max_lat + cfg.ring_depth
                >= ev.TIME_MOD // 2):
            # Extends the PulseCommConfig superstep + ring_depth guard by
            # the transport's modeled path latency: a word deferred for up
            # to superstep-1 steps, shifted by up to max_lat on the wire
            # and then held up to ring_depth steps in the ring must stay
            # inside the wrap half-window end to end, or a deferred
            # delivery could alias onto a future deadline instead of
            # expiring with accounting.
            raise ValueError(
                f"superstep {cfg.superstep} + transport path latency "
                f"{max_lat} + ring_depth {cfg.ring_depth} reaches the "
                f"8-bit wrap half-window ({ev.TIME_MOD // 2}); a deferred "
                "word could alias onto a future deadline — lower the "
                "superstep or shorten the topology's paths")

    @property
    def transport(self) -> tp.Transport:
        return self._binding.transport

    @property
    def batched(self) -> bool:
        return self._binding.batched

    def degrade(self, healthy=None, dead_links=()) -> "PulseFabric":
        """A new fabric on the same config/transport spec executing the
        route plan recompiled around the given failures — the recovery
        boundary's plan swap (carries are shape-compatible, so ring /
        flow / merge / sendq state threads straight across).  Compile-time
        route recompilation keeps the step function jit-static; swap
        fabrics between steps, never inside a trace."""
        return PulseFabric(self.cfg, self._spec, flow=self.flow,
                           healthy=healthy, dead_links=dead_links)

    # -- flow control -------------------------------------------------------

    def init_flow(self) -> fc.RingState | None:
        """Fresh credit state (per chip; batched over chips on the local
        path).  None when flow control is disabled."""
        if self.flow is None:
            return None
        state = fc.init(self.flow.capacity)
        if self.batched:
            state = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.cfg.n_chips,) + x.shape),
                state,
            )
        return state

    # -- temporal merge -----------------------------------------------------

    @property
    def merge_enabled(self) -> bool:
        """True when the stateful rate-limited merge stage runs (full mode
        with a positive merge_rate)."""
        return self.cfg.mode == "full" and self.cfg.merge_rate > 0

    def init_merge(self) -> mg.MergeBuffer | None:
        """Fresh (empty) merge queue per chip — batched over chips on the
        local path.  None when the merge stage is disabled."""
        if not self.merge_enabled:
            return None
        buf = mg.merge_init(self.cfg.merge_depth)
        if self.batched:
            buf = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.cfg.n_chips,) + x.shape),
                buf,
            )
        return buf

    # -- retransmit send queue ---------------------------------------------

    @property
    def sendq_enabled(self) -> bool:
        """True when credit-stalled events are queued for retransmission
        instead of dropped (flow control with retransmit_depth > 0)."""
        return self.flow is not None and self.flow.retransmit_depth > 0

    def init_sendq(self) -> fc.SendQueue | None:
        """Fresh (empty) retransmit queue per chip — batched over chips on
        the local path.  None when the retransmit queue is disabled."""
        if not self.sendq_enabled:
            return None
        q = fc.sendq_init(self.flow.retransmit_depth)
        if self.batched:
            q = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.cfg.n_chips,) + x.shape),
                q,
            )
        return q

    # -- superstep flush slab ----------------------------------------------

    def init_flushbuf(self) -> pc.FlushBuffer:
        """Fresh (empty) superstep flush slab per chip — batched over chips
        on the local path.  The slab is internal to :meth:`superstep` (each
        call covers one complete B-step block), exposed for inspection and
        tests."""
        buf = pc.flush_init(self.cfg)
        if self.batched:
            buf = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.cfg.n_chips,) + x.shape),
                buf,
            )
        return buf

    # -- cached jitted drivers ---------------------------------------------

    def _cached_jit(self, name: str, fn: Callable) -> Callable:
        """One persistent ``jax.jit`` wrapper per driver, cached on the
        fabric: repeated ``run``/benchmark iterations reuse the same
        executable instead of re-tracing per call (jit's own signature
        cache keys on input shapes/dtypes and carry structure).
        ``trace_counts[name]`` counts actual retraces — pinned in
        tests/test_superstep.py."""
        if name not in self._jit_cache:
            def traced(*args):
                self.trace_counts[name] = self.trace_counts.get(name, 0) + 1
                return fn(*args)

            self._jit_cache[name] = jax.jit(traced)
        return self._jit_cache[name]

    def jit_step(self) -> Callable:
        """Cached jitted :meth:`step` (positional arguments only)."""
        return self._cached_jit("step", self.step)

    def jit_superstep(self) -> Callable:
        """Cached jitted :meth:`superstep` (positional arguments only)."""
        return self._cached_jit("superstep", self.superstep)

    def _requeue(
        self, routed: rt.RoutedEvents, sendq: fc.SendQueue, now: jax.Array
    ) -> rt.RoutedEvents:
        """Re-offer queued events ahead of this step's fresh stream (age
        priority for bucket slots).  Queued words carry the 8-bit on-wire
        timestamp; the full deadline is reconstructed against the ring
        clock, so a word that expired while stalled fails the injection
        window next and drops into ``expired`` — a queued word is re-judged
        every step and can never age across the wrap unnoticed."""
        q_addr, _, q_valid = ev.decode_word(sendq.words)
        q_valid = q_valid & (sendq.dest >= 0)
        q_deadline = ev.word_deadline(sendq.words, now)
        cat = lambda q, r: jnp.concatenate([q, r])
        return rt.RoutedEvents(
            dest_chip=cat(jnp.where(q_valid, sendq.dest, 0),
                          routed.dest_chip),
            dest_addr=cat(q_addr, routed.dest_addr),
            deadline=cat(q_deadline, routed.deadline),
            valid=cat(q_valid, routed.valid),
        )

    def _gate(
        self,
        flow: fc.RingState,
        packed: bk.PackedBuckets,
    ) -> tuple[fc.RingState, bk.PackedBuckets, jax.Array,
               fc.SendQueue | None]:
        """Credit gate: inject only as many non-empty buckets as credits
        allow (lowest bucket index first).  Withheld buckets are pulled off
        the wire; without a retransmit queue their events are dropped at
        the source and counted in ``stalled``.  With
        ``retransmit_depth > 0`` they refill the send queue instead (FIFO
        over bucket-major lane order) and only the overflow beyond the
        queue depth drops into ``stalled``."""
        cfg = self.cfg
        ready = packed.counts > 0
        n_ready = jnp.sum(ready.astype(jnp.int32))
        flow, accepted = fc.produce(flow, n_ready)
        rank = jnp.cumsum(ready.astype(jnp.int32)) - ready.astype(jnp.int32)
        inject = ready & (rank < accepted)
        withheld = packed.valid & ~inject[:, None]

        sendq = None
        if self.sendq_enabled:
            depth = self.flow.retransmit_depth
            w_words = jnp.where(withheld, packed.words,
                                jnp.int32(ev.WORD_SENTINEL)).reshape(-1)
            # The word carries only the destination input row; recover the
            # destination chip from the bucket's static binding.
            w_dest = jnp.broadcast_to(
                (jnp.arange(cfg.n_buckets, dtype=jnp.int32)
                 // cfg.buckets_per_chip)[:, None],
                (cfg.n_buckets, cfg.bucket_capacity)).reshape(-1)
            held = w_words >= 0
            order = jnp.argsort(~held, stable=True)   # held lanes first
            pad = (jnp.full((depth,), ev.WORD_SENTINEL, jnp.int32),
                   jnp.full((depth,), -1, jnp.int32))
            q_words = jnp.concatenate([w_words[order], pad[0]])[:depth]
            q_dest = jnp.concatenate([w_dest[order], pad[1]])[:depth]
            q_dest = jnp.where(q_words >= 0, q_dest, -1)
            sendq = fc.SendQueue(words=q_words, dest=q_dest)
            n_withheld = jnp.sum(held.astype(jnp.int32))
            stalled = jnp.maximum(n_withheld - depth, 0).astype(jnp.int32)
        else:
            stalled = jnp.sum(withheld).astype(jnp.int32)

        packed = packed._replace(
            words=jnp.where(inject[:, None], packed.words,
                            jnp.int32(ev.WORD_SENTINEL)),
            counts=jnp.where(inject, packed.counts, 0),
        )
        # Consumer retires up to drain_rate packets -> credits come back
        # next step (notification conservation is property-tested in
        # tests/test_flowcontrol.py).
        flow, _ = fc.consume(flow, self.flow.drain_rate)
        return flow, packed, stalled, sendq

    # -- the single step / superstep body -----------------------------------

    def _chip_superstep(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None,
        merge: mg.MergeBuffer | None,
        sendq: fc.SendQueue | None,
    ) -> tuple[dl.DelayRing, pc.Delivered, pc.CommStats,
               fc.RingState | None, mg.MergeBuffer | None,
               fc.SendQueue | None]:
        """One complete B-step superstep block for one chip (B == the
        leading axis of ``events``; B=1 is the plain per-step schedule).

        Three phases — the exchange is launched exactly ONCE per block:

        1. *inject* (per substep k, clock ``t0 + k``): route, admit into
           the wrap window with the remaining deferral as extra slack,
           credit-gate, and flush-pack into column k of the FlushBuffer
           slab;
        2. *flush*: ONE fused collective moves the whole
           ``[n_buckets, B, capacity]`` slab (one ``all_to_all`` on a
           dense transport, one hop-forwarded batch on a routed one);
        3. *drain* (per substep k): replay the per-step schedule at the
           destination — merge substep k's arrivals against clock
           ``t0 + k`` and deposit with exactly the judgment the B=1
           schedule would have applied (``min_ahead`` guards the slots
           popped during the deferral).

        Because every admitted word carries more slack than its remaining
        wait, delivery is bitwise-equal to B separate steps
        (tests/test_superstep.py); the returned ``delivered`` / ``stats``
        carry a leading substep axis and ``ring.now`` is left at ``t0``
        (the caller owns the clock, exactly as for :meth:`step`).

        The three phases live in :meth:`_inject_block` (1),
        :func:`repro.core.pulse_comm.exchange_flush_issue` (2) and
        :meth:`_drain_block` (3) — the pipelined schedule
        (:meth:`_chip_pipeline_block`) reuses the same pieces but drains
        the *previous* block's issued exchange instead of its own.
        """
        t0 = ring.now
        with phase_scope("fabric/inject"):
            slab, inject, flow, sendq = self._inject_block(
                events, table, flow, sendq, t0)
        with phase_scope("fabric/exchange"):
            issued = pc.exchange_flush_issue(self.cfg, self.transport, slab)
        with phase_scope("fabric/drain"):
            ring, delivered, stats, merge = self._drain_block(
                ring, merge, issued, inject, t0)
        return ring, delivered, stats, flow, merge, sendq

    def _inject_block(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        flow: fc.RingState | None,
        sendq: fc.SendQueue | None,
        t0: jax.Array,
    ) -> tuple[jax.Array, pc.InjectStats, fc.RingState | None,
               fc.SendQueue | None]:
        """Phase 1 for one chip: per substep k (clock ``t0 + k``) route,
        admit into the wrap window with the remaining deferral as extra
        slack, credit-gate and flush-pack into column k of the FlushBuffer
        slab.  Returns ``(slab, inject_stats, flow, sendq)`` — the filled
        ``int32[n_buckets, B, capacity]`` slab plus the per-substep
        source-side accounting the drain later folds into CommStats.
        """
        cfg = self.cfg
        b = events.addr.shape[0]
        flushbuf = pc.flush_init(cfg)
        inject_stats = []
        reach_row = None
        if self._deliverable is not None:
            # This chip's row of the static deliverability table: False
            # where the destination (or every surviving route to it) is
            # dead under the installed health mask.
            reach_row = jnp.take(jnp.asarray(self._deliverable),
                                 self.transport.chip_index(), axis=0)

        if cfg.use_pallas and self.flow is None and table.fanout == 1:
            # Megakernel fast path: the whole B-substep inject chain in a
            # single pallas_call (repro.kernels.fused_inject), bitwise
            # equal to the loop below (tests/test_fused.py).  The credit
            # gate stays host-side (its feedback is sequential across
            # substeps), so flow-controlled fabrics take the unfused loop.
            slab, inject = self._inject_block_fused(events, table,
                                                    reach_row, t0)
            return slab, inject, flow, sendq

        for k in range(b):
            now_k = t0 + k
            defer_k = (b - 1) - k
            events_k = jax.tree.map(lambda x: x[k], events)
            routed = rt.route(events_k, table)
            # ``sent`` counts each substep's fresh stream only — a queued
            # event was counted when first offered, so run-level
            # conservation reads
            #   Σ sent == ring + expired + overflow + merge_dropped
            #             + stalled + lost_to_failure + final queue
            #             occupancies.
            sent = jnp.sum(routed.valid.astype(jnp.int32))
            if self.sendq_enabled:
                routed = self._requeue(routed, sendq, now_k)
            lost = jnp.int32(0)
            if reach_row is not None:
                # Cull after the requeue so replayed in-flight events bound
                # for a chip that died while they waited are accounted too;
                # before the wrap check so a culled event is never also
                # counted expired.  Out-of-range destinations keep their
                # historical drop path at the exchange.
                in_range = (routed.dest_chip >= 0) & (
                    routed.dest_chip < cfg.n_chips)
                ok = ~in_range | jnp.take(
                    reach_row, jnp.clip(routed.dest_chip, 0,
                                        cfg.n_chips - 1))
                lost = jnp.sum(routed.valid & ~ok).astype(jnp.int32)
                routed = routed._replace(valid=routed.valid & ok)
            # Enforce the 8-bit wrap contract at the injection boundary:
            # only deadlines strictly inside the future half-window
            # (defer < diff < 128) ride the wire word.  Later deadlines
            # would alias onto near ones and deposit ghost spikes 256
            # steps early; deadlines at or below the remaining deferral
            # (diff <= defer; defer == 0 for B=1, restoring the plain
            # diff > 0 window) would reach the ring only after their slot
            # was popped — undeliverable under the deferred exchange, so
            # they are dropped here with the same ``expired`` accounting
            # the pre-word path used, without ever touching the wire.
            diff = routed.deadline - now_k
            in_window = (diff > defer_k) & (diff < ev.TIME_MOD // 2)
            wrap_expired = jnp.sum(
                routed.valid & ~in_window).astype(jnp.int32)
            routed = routed._replace(valid=routed.valid & in_window)
            flushbuf, counts, overflow, traffic = pc.aggregate_into(
                cfg, routed, flushbuf, k)

            stalled = jnp.int32(0)
            if self.flow is not None:
                view = bk.PackedBuckets(
                    words=flushbuf.slab[:, k, :], counts=counts,
                    overflow=overflow)
                flow, view, stalled, sendq = self._gate(flow, view)
                flushbuf = flushbuf._replace(
                    slab=flushbuf.slab.at[:, k, :].set(view.words))
                counts = view.counts

            n_packets = jnp.sum((counts > 0).astype(jnp.int32))
            fill = jnp.minimum(counts, cfg.bucket_capacity)
            wire = (n_packets * pc.HEADER_BYTES
                    + jnp.sum(fill) * pc.EVENT_BYTES)
            inject_stats.append(dict(
                sent=sent, overflow=overflow, stalled=stalled,
                wrap_expired=wrap_expired, traffic=traffic, lost=lost,
                wire_bytes=wire.astype(jnp.int32),
                utilization=(fill.astype(jnp.float32).mean()
                             / float(cfg.bucket_capacity)),
            ))

        stack = lambda key: jnp.stack([s[key] for s in inject_stats])
        inject = pc.InjectStats(
            sent=stack("sent"), overflow=stack("overflow"),
            stalled=stack("stalled"), wrap_expired=stack("wrap_expired"),
            lost=stack("lost"), wire_bytes=stack("wire_bytes"),
            utilization=stack("utilization"), traffic=stack("traffic"))
        return flushbuf.slab, inject, flow, sendq

    def _inject_block_fused(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        reach_row: jax.Array | None,
        t0: jax.Array,
    ) -> tuple[jax.Array, pc.InjectStats]:
        """Single-launch inject path: route + reach cull + wrap window +
        flush-pack for all B substeps inside one kernel, the slab and all
        counters VMEM-resident across the block.  The wire-byte and
        utilization figures derive from the per-substep bucket counts with
        the same formulas as the unfused loop, so every InjectStats field
        is bitwise-identical.
        """
        from repro.kernels.fused_inject import ops as fi_ops

        cfg = self.cfg
        out = fi_ops.fused_inject(
            events, table, reach_row, t0,
            n_chips=cfg.n_chips, buckets_per_chip=cfg.buckets_per_chip,
            capacity=cfg.bucket_capacity, mode=cfg.mode,
            time_window=cfg.time_window)
        fill = jnp.minimum(out.counts, cfg.bucket_capacity)
        n_packets = jnp.sum((out.counts > 0).astype(jnp.int32), axis=1)
        wire = (n_packets * pc.HEADER_BYTES
                + jnp.sum(fill, axis=1) * pc.EVENT_BYTES)
        b = events.addr.shape[0]
        inject = pc.InjectStats(
            sent=out.sent, overflow=out.overflow,
            stalled=jnp.zeros((b,), jnp.int32),
            wrap_expired=out.wrap_expired, lost=out.lost,
            wire_bytes=wire.astype(jnp.int32),
            utilization=(fill.astype(jnp.float32).mean(axis=1)
                         / float(cfg.bucket_capacity)),
            traffic=out.traffic)
        return out.slab, inject

    def _drain_block(
        self,
        ring: dl.DelayRing,
        merge: mg.MergeBuffer | None,
        issued: pc.IssuedFlush,
        inject: pc.InjectStats,
        t0: jax.Array,
        *,
        extra_ahead: int = 0,
        valid: jax.Array | None = None,
    ) -> tuple[dl.DelayRing, pc.Delivered, pc.CommStats,
               mg.MergeBuffer | None]:
        """Phase 3 for one chip: complete the issued exchange and replay
        the per-step schedule at the destination — merge substep k's
        arrivals against clock ``t0 + k`` and deposit with exactly the
        judgment the B=1 schedule would have applied.

        ``extra_ahead`` widens the deposit guard for the pipelined
        schedule: a block drained one block late has had the *following*
        block's slots popped too, so deposits must clear ``B`` additional
        slots (``min_ahead = extra_ahead + defer_k``) — a word landing
        inside the already-popped window is expired with accounting
        instead of ghosting a ring revolution later.  ``valid`` (a scalar
        bool) gates the whole drain: an empty pipeline carry masks its
        words to sentinels and leaves the merge queue untouched, so the
        prologue block contributes nothing.
        """
        cfg = self.cfg
        delivered_words, link = pc.exchange_flush_complete(
            cfg, self.transport, issued)
        b = delivered_words.shape[0]
        if valid is not None:
            delivered_words = jnp.where(
                valid, delivered_words, jnp.int32(ev.WORD_SENTINEL))
        lost_drain = jnp.zeros((b,), jnp.int32)
        if self._deliverable is not None:
            # Already-exchanged words can still be addressed to a chip
            # that died while they were in flight (a pipeline carry
            # restored across a recovery boundary): cull arrivals at a
            # dead destination into lost_to_failure rather than silently
            # depositing them into a dead chip's ring.  On the serial
            # schedule nothing ever arrives at a dead chip (sources cull
            # at inject), so this is the identity there.
            me = self.transport.chip_index()
            dele = jnp.asarray(self._deliverable)
            alive_self = jnp.take(dele.reshape(-1),
                                  me * cfg.n_chips + me)
            lost_drain = jnp.where(
                alive_self, 0,
                jnp.sum(ev.word_valid(delivered_words).astype(jnp.int32),
                        axis=1))
            delivered_words = jnp.where(
                alive_self, delivered_words, jnp.int32(ev.WORD_SENTINEL))

        if cfg.use_pallas:
            # Megakernel fast path: merge + deposit for all B substeps in
            # a single pallas_call (repro.kernels.fused_drain) — the ring
            # and merge queue stay VMEM-resident across the block and the
            # gate (pipeline ``valid``) is applied in-kernel, replacing
            # the queue-revert below.  Bitwise equal to the unfused chain
            # (tests/test_fused.py).
            from repro.kernels.fused_drain import ops as fd_ops

            dmode = ("rate" if cfg.mode == "full" and self.merge_enabled
                     else "sort" if cfg.mode == "full" else "passthrough")
            fused = fd_ops.fused_drain(
                ring, delivered_words,
                merge.words if dmode == "rate" else None, t0,
                mode=dmode, rate=cfg.merge_rate, extra_ahead=extra_ahead,
                gate=valid)
            ring = fused.ring
            if dmode == "rate":
                merge = mg.MergeBuffer(words=fused.queue)
            out_words = fused.words
            dep_expired = fused.dep_expired
            merge_dropped = fused.dropped
        else:
            ring, out_words, dep_expired, merge_dropped, merge = (
                self._drain_block_unfused(ring, merge, delivered_words,
                                          t0, extra_ahead, valid))

        stats_steps = []
        for k in range(b):
            last = k == b - 1
            stats_steps.append(pc.CommStats(
                sent=inject.sent[k],
                overflow=inject.overflow[k],
                merge_dropped=jnp.asarray(merge_dropped[k], jnp.int32),
                expired=inject.wrap_expired[k] + dep_expired[k],
                stalled=inject.stalled[k],
                utilization=inject.utilization[k],
                wire_bytes=inject.wire_bytes[k],
                traffic=inject.traffic[k],
                # The collective fires once per block: its link occupancy
                # is attributed to the flush substep (zeros elsewhere).
                # Per-block link_words totals match the per-step schedule
                # exactly; link_backlog is judged at block granularity (B
                # rounds of capacity — deferral smooths per-step bursts,
                # so it is <= the per-step schedule's total).
                link_words=link.words if last else jnp.zeros_like(
                    link.words),
                link_backlog=link.backlog if last else jnp.zeros_like(
                    link.backlog),
                lost_to_failure=inject.lost[k] + lost_drain[k],
            ))

        delivered = pc.Delivered(words=out_words)
        stats = jax.tree.map(lambda *xs: jnp.stack(xs), *stats_steps)
        return ring, delivered, stats, merge

    def _drain_block_unfused(
        self,
        ring: dl.DelayRing,
        merge: mg.MergeBuffer | None,
        delivered_words: jax.Array,
        t0: jax.Array,
        extra_ahead: int,
        valid: jax.Array | None,
    ) -> tuple[dl.DelayRing, jax.Array, jax.Array, jax.Array,
               mg.MergeBuffer | None]:
        """The composed merge + per-substep deposit chain — the bitwise
        reference the fused drain kernel is pinned against.  Returns
        ``(ring, out_words[B, lanes], dep_expired[B], merge_dropped[B],
        merge)``.
        """
        cfg = self.cfg
        b = delivered_words.shape[0]
        merge_out = None
        merge_dropped = jnp.zeros((b,), jnp.int32)
        if cfg.mode == "full" and self.merge_enabled:
            # Stateful rate-limited merge: the B-step batch drains through
            # the persistent queue with per-step emission against each
            # substep's clock — congested events are *delayed to later
            # steps*, not destroyed, and only queue overflow beyond
            # merge_depth drops (counted per substep in merge_dropped), so
            # delivered == emitted + queued + dropped holds every substep
            # by construction.  The sort key comes straight from the low
            # bits of the words — no decode on the hot path.
            new_merge, merge_out, merge_dropped = mg.merge_drain_words(
                merge, delivered_words, now0=t0, rate=cfg.merge_rate,
                use_pallas=cfg.use_pallas,
            )
            if valid is not None:
                # An empty carry must not advance the merge queue (its
                # sentinel drain would still emit queued words).
                merge = jax.tree.map(
                    lambda n, o: jnp.where(valid, n, o), new_merge, merge)
                merge_out = jnp.where(valid, merge_out,
                                      jnp.int32(ev.WORD_SENTINEL))
                merge_dropped = jnp.where(valid, merge_dropped, 0)
            else:
                merge = new_merge

        out_words, dep_expired = [], []
        for k in range(b):
            now_k = t0 + k
            defer_k = (b - 1) - k
            if merge_out is not None:
                words_k = merge_out[k]
            elif cfg.mode == "full":
                words_k = mg.merge_words(delivered_words[k], now_k)
            else:
                words_k = delivered_words[k]
            ring, expired_k = dl.deposit_words(
                ring, words_k, now=now_k, min_ahead=extra_ahead + defer_k)
            out_words.append(words_k)
            dep_expired.append(expired_k)
        return (ring, jnp.stack(out_words), jnp.stack(dep_expired),
                merge_dropped, merge)

    def _chip_step(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None,
        merge: mg.MergeBuffer | None,
        sendq: fc.SendQueue | None,
    ) -> tuple[dl.DelayRing, pc.Delivered, pc.CommStats,
               fc.RingState | None, mg.MergeBuffer | None,
               fc.SendQueue | None]:
        """The per-step body: a superstep block of exactly one substep."""
        out = self._chip_superstep(
            jax.tree.map(lambda x: x[None], events), table, ring,
            flow, merge, sendq,
        )
        ring, delivered, stats, flow, merge, sendq = out
        squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
        return ring, squeeze(delivered), squeeze(stats), flow, merge, sendq

    # -- public API ---------------------------------------------------------

    def step(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None = None,
        merge: mg.MergeBuffer | None = None,
        sendq: fc.SendQueue | None = None,
    ) -> FabricResult:
        """One pulse-communication step.

        Local path: ``events [n_chips, E]``, ``table [n_chips, N, K]``,
        ``ring [n_chips, D, n_inputs]``.  Shard path: the same without the
        leading chip axis (call inside shard_map over the mesh axis).

        ``flow`` threads the credit state when flow control is configured,
        ``merge`` the persistent merge queue when the stateful merge stage
        is active and ``sendq`` the retransmit queue when
        ``flow.retransmit_depth > 0``; pass the previous step's
        ``FabricResult.flow`` / ``.merge`` / ``.sendq`` (auto-initialized
        on first use if omitted).

        With ``cfg.superstep > 1`` the exchange schedule is defined over
        whole B-step blocks, not single steps — drive the fabric through
        :meth:`superstep` (this method raises).
        """
        if self.cfg.superstep != 1:
            raise ValueError(
                f"cfg.superstep={self.cfg.superstep}: the exchange is "
                "batched over whole B-step blocks, so per-step driving is "
                "undefined — call superstep(events[B, ...], ...) (or "
                "snn.network.run, which blocks the scan automatically)")
        flow, merge, sendq = self._init_missing(flow, merge, sendq)
        if self.batched:
            ring, delivered, stats, flow, merge, sendq = jax.vmap(
                self._chip_step, axis_name=LOCAL_AXIS
            )(events, table, ring, flow, merge, sendq)
        else:
            ring, delivered, stats, flow, merge, sendq = self._chip_step(
                events, table, ring, flow, merge, sendq
            )
        return FabricResult(ring=ring, delivered=delivered, stats=stats,
                            flow=flow, merge=merge, sendq=sendq)

    def _init_missing(self, flow, merge, sendq):
        if self.flow is not None and flow is None:
            flow = self.init_flow()
        if self.merge_enabled and merge is None:
            merge = self.init_merge()
        if self.sendq_enabled and sendq is None:
            sendq = self.init_sendq()
        return flow, merge, sendq

    def superstep(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None = None,
        merge: mg.MergeBuffer | None = None,
        sendq: fc.SendQueue | None = None,
    ) -> FabricResult:
        """One B-step superstep block: B injections, ONE collective.

        ``events`` carries a leading substep axis of size
        ``cfg.superstep``: local path ``[B, n_chips, E]``, shard path
        ``[B, E]``.  Substep k runs at clock ``ring.now + k`` — the caller
        advances ``ring.now`` by B afterwards, exactly as it ticks once
        after :meth:`step` (``snn.network`` does this when restructuring
        its scan over blocks).  The returned ``delivered`` and ``stats``
        carry the same leading [B] axis (local: ``[B, n_chips, ...]``);
        carries (``flow`` / ``merge`` / ``sendq``) thread across blocks
        like they do across steps.

        Collective launches per simulated step drop from 1 to 1/B
        (HLO-pinned in tests/test_superstep.py); delivery stays
        bitwise-equal to the B=1 schedule because admission only puts
        events on the wire with more slack than their remaining deferral
        (see :meth:`_chip_superstep`).  Works for any ``cfg.superstep``
        including 1.
        """
        b = events.addr.shape[0]
        if b != self.cfg.superstep:
            raise ValueError(
                f"events carry {b} substeps, cfg.superstep is "
                f"{self.cfg.superstep}")
        flow, merge, sendq = self._init_missing(flow, merge, sendq)
        if self.batched:
            ring, delivered, stats, flow, merge, sendq = jax.vmap(
                self._chip_superstep, axis_name=LOCAL_AXIS,
                out_axes=(0, 1, 1, 0, 0, 0),
            )(_chip_major(events, 1), table, ring, flow, merge, sendq)
        else:
            ring, delivered, stats, flow, merge, sendq = (
                self._chip_superstep(events, table, ring, flow, merge,
                                     sendq))
        return FabricResult(ring=ring, delivered=delivered, stats=stats,
                            flow=flow, merge=merge, sendq=sendq)

    # -- pipelined superstep schedule ----------------------------------------

    @property
    def _n_ports(self) -> int:
        """Port count of the transport's per-exchange link stats (the
        leading dim a :class:`repro.core.pulse_comm.PipelineCarry`'s link
        leg must match)."""
        topo = getattr(self.transport, "topology", None)
        return topo.n_ports if topo is not None else 1

    def init_pending(self) -> pc.PipelineCarry:
        """An empty pipeline carry (``valid=False``) — batched over chips
        on the local path.  The prologue block of the pipelined schedule:
        draining it deposits nothing and contributes zero stats."""
        carry = pc.pipeline_init(self.cfg, self._n_ports)
        if self.batched:
            carry = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.cfg.n_chips,) + x.shape),
                carry,
            )
        return carry

    def _check_pipeline_guard(self) -> None:
        """Tighten the wrap guard for the pipelined schedule: a word now
        waits up to *two* blocks (its own deferral plus one block in the
        pipeline carry) before deposit, so the end-to-end wait
        ``2B + path latency + ring_depth`` must stay inside the 8-bit
        half-window or a carried word could alias onto a future deadline
        instead of expiring with accounting."""
        max_lat = int(getattr(self.transport, "max_path_latency", 0))
        if (2 * self.cfg.superstep + max_lat + self.cfg.ring_depth
                >= ev.TIME_MOD // 2):
            raise ValueError(
                f"pipelined schedule: 2*superstep ({2 * self.cfg.superstep})"
                f" + transport path latency {max_lat} + ring_depth "
                f"{self.cfg.ring_depth} reaches the 8-bit wrap half-window "
                f"({ev.TIME_MOD // 2}); an in-flight word could alias onto "
                "a future deadline — lower the superstep or shorten the "
                "topology's paths")

    def _chip_pipeline_block(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None,
        merge: mg.MergeBuffer | None,
        sendq: fc.SendQueue | None,
        pending: pc.PipelineCarry,
    ) -> tuple[dl.DelayRing, pc.Delivered, pc.CommStats,
               fc.RingState | None, mg.MergeBuffer | None,
               fc.SendQueue | None, pc.PipelineCarry]:
        """One pipelined stage for one chip: inject and *issue* block f,
        drain block f−1 (the incoming carry).

        Program order per stage — the scheduling contract pinned in
        tests/test_pipeline.py:

        1. inject block f into the live slab (compute only);
        2. issue block f's exchange — every collective launches HERE,
           before any drain op;
        3. complete + drain block f−1 from ``pending`` (destination-side
           elementwise work: latency shift, merge, deposit).

        The issued-but-undrained block f becomes the outgoing carry.  Its
        drain replays the per-step schedule one block late, so deposits
        must clear the slots popped during the extra block
        (``extra_ahead=B`` in :meth:`_drain_block`); delivery stays
        bitwise-equal to the serial schedule whenever every admitted word
        carries more slack than the two-block wait (min delay + path
        latency > 2B−1), which the serial admission window plus the
        pipeline wrap guard make the common case.  The returned
        ``delivered`` / ``stats`` describe block f−1 — one block behind
        the inputs, realigned by :meth:`run_pipelined`'s epilogue.
        """
        b = events.addr.shape[0]
        t0 = ring.now
        with phase_scope("fabric/inject"):
            slab, inject, flow, sendq = self._inject_block(
                events, table, flow, sendq, t0)
        with phase_scope("fabric/exchange"):
            issued = pc.exchange_flush_issue(self.cfg, self.transport, slab)
        with phase_scope("fabric/drain"):
            ring, delivered, stats, merge = self._drain_block(
                ring, merge,
                pc.IssuedFlush(words=pending.words, link=pending.link),
                pending.inject, pending.t0,
                extra_ahead=b, valid=pending.valid)
        pending = pc.PipelineCarry(
            words=issued.words, link=issued.link, inject=inject,
            t0=jnp.asarray(t0, jnp.int32),
            valid=jnp.ones_like(pending.valid))
        return ring, delivered, stats, flow, merge, sendq, pending

    def _chip_flush_pending(
        self,
        ring: dl.DelayRing,
        merge: mg.MergeBuffer | None,
        pending: pc.PipelineCarry,
    ) -> tuple[dl.DelayRing, pc.Delivered, pc.CommStats,
               mg.MergeBuffer | None, pc.PipelineCarry]:
        """Epilogue for one chip: drain the carried block with the *serial*
        deposit guard (``extra_ahead=0`` — nothing popped its slots beyond
        the in-block deferral, exactly as if the serial schedule had
        drained it in place) and return a reset (empty) carry."""
        with phase_scope("fabric/flush"):
            ring, delivered, stats, merge = self._drain_block(
                ring, merge,
                pc.IssuedFlush(words=pending.words, link=pending.link),
                pending.inject, pending.t0,
                extra_ahead=0, valid=pending.valid)
        empty = pc.PipelineCarry(
            words=jnp.full_like(pending.words, ev.WORD_SENTINEL),
            link=jax.tree.map(jnp.zeros_like, pending.link),
            inject=jax.tree.map(jnp.zeros_like, pending.inject),
            t0=jnp.zeros_like(pending.t0),
            valid=jnp.zeros_like(pending.valid),
        )
        return ring, delivered, stats, merge, empty

    def _chip_run_pipelined(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None,
        merge: mg.MergeBuffer | None,
        sendq: fc.SendQueue | None,
    ):
        """Scan :meth:`_chip_pipeline_block` over F blocks, then flush.

        The scan's slot f drains block f−1 (slot 0 drains the empty
        prologue), so the per-block outputs are realigned by dropping
        slot 0 and appending the epilogue flush — the result is indexed
        by block exactly like F serial supersteps.  The clock advances
        internally (``ring.now + B`` per block); on return ``ring.now``
        sits at ``t0 + F*B``."""
        b = events.addr.shape[1]
        pending = pc.pipeline_init(self.cfg, self._n_ports)

        def body(carry, events_f):
            ring, flow, merge, sendq, pending = carry
            ring, delivered, stats, flow, merge, sendq, pending = (
                self._chip_pipeline_block(
                    events_f, table, ring, flow, merge, sendq, pending))
            ring = dl.DelayRing(ring=ring.ring, now=ring.now + b)
            return (ring, flow, merge, sendq, pending), (delivered, stats)

        carry, scanned = jax.lax.scan(
            body, (ring, flow, merge, sendq, pending), events)
        ring, flow, merge, sendq, pending = carry
        ring, f_del, f_stats, merge, pending = self._chip_flush_pending(
            ring, merge, pending)
        realign = lambda s, last: jax.tree.map(
            lambda a, z: jnp.concatenate([a[1:], z[None]], axis=0), s, last)
        delivered = realign(scanned[0], f_del)
        stats = realign(scanned[1], f_stats)
        return ring, delivered, stats, flow, merge, sendq, pending

    def jit_pipeline_block(self) -> Callable:
        """Cached jitted :meth:`pipeline_block` (positional args only)."""
        return self._cached_jit("pipeline_block", self.pipeline_block)

    def jit_run_pipelined(self) -> Callable:
        """Cached jitted :meth:`run_pipelined` (positional args only)."""
        return self._cached_jit("run_pipelined", self.run_pipelined)

    def pipeline_block(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None = None,
        merge: mg.MergeBuffer | None = None,
        sendq: fc.SendQueue | None = None,
        pending: pc.PipelineCarry | None = None,
    ) -> FabricResult:
        """One stage of the pipelined superstep schedule.

        Same signature and clock contract as :meth:`superstep` (substep k
        at ``ring.now + k``, caller advances ``ring.now`` by B) plus the
        ``pending`` carry: the stage injects and *issues* this block's
        exchange, and completes + drains the carried previous block.  The
        returned ``delivered`` / ``stats`` therefore describe the
        *previous* block (zeros / sentinels on the first call, whose carry
        is the empty prologue); the new carry rides in
        ``FabricResult.pending`` — thread it into the next call and
        :meth:`flush_pending` it at the end of the run.  Use
        :meth:`run_pipelined` when the whole block sequence is available
        up front; this method exists for streaming drivers
        (``snn.network`` feeds one block per outer-scan step) and for
        checkpoint/recovery boundaries, where the carry must be visible.
        """
        b = events.addr.shape[0]
        if b != self.cfg.superstep:
            raise ValueError(
                f"events carry {b} substeps, cfg.superstep is "
                f"{self.cfg.superstep}")
        self._check_pipeline_guard()
        flow, merge, sendq = self._init_missing(flow, merge, sendq)
        if pending is None:
            pending = self.init_pending()
        if self.batched:
            out = jax.vmap(
                self._chip_pipeline_block, axis_name=LOCAL_AXIS,
                out_axes=(0, 1, 1, 0, 0, 0, 0),
            )(_chip_major(events, 1), table, ring, flow, merge, sendq,
              pending)
        else:
            out = self._chip_pipeline_block(
                events, table, ring, flow, merge, sendq, pending)
        ring, delivered, stats, flow, merge, sendq, pending = out
        return FabricResult(ring=ring, delivered=delivered, stats=stats,
                            flow=flow, merge=merge, sendq=sendq,
                            pending=pending)

    def flush_pending(
        self,
        ring: dl.DelayRing,
        pending: pc.PipelineCarry,
        flow: fc.RingState | None = None,
        merge: mg.MergeBuffer | None = None,
        sendq: fc.SendQueue | None = None,
    ) -> FabricResult:
        """Epilogue: drain the in-flight carry (no inject, no collective).

        Completes and drains the carried block against its own clock
        (``pending.t0``) with the serial deposit guard, returning its
        ``delivered`` / ``stats`` and an empty reset carry.  ``flow`` and
        ``sendq`` pass through untouched (flushing moves no new events
        through the credit gate)."""
        if self.merge_enabled and merge is None:
            merge = self.init_merge()
        if self.batched:
            ring, delivered, stats, merge, pending = jax.vmap(
                self._chip_flush_pending, axis_name=LOCAL_AXIS,
                in_axes=(0, 0, 0), out_axes=(0, 1, 1, 0, 0),
            )(ring, merge, pending)
        else:
            ring, delivered, stats, merge, pending = (
                self._chip_flush_pending(ring, merge, pending))
        return FabricResult(ring=ring, delivered=delivered, stats=stats,
                            flow=flow, merge=merge, sendq=sendq,
                            pending=pending)

    def run_pipelined(
        self,
        events: ev.EventBuffer,
        table: rt.RoutingTable,
        ring: dl.DelayRing,
        flow: fc.RingState | None = None,
        merge: mg.MergeBuffer | None = None,
        sendq: fc.SendQueue | None = None,
    ) -> FabricResult:
        """Run F pipelined superstep blocks end to end: prologue, F−1
        steady-state stages (block f's exchange issued before block f−1's
        drain, concurrent with block f+1's inject under the XLA
        scheduler), epilogue flush.

        ``events`` carries leading [F, B] axes: local path
        ``[F, B, n_chips, E]``, shard path ``[F, B, E]``.  The returned
        ``delivered`` / ``stats`` are realigned to blocks — element f is
        exactly block f, bitwise-equal to F serial :meth:`superstep`
        calls whenever every admitted word has ``delay + path latency >
        2B−1`` (tests/test_pipeline.py pins this for the repo's standard
        workloads).  Unlike :meth:`superstep`, the clock advances
        internally: on return ``ring.now == t0 + F*B`` and
        ``FabricResult.pending`` is the empty reset carry.  For streaming
        or recovery-aware drivers, use :meth:`pipeline_block` /
        :meth:`flush_pending` directly.
        """
        if events.addr.ndim < 2 or events.addr.shape[1] != (
                self.cfg.superstep):
            raise ValueError(
                f"events must carry [F, B={self.cfg.superstep}, ...] "
                f"leading axes, got shape {events.addr.shape}")
        self._check_pipeline_guard()
        flow, merge, sendq = self._init_missing(flow, merge, sendq)
        if self.batched:
            out = jax.vmap(
                self._chip_run_pipelined, axis_name=LOCAL_AXIS,
                out_axes=(0, 2, 2, 0, 0, 0, 0),
            )(_chip_major(events, 2), table, ring, flow, merge, sendq)
        else:
            out = self._chip_run_pipelined(
                events, table, ring, flow, merge, sendq)
        ring, delivered, stats, flow, merge, sendq, pending = out
        return FabricResult(ring=ring, delivered=delivered, stats=stats,
                            flow=flow, merge=merge, sendq=sendq,
                            pending=pending)
