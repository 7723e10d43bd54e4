"""PulseComm — the paper's inter-chip pulse-communication pipeline.

Composes the stages of Fig. 2 into one functional step, per chip:

    spikes → events → routing LUT → (deadline) → bucket aggregation
           → network exchange (all_to_all / ppermute) → [merge] → delay ring

Two operating modes:

* ``simplified`` — the paper's scaled-down prototype: the destination lookup
  yields a bucket index directly, network addresses are statically
  configured in the buckets, and **no temporal merging** is performed
  (delivery scatters straight into the delay ring, which is order-free).
* ``full`` — the complete scheme of [arXiv:2111.15296] this paper adapts:
  dynamic bucket *renaming* (pool keyed by destination × time-window) and a
  time-ordered merge stage at the destination, optionally rate-limited to
  model merge congestion.

The same code runs per-shard under ``shard_map`` (ShardMapTransport → real
ICI collectives; this is what the dry-run lowers) and on a single device
with a leading chip axis (LocalTransport; CPU tests).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import buckets as bk
from repro.core import delays as dl
from repro.core import events as ev
from repro.core import merge as mg
from repro.core import routing as rt
from repro.core import transport as tp
from repro.obs.trace import phase_scope

# On-wire cost model (bytes). A pulse event is 14-bit address + 8-bit
# timestamp packed into ONE wire word (paper §2) -> 3 bytes, padded to 4 on
# the int32 datapath — and since the fabric now exchanges exactly that one
# word slab per step, EVENT_BYTES matches what the transport actually moves.
# The pre-word SoA fabric exchanged three int32 arrays (addr / deadline /
# valid) per event lane, i.e. SOA_EVENT_BYTES per event — kept for
# before/after wire accounting in the benchmarks.  An Extoll packet carries
# ~32 bytes of header+CRC framing.
WORD_BYTES = 4
EVENT_BYTES = WORD_BYTES
SOA_EVENT_BYTES = 3 * WORD_BYTES   # legacy three-array wire format
HEADER_BYTES = 32


@dataclasses.dataclass(frozen=True)
class PulseCommConfig:
    n_chips: int
    neurons_per_chip: int = 512       # HICANN-X: 512 AdEx neurons
    n_inputs_per_chip: int = 256      # synapse rows (input labels)
    event_capacity: int = 256         # E: per-step event budget per chip
    fanout: int = 1                   # routing-LUT fan-out K
    bucket_capacity: int = 16         # C: events aggregated per packet
    buckets_per_chip: int = 1         # streams (simplified) / pool (full)
    ring_depth: int = 16              # delay-ring depth >= max axonal delay
    mode: str = "simplified"          # "simplified" | "full"
    merge_rate: int = 0               # full mode: events/step the merge emits
    merge_depth: int = 64             # full mode: merge-queue depth
    time_window: int = 4              # full mode: renaming window (steps)
    use_pallas: bool = False          # every Pallas kernel, on any backend
    superstep: int = 1                # B: sim steps batched per exchange

    def __post_init__(self):
        if self.mode not in ("simplified", "full"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.superstep < 1:
            raise ValueError(
                f"superstep {self.superstep} must be >= 1 (1 = one "
                "exchange per simulated step, the unbatched schedule)")
        if self.superstep > 1 and (
                self.superstep + self.ring_depth >= ev.TIME_MOD // 2):
            # A flushed word is deferred up to superstep-1 steps and must
            # still land inside the ring horizon, so the useful deadline
            # range spans superstep + ring_depth steps of the 8-bit wire
            # timestamp.  Past the wrap half-window a deferred word could
            # alias onto a future deadline instead of expiring — same
            # contract as the ring_depth bound below, extended by the
            # deferral (the fabric additionally adds the transport's path
            # latency to this bound).
            raise ValueError(
                f"superstep {self.superstep} + ring_depth "
                f"{self.ring_depth} reaches the 8-bit wrap half-window "
                f"({ev.TIME_MOD // 2}); a deferred word could alias onto "
                "a future deadline")
        if self.neurons_per_chip > (1 << ev.ADDR_BITS):
            raise ValueError("neuron address exceeds 14-bit event format")
        if self.n_inputs_per_chip > (1 << ev.ADDR_BITS):
            # The wire word carries the *destination* (input-row) address in
            # its 14-bit field; a wider input space would silently truncate
            # and deposit spikes on the wrong synapse row.
            raise ValueError("input address exceeds 14-bit event format")
        if self.merge_rate > 0 and (
                self.merge_depth > (ev.TIME_MOD // 2) * self.merge_rate):
            # A word queued in the rate-limited merge drains within
            # ceil(depth / rate) steps of its deadline passing (stale words
            # sort ahead of every in-window arrival).  Keeping that bound
            # under 128 steps guarantees no queued word can age across the
            # 8-bit wrap and alias onto a future deadline.
            raise ValueError(
                f"merge_depth {self.merge_depth} exceeds "
                f"{ev.TIME_MOD // 2} * merge_rate; a queued word could age "
                f"past the 8-bit wrap window")
        if self.ring_depth >= ev.TIME_MOD // 2:
            # The wire word carries only the 8-bit wrap timestamp; the ring
            # horizon must stay inside the wraparound half-window so the
            # deadline of every deliverable event is reconstructible.
            raise ValueError(
                f"ring_depth {self.ring_depth} exceeds the 8-bit wrap "
                f"half-window ({ev.TIME_MOD // 2 - 1})")

    @property
    def n_buckets(self) -> int:
        return self.n_chips * self.buckets_per_chip

    @property
    def lanes_in(self) -> int:
        """Incoming lanes per chip after exchange."""
        return self.n_chips * self.buckets_per_chip * self.bucket_capacity


class CommStats(NamedTuple):
    """Per-step accounting (all per-chip; aggregate over chips upstream).

    ``link_words`` / ``link_backlog`` are indexed by this chip's network
    port (``[n_ports]``): words the chip drove over each port this step,
    and the words in excess of the modeled per-link capacity.  Dense
    transports expose a single "net" port (off-chip words, zero backlog);
    a :class:`repro.core.topology.RoutedTransport` reports its topology's
    ports (torus ±dim links / tree up-down links) including transit
    traffic the chip forwards on behalf of others.

    ``lost_to_failure`` counts events culled before the wire because their
    source or destination chip (or every route between them) is dead under
    the fabric's installed health mask — the resilience subsystem's leg of
    the conservation invariant ``injected == delivered + queued + stalled
    + expired + lost_to_failure`` (see :mod:`repro.core.resilience`).
    """

    sent: jax.Array          # valid events offered to the network
    overflow: jax.Array      # dropped at bucket packing (congestion)
    merge_dropped: jax.Array  # dropped at merge buffer (full mode)
    expired: jax.Array       # dropped at deposit (deadline passed/too far)
    stalled: jax.Array       # dropped at the source by the credit gate
    utilization: jax.Array   # mean bucket fill fraction
    wire_bytes: jax.Array    # header + payload bytes injected
    traffic: jax.Array       # [n_chips] events by destination chip
    link_words: jax.Array    # [n_ports] words driven per network port
    link_backlog: jax.Array  # [n_ports] words beyond per-link capacity
    lost_to_failure: jax.Array  # culled: source/dest/route dead (resilience)


class Delivered(NamedTuple):
    """Post-exchange event lanes at the destination chip.

    Carries the packed wire words — the only payload the network moves.
    The SoA views (``addr`` / ``deadline`` / ``valid``) decode on demand;
    ``deadline`` is the 8-bit on-wire timestamp (reconstruct full-width
    deadlines with :func:`repro.core.events.word_deadline` and the ring's
    ``now`` where needed).
    """

    words: jax.Array     # int32[lanes] packed events (WORD_SENTINEL = empty)

    @property
    def addr(self) -> jax.Array:
        return ev.word_addr(self.words)

    @property
    def deadline(self) -> jax.Array:
        return ev.word_time(self.words)

    @property
    def valid(self) -> jax.Array:
        return ev.word_valid(self.words)


def _pack(cfg: PulseCommConfig, bucket_id, addr, deadline, valid) -> bk.PackedBuckets:
    if cfg.use_pallas:
        from repro.kernels.bucket_pack import ops as bp_ops

        return bp_ops.bucket_pack(
            bucket_id, addr, deadline, valid,
            n_buckets=cfg.n_buckets, capacity=cfg.bucket_capacity,
        )
    return bk.pack(
        bucket_id, addr, deadline, valid,
        n_buckets=cfg.n_buckets, capacity=cfg.bucket_capacity,
    )


def aggregate(cfg: PulseCommConfig, routed: rt.RoutedEvents) -> tuple[bk.PackedBuckets, jax.Array]:
    """Stage 1-2 at the source: bucket assignment + packing.

    Returns (packed slabs [n_buckets, C], traffic matrix [n_chips]).
    """
    if cfg.mode == "simplified":
        bucket_id = bk.static_bucket_ids(
            routed.dest_chip, n_chips=cfg.n_chips, streams=cfg.buckets_per_chip
        )
    else:
        bucket_id = bk.dynamic_bucket_ids(
            routed.dest_chip, routed.deadline,
            n_chips=cfg.n_chips, pool_per_chip=cfg.buckets_per_chip,
            window=cfg.time_window,
        )
    packed = _pack(cfg, bucket_id, routed.dest_addr, routed.deadline, routed.valid)
    traffic = tp.exchange_matrix(routed.dest_chip, routed.valid, cfg.n_chips)
    return packed, traffic


class FlushBuffer(NamedTuple):
    """Per-chip superstep exchange accumulator (the flush-slab carry).

    With ``cfg.superstep = B > 1`` the fabric defers the network exchange:
    each simulated step packs its admitted events into one column of this
    slab, and only when all B columns are filled does ONE fused collective
    move the whole block (see :meth:`repro.core.fabric.PulseFabric.
    superstep`).  The delay-ring slack window funds the deferral — events
    are admitted only with more slack than their remaining wait, so a
    flushed word is never stale on arrival.

    slab  : int32[n_buckets, B, capacity] packed wire words
            (``events.WORD_SENTINEL`` = empty); column k holds substep k's
            packets for the current block.
    phase : int32[] substeps accumulated so far (0..B; B = ready to flush).
    """

    slab: jax.Array
    phase: jax.Array

    @property
    def superstep(self) -> int:
        return self.slab.shape[-2]

    def occupancy(self) -> jax.Array:
        return jnp.sum(ev.word_valid(self.slab).astype(jnp.int32),
                       axis=(-3, -2, -1))


def flush_init(cfg: PulseCommConfig) -> FlushBuffer:
    """An empty flush slab for one chip (``cfg.superstep`` columns)."""
    return FlushBuffer(
        slab=ev.sentinel_words(
            (cfg.n_buckets, cfg.superstep, cfg.bucket_capacity)),
        phase=jnp.asarray(0, jnp.int32),
    )


def aggregate_into(
    cfg: PulseCommConfig,
    routed: rt.RoutedEvents,
    flushbuf: FlushBuffer,
    substep: int,
) -> tuple[FlushBuffer, jax.Array, jax.Array, jax.Array]:
    """Stage 1-2 at the source, fused into the superstep flush slab.

    Like :func:`aggregate`, but the packed words scatter directly into
    column ``substep`` of the flush slab — no per-step intermediate slab.
    Returns ``(flushbuf, counts[n_buckets], overflow, traffic[n_chips])``.
    """
    if cfg.mode == "simplified":
        bucket_id = bk.static_bucket_ids(
            routed.dest_chip, n_chips=cfg.n_chips,
            streams=cfg.buckets_per_chip)
    else:
        bucket_id = bk.dynamic_bucket_ids(
            routed.dest_chip, routed.deadline,
            n_chips=cfg.n_chips, pool_per_chip=cfg.buckets_per_chip,
            window=cfg.time_window,
        )
    if cfg.use_pallas:
        from repro.kernels.bucket_pack import ops as bp_ops

        slab, counts, overflow = bp_ops.flush_pack(
            bucket_id, routed.dest_addr, routed.deadline, routed.valid,
            slab=flushbuf.slab, capacity=cfg.bucket_capacity,
            substep=substep,
        )
    else:
        slab, counts, overflow = bk.flush_pack(
            bucket_id, routed.dest_addr, routed.deadline, routed.valid,
            slab=flushbuf.slab, capacity=cfg.bucket_capacity,
            substep=substep,
        )
    traffic = tp.exchange_matrix(routed.dest_chip, routed.valid, cfg.n_chips)
    flushbuf = FlushBuffer(slab=slab, phase=jnp.asarray(substep + 1,
                                                       jnp.int32))
    return flushbuf, counts, overflow, traffic


class LinkStats(NamedTuple):
    """Per-port link accounting for one exchange (see ``CommStats``)."""

    words: jax.Array     # int32[n_ports]
    backlog: jax.Array   # int32[n_ports]


class IssuedFlush(NamedTuple):
    """A superstep exchange that has been *issued* but not *completed*.

    The issue half (:func:`exchange_flush_issue`) launches every collective
    of the exchange — the fused ``all_to_all`` on a dense transport, the
    whole hop-forwarded ``ppermute`` round-set on a routed one — and
    returns the transport-layout delivery.  The complete half
    (:func:`exchange_flush_complete`) does only destination-side work
    (the routed path-latency timestamp shift and the per-substep
    unpacking), so a pipelined schedule can put the *issue* of block f
    before the *drain* of block f−1 in program order: the collective's
    result is not consumed until the next pipeline stage, which is
    exactly the loop-carried shape XLA's collective pipeliner overlaps
    with the following block's inject compute.

    words : int32[n_chips, buckets_per_chip, B, capacity] — delivered
            slabs, leading axis = source chip; on a routed transport the
            on-wire timestamps are still *unshifted* (the path-latency
            shift is destination-side work and belongs to complete).
    link  : per-port words/backlog of the issued exchange.
    """

    words: jax.Array
    link: LinkStats


def exchange_flush_issue(
    cfg: PulseCommConfig, transport: tp.Transport, slab: jax.Array
) -> IssuedFlush:
    """Issue half of the superstep exchange: launch the collective(s).

    ``slab`` is the filled ``int32[n_buckets, B, capacity]`` flush slab.
    Every collective op of the exchange is traced here; the returned
    :class:`IssuedFlush` carries the raw transport-layout delivery for a
    later :func:`exchange_flush_complete`.
    """
    with phase_scope("pulse_comm/exchange_issue"):
        return _exchange_flush_issue(cfg, transport, slab)


def _exchange_flush_issue(
    cfg: PulseCommConfig, transport: tp.Transport, slab: jax.Array
) -> IssuedFlush:
    b = slab.shape[1]
    shape = (cfg.n_chips, cfg.buckets_per_chip, b, cfg.bucket_capacity)
    block = slab.reshape(shape)
    if hasattr(transport, "exchange_words"):
        if b > 1 and hasattr(transport, "with_flush_rounds"):
            # The block carries B steps of payload and the link has B
            # steps to drain it: judge backlog against B rounds of
            # capacity (word counts are unaffected).
            transport = transport.with_flush_rounds(b)
        if hasattr(transport, "exchange_words_start"):
            words, link_words, link_backlog = (
                transport.exchange_words_start(block))
        else:
            words, link_words, link_backlog = transport.exchange_words(block)
    else:
        words = transport.all_to_all(block)
        own = jnp.take(block, transport.chip_index(), axis=0)
        off_chip = (jnp.sum(ev.word_valid(block).astype(jnp.int32))
                    - jnp.sum(ev.word_valid(own).astype(jnp.int32)))
        link_words = off_chip[None]
        link_backlog = jnp.zeros((1,), jnp.int32)
    return IssuedFlush(words=words,
                       link=LinkStats(words=link_words,
                                      backlog=link_backlog))


def exchange_flush_complete(
    cfg: PulseCommConfig, transport: tp.Transport, issued: IssuedFlush
) -> tuple[jax.Array, LinkStats]:
    """Complete half: destination-side finishing of an issued exchange.

    Applies the routed transport's path-latency timestamp shift (a
    no-collective elementwise op) and unpacks the transport layout into
    per-substep lanes ``int32[B, lanes_in]``.  An in-flight block that
    crosses a recovery boundary is completed by the *degraded* fabric, so
    its words are re-timed under the recompiled plan — exactly what a
    replayed in-flight word experiences on the detoured routes.
    """
    with phase_scope("pulse_comm/exchange_complete"):
        words = issued.words
        if hasattr(transport, "exchange_words_finish"):
            words = transport.exchange_words_finish(words)
        b = words.shape[2]
        # [n_chips(src), bpc, B, C] -> [B, n_chips * bpc * C] per substep
        out = jnp.moveaxis(words, 2, 0).reshape(b, cfg.lanes_in)
        return out, issued.link


def exchange_flush(
    cfg: PulseCommConfig, transport: tp.Transport, slab: jax.Array
) -> tuple[jax.Array, LinkStats]:
    """Stage 3 on a whole superstep block: ONE collective for B steps.

    ``slab`` is the filled ``int32[n_buckets, B, capacity]`` flush slab.
    The exchange runs on the ``[n_chips, buckets_per_chip, B * capacity]``
    layout — a single fused ``all_to_all`` on a dense transport, or one
    hop-forwarded batch (``ppermute`` round-set) on a routed topology,
    either way amortizing the per-collective launch cost over B simulated
    steps.  Substep identity is preserved: the returned words are
    ``int32[B, lanes_in]``, substep k carrying exactly what B separate
    exchanges would have delivered at that step (latency shifts included),
    which is what keeps the superstep schedule bitwise-equal to B=1.

    This is the serial composition of the issue/complete pair — the
    pipelined schedule (:meth:`repro.core.fabric.PulseFabric.
    run_pipelined`) calls the halves separately so block f's issue can
    precede block f−1's drain.
    """
    issued = exchange_flush_issue(cfg, transport, slab)
    return exchange_flush_complete(cfg, transport, issued)


class InjectStats(NamedTuple):
    """Per-substep source-side accounting of one injected block
    (everything :class:`CommStats` needs that is known at inject time —
    the drain-side legs join in at drain).  All fields carry a leading
    [B] substep axis."""

    sent: jax.Array          # int32[B]
    overflow: jax.Array      # int32[B]
    stalled: jax.Array       # int32[B]
    wrap_expired: jax.Array  # int32[B]
    lost: jax.Array          # int32[B]  culled by the health mask
    wire_bytes: jax.Array    # int32[B]
    utilization: jax.Array   # f32[B]
    traffic: jax.Array       # int32[B, n_chips]


class PipelineCarry(NamedTuple):
    """The in-flight block of the pipelined superstep schedule — the
    second (double-buffered) flush slab, post-exchange.

    While the live :class:`FlushBuffer` packs block f, this carry holds
    block f−1: already *issued* (its collective has run — ``words`` is
    the raw transport-layout delivery of :class:`IssuedFlush`) but not
    yet *drained* (no merge/deposit has seen it).  It threads through
    the fabric exactly like the ``flow``/``merge``/``sendq`` carries and
    is checkpoint-visible, so a recovery boundary can replay or account
    it — :meth:`PipelineCarry.occupancy` is the ``in_flight`` leg of the
    conservation identity::

        Σ sent == deposited + expired + overflow + merge_dropped
                  + stalled + lost_to_failure + queue occupancies
                  + in_flight

    words  : int32[n_chips, buckets_per_chip, B, capacity] issued
             delivery (see :class:`IssuedFlush`; sentinel = empty lane).
    link   : the issued exchange's per-port accounting.
    inject : the block's per-substep source-side stats, reported when
             the block is drained.
    t0     : int32[] block-start clock of the in-flight block.
    valid  : bool[] False = pipeline empty (prologue / after a flush).
    """

    words: jax.Array
    link: LinkStats
    inject: InjectStats
    t0: jax.Array
    valid: jax.Array

    @property
    def superstep(self) -> int:
        return self.words.shape[-2]

    def occupancy(self) -> jax.Array:
        """Valid in-flight words (0 when the pipeline is empty)."""
        n = jnp.sum(ev.word_valid(self.words).astype(jnp.int32),
                    axis=(-4, -3, -2, -1))
        return jnp.where(self.valid, n, 0)


def pipeline_init(cfg: PulseCommConfig, n_ports: int = 1) -> PipelineCarry:
    """An empty pipeline carry for one chip (``valid=False``; every
    stats field zero so a drained empty carry contributes nothing)."""
    b = cfg.superstep
    z = jnp.zeros((b,), jnp.int32)
    return PipelineCarry(
        words=ev.sentinel_words(
            (cfg.n_chips, cfg.buckets_per_chip, b, cfg.bucket_capacity)),
        link=LinkStats(words=jnp.zeros((n_ports,), jnp.int32),
                       backlog=jnp.zeros((n_ports,), jnp.int32)),
        inject=InjectStats(
            sent=z, overflow=z, stalled=z, wrap_expired=z, lost=z,
            wire_bytes=z, utilization=jnp.zeros((b,), jnp.float32),
            traffic=jnp.zeros((b, cfg.n_chips), jnp.int32)),
        t0=jnp.asarray(0, jnp.int32),
        valid=jnp.asarray(False, jnp.bool_),
    )


def exchange_with_stats(
    cfg: PulseCommConfig, transport: tp.Transport, packed: bk.PackedBuckets
) -> tuple[Delivered, LinkStats]:
    """Stage 3: route packets to their destination chips.

    On a dense transport this is ONE ``all_to_all`` on the packed word slab
    — the single collective of the whole step (previously three: addr,
    deadline and valid each crossed the interconnect separately) — and the
    link stats are a single "net" port carrying the off-chip words.  A
    transport exposing ``exchange_words`` (a routed topology) instead
    forwards the slab hop by hop and reports its own per-port counts.  The
    slab is laid out [n_chips, buckets_per_chip, C] so the exchange
    delivers slab *d* of every source to chip *d*; afterwards the leading
    axis indexes the *source* chip.
    """
    shape = (cfg.n_chips, cfg.buckets_per_chip, cfg.bucket_capacity)
    slab = packed.words.reshape(shape)
    if hasattr(transport, "exchange_words"):
        words, link_words, link_backlog = transport.exchange_words(slab)
    else:
        words = transport.all_to_all(slab)
        own = jnp.take(slab, transport.chip_index(), axis=0)
        off_chip = (jnp.sum(ev.word_valid(slab).astype(jnp.int32))
                    - jnp.sum(ev.word_valid(own).astype(jnp.int32)))
        link_words = off_chip[None]
        link_backlog = jnp.zeros((1,), jnp.int32)
    return (Delivered(words=words.reshape(cfg.lanes_in)),
            LinkStats(words=link_words, backlog=link_backlog))


def exchange(
    cfg: PulseCommConfig, transport: tp.Transport, packed: bk.PackedBuckets
) -> Delivered:
    """Stage 3 without the link accounting — see
    :func:`exchange_with_stats` (which the fabric uses)."""
    return exchange_with_stats(cfg, transport, packed)[0]


def merge_delivered(
    cfg: PulseCommConfig, delivered: Delivered, now: jax.Array | int = 0
) -> Delivered:
    """Stage 4 (full mode): time-ordered k-way merge of source streams,
    sorting the wire words directly by their wrap-aware deadline key
    relative to ``now`` (the ring clock)."""
    del cfg  # layout-free: the word merge sorts the flat lane set
    return Delivered(words=mg.merge_words(delivered.words, now))


def comm_step(
    cfg: PulseCommConfig,
    transport: tp.Transport,
    events: ev.EventBuffer,
    table: rt.RoutingTable,
    ring: dl.DelayRing,
) -> tuple[dl.DelayRing, Delivered, CommStats]:
    """Deprecated shim — use :class:`repro.core.fabric.PulseFabric`.

    One pulse-communication step for one chip (shard-local view), delegated
    to the unified fabric body with the given transport instance.  The
    3-tuple return cannot thread the stateful merge queue, so in full mode
    with ``merge_rate > 0`` every call starts from an empty queue (events
    held back this step are only recoverable through the fabric API).
    """
    from repro.core import fabric as fb

    warnings.warn(
        "pulse_comm.comm_step is deprecated; use "
        "PulseFabric(cfg, transport=...).step(...)",
        DeprecationWarning, stacklevel=2,
    )
    res = fb.PulseFabric(cfg, transport=transport).step(events, table, ring)
    return res.ring, res.delivered, res.stats


def multi_chip_step(
    cfg: PulseCommConfig,
    events: ev.EventBuffer,     # leading chip axis [n_chips, E]
    table: rt.RoutingTable,     # [n_chips, N, K] (per-chip LUTs)
    rings: dl.DelayRing,        # [n_chips, D, n_inputs]
) -> tuple[dl.DelayRing, Delivered, CommStats]:
    """Deprecated shim — use :class:`repro.core.fabric.PulseFabric`.

    Single-device multi-chip step, delegated to the fabric's "local"
    transport (same per-chip body under an internal vmap).  Unlike the old
    hand-written local path this reports real full-mode ``merge_dropped``
    and applies ``merge_rate`` / ``merge_depth`` — but the 3-tuple return
    cannot thread the merge queue across calls, so each call starts from an
    empty queue; use the fabric API to carry it.
    """
    from repro.core import fabric as fb

    warnings.warn(
        "pulse_comm.multi_chip_step is deprecated; use "
        'PulseFabric(cfg, transport="local").step(...)',
        DeprecationWarning, stacklevel=2,
    )
    res = fb.PulseFabric(cfg, transport="local").step(events, table, rings)
    return res.ring, res.delivered, res.stats
