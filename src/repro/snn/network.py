"""Multi-chip spiking network: HICANN-X chips + PulseFabric interconnect.

Per-step protocol (time t):

  1. pop delay-ring slot t        → input spike counts  [n_inputs]
  2. add external input           (background generators / host stimulus)
  3. crossbar matmul              → synaptic currents   [n_neurons]
  4. neuron dynamics (LIF/AdEx)   → output spikes       [n_neurons]
  5. spikes → events → PulseFabric → deposited into destination rings
     (deadline = t + axonal delay >= t+1)
  6. tick

Two inter-chip communication paths:

* ``event`` — the paper's path: events, routing LUT, buckets, exchange —
  all through :class:`repro.core.fabric.PulseFabric`, which moves the
  packed single-word wire format (one int32 per event, one ``all_to_all``
  per step) end-to-end.  Exact integer semantics, finite capacities,
  explicit loss accounting.  Not differentiable (addresses are discrete).
* ``dense`` — differentiable reference: the same routing table applied as a
  scatter-add of float spike values into the destination rings (infinite
  capacity).  Used for surrogate-gradient training and as the oracle in
  equivalence tests: with no overflow/expiry the two paths deliver identical
  integer spike counts (tests/test_network.py).

There is exactly ONE step body (:func:`_step_impl`), shared by the
single-device form (:func:`step` / :func:`run` / :func:`run_plastic` —
leading chip axis, fabric transport "local") and the shard_map production
form (:func:`shard_step` — chips = mesh shards, real ICI collectives).
The two differ only in the fabric binding and whether per-chip functions
run under ``jax.vmap``.  :func:`shard_run` drives the shard form over a
device mesh (:func:`shard_map_block`) with :func:`run`'s arguments and
results.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import delays as dl
from repro.core import events as ev
from repro.core import fabric as fb
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.core import topology as tpo
from repro.core import transport as tp
from repro.obs import metrics as obm
from repro.obs.trace import phase_scope
from repro.snn import neuron as nr
from repro.snn import synapse as sy


@dataclasses.dataclass(frozen=True)
class NetworkConfig:
    comm: pc.PulseCommConfig
    neuron_model: str = "lif"          # "lif" | "adex"
    comm_mode: str = "event"           # "event" | "dense"
    record_voltage: bool = True
    flow: fb.FlowControlConfig | None = None   # optional credit back-pressure
    topology: tpo.Topology | None = None       # switched network (None=dense)
    # Pipelined superstep schedule: issue block f's exchange before
    # draining block f−1, overlapping the collective with the next
    # block's neuron compute (the in-flight block rides in
    # NetworkState.pending).  Delivery stays bitwise-equal to the serial
    # schedule when every axonal delay + path latency exceeds 2B−1
    # (tests/test_pipeline.py); records keep their [T, ...] shape.
    pipeline: bool = False
    # Resilience: run on a degraded fabric — routes recompiled around the
    # failures, unreachable traffic culled into CommStats.lost_to_failure
    # (see repro.core.resilience; dead_links needs a topology).
    healthy: Any = None                # alive chips (indices / bool mask)
    dead_links: tuple = ()             # cut (chip, port) pairs
    # Telemetry: True (defaults) or a repro.obs.MetricsConfig threads a
    # device-resident MetricsCarry through the scan (NetworkState.metrics)
    # — aggregated in-scan with zero host syncs, checkpoint-visible, and
    # never read by the delivered spike path, so runs are bitwise-equal
    # with it on or off.  Supported on the batched (local-fabric) forms;
    # shard-local entry points leave state.metrics untouched.
    telemetry: Any = None

    def __post_init__(self):
        if self.neuron_model not in ("lif", "adex"):
            raise ValueError(self.neuron_model)
        if self.comm_mode not in ("event", "dense"):
            raise ValueError(self.comm_mode)
        if self.pipeline and self.comm_mode != "event":
            raise ValueError(
                "pipeline=True overlaps the event-path exchange; the dense "
                "comm_mode has no collective to pipeline")
        if self.topology is not None and \
                self.topology.n_chips != self.comm.n_chips:
            raise ValueError(
                f"topology has {self.topology.n_chips} chips, comm config "
                f"{self.comm.n_chips}")


class NetworkParams(NamedTuple):
    crossbar: sy.Crossbar        # w: [n_chips, n_inputs, n_neurons]
    neuron: Any                  # LIFParams/AdExParams, leading chip axis
    table: rt.RoutingTable       # [n_chips, n_neurons, K]


class NetworkState(NamedTuple):
    neuron: Any                  # LIFState/AdExState, leading chip axis
    ring: dl.DelayRing           # ring:[n_chips, D, n_inputs] now:[n_chips]
    t: jax.Array
    flow: Any = None             # credit state when cfg.flow is configured
    merge: Any = None            # merge queue (full mode, merge_rate > 0)
    sendq: Any = None            # retransmit queue (flow.retransmit_depth>0)
    pending: Any = None          # in-flight pipeline carry (cfg.pipeline)
    metrics: Any = None          # MetricsCarry when cfg.telemetry is set


class StepRecord(NamedTuple):
    spikes: jax.Array            # [n_chips, n_neurons] (f32 0/1)
    voltage: jax.Array           # [n_chips, n_neurons]
    stats: pc.CommStats
    # Events popped from the delay ring this step — with the final ring
    # contents, the delivered leg of the conservation identity.
    delivered: jax.Array         # [n_chips] int32


def _neuron_fns(cfg: NetworkConfig):
    if cfg.neuron_model == "lif":
        return nr.lif_step, nr.lif_init
    return nr.adex_step, nr.adex_init


def local_fabric(cfg: NetworkConfig) -> fb.PulseFabric:
    """The fabric binding used by the single-device forms (routed through
    ``cfg.topology`` when one is configured)."""
    transport = cfg.topology if cfg.topology is not None else "local"
    return fb.PulseFabric(cfg.comm, transport=transport, flow=cfg.flow,
                          healthy=cfg.healthy, dead_links=cfg.dead_links)


def shard_fabric(cfg: NetworkConfig,
                 axis: str | tuple[str, ...]) -> fb.PulseFabric:
    """The fabric binding used inside shard_map over ``axis``."""
    if cfg.topology is not None:
        transport = tpo.RoutedTransport(topology=cfg.topology, axis=axis)
    else:
        transport = tp.ShardMapTransport(axis=axis, n_chips=cfg.comm.n_chips)
    return fb.PulseFabric(cfg.comm, transport=transport, flow=cfg.flow,
                          healthy=cfg.healthy, dead_links=cfg.dead_links)


def init_params(
    key: jax.Array,
    cfg: NetworkConfig,
    *,
    table: rt.RoutingTable | None = None,
    weight_scale: float = 0.3,
) -> NetworkParams:
    c = cfg.comm
    k1, k2 = jax.random.split(key)
    xb = jax.vmap(
        lambda k: sy.init_crossbar(k, c.n_inputs_per_chip, c.neurons_per_chip,
                                   scale=weight_scale)
    )(jax.random.split(k1, c.n_chips))
    if cfg.neuron_model == "lif":
        nparams = nr.lif_params(c.neurons_per_chip)
    else:
        nparams = nr.adex_params(c.neurons_per_chip)
    nparams = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (c.n_chips,) + x.shape), nparams
    )
    if table is None:
        table = rt.random_table(k2, c.neurons_per_chip, c.n_chips,
                                fanout=c.fanout, max_delay=c.ring_depth // 2)
    if table.dest_chip.ndim == 2:  # broadcast one shared LUT to all chips
        table = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (c.n_chips,) + x.shape), table
        )
    return NetworkParams(crossbar=xb, neuron=nparams, table=table)


def _metrics_cfg(cfg: NetworkConfig) -> obm.MetricsConfig | None:
    """Resolve cfg.telemetry to a MetricsConfig (None = disabled).

    An unset ``link_capacity`` is filled from the topology's
    ``link_bandwidth`` so the link utilization EMA is a true ratio
    whenever the fabric actually bounds its links.
    """
    t = cfg.telemetry
    if t is None or t is False:
        return None
    mcfg = obm.MetricsConfig() if t is True else t
    if mcfg.link_capacity == 0 and cfg.topology is not None \
            and cfg.topology.link_bandwidth > 0:
        mcfg = dataclasses.replace(mcfg,
                                   link_capacity=cfg.topology.link_bandwidth)
    return mcfg


def _metrics_update(cfg: NetworkConfig, fabric: fb.PulseFabric,
                    metrics: Any, stats: pc.CommStats, *,
                    merge: Any = None, pending: Any = None) -> Any:
    """Fold one fabric call's stats into the carry (no-op when off).

    Telemetry observes the event fabric; the dense (differentiable)
    path has no fabric counters, so its zero-stats are not folded in.
    """
    if metrics is None or not fabric.batched or cfg.comm_mode != "event":
        return metrics
    with phase_scope("obs/metrics_update"):
        return obm.metrics_update(_metrics_cfg(cfg), metrics, stats,
                                  merge=merge, pending=pending)


def init_state(cfg: NetworkConfig, params: NetworkParams) -> NetworkState:
    c = cfg.comm
    _, ninit = _neuron_fns(cfg)
    nstate = jax.vmap(ninit)(params.neuron)
    ring_dtype = jnp.float32 if cfg.comm_mode == "dense" else jnp.int32
    ring = jax.vmap(
        lambda _: dl.init(c.ring_depth, c.n_inputs_per_chip, dtype=ring_dtype)
    )(jnp.arange(c.n_chips))
    fabric = local_fabric(cfg)
    pending = fabric.init_pending() if cfg.pipeline else None
    mcfg = _metrics_cfg(cfg)
    metrics = None
    if mcfg is not None:
        n_ports = cfg.topology.n_ports if cfg.topology is not None else 1
        metrics = obm.metrics_init(mcfg, c.n_chips, n_ports)
    return NetworkState(neuron=nstate, ring=ring, t=jnp.asarray(0, jnp.int32),
                        flow=fabric.init_flow(), merge=fabric.init_merge(),
                        sendq=fabric.init_sendq(), pending=pending,
                        metrics=metrics)


# ---------------------------------------------------------------------------
# Dense (differentiable) communication path
# ---------------------------------------------------------------------------

def dense_route(
    cfg: pc.PulseCommConfig,
    spikes: jax.Array,            # [n_chips, n_neurons] float
    table: rt.RoutingTable,       # [n_chips, n_neurons, K]
    ring: dl.DelayRing,           # batched over chips
    t: jax.Array,
) -> dl.DelayRing:
    """Apply the routing table as a differentiable scatter-add of spike
    values into the destination delay rings (infinite capacity)."""
    n_chips, n, k = table.dest_chip.shape
    d = cfg.ring_depth
    vals = (spikes[:, :, None] * table.valid).reshape(-1)          # [n_chips*N*K]
    dest_chip = table.dest_chip.reshape(-1)
    dest_addr = jnp.clip(table.dest_addr.reshape(-1), 0, cfg.n_inputs_per_chip - 1)
    deadline = t + table.delay.reshape(-1)
    slot = deadline % d
    ok = (table.delay.reshape(-1) >= 1) & (table.delay.reshape(-1) <= d)
    vals = jnp.where(ok, vals, 0.0)
    new = ring.ring.at[dest_chip, slot, dest_addr].add(
        vals.astype(ring.ring.dtype), mode="drop")
    return dl.DelayRing(ring=new, now=ring.now)


def _zero_stats(c: pc.PulseCommConfig) -> pc.CommStats:
    z = jnp.zeros((c.n_chips,), jnp.int32)
    return pc.CommStats(
        sent=z, overflow=z, merge_dropped=z, expired=z, stalled=z,
        utilization=jnp.zeros((c.n_chips,), jnp.float32),
        wire_bytes=z, traffic=jnp.zeros((c.n_chips, c.n_chips), jnp.int32),
        link_words=jnp.zeros((c.n_chips, 1), jnp.int32),
        link_backlog=jnp.zeros((c.n_chips, 1), jnp.int32),
        lost_to_failure=z,
    )


# ---------------------------------------------------------------------------
# The ONE step body
# ---------------------------------------------------------------------------

def _step_impl(
    cfg: NetworkConfig,
    fabric: fb.PulseFabric,
    table: rt.RoutingTable,
    neuron_params: Any,
    w: jax.Array,
    state: NetworkState,
    ext_input: jax.Array,
    *,
    stdp_cfg=None,
    stdp_state=None,
):
    """One network step — shared by :func:`step`, :func:`shard_step` and
    :func:`run_plastic`.

    ``fabric.batched`` decides the execution form: batched (leading chip
    axis, per-chip functions vmapped, fabric "local") or shard-local
    (unbatched, fabric collectives are real ICI ops).

    The credit state rides in ``state.flow`` and the persistent merge queue
    in ``state.merge``, so every entry point threads back-pressure and
    temporal merging across steps (auto-initialized when configured but the
    state was built without them).

    When ``stdp_cfg`` is given, the crossbar is plastic: the correlation
    sensor sees the *delivered* input spikes (ring output + external) as the
    pre-synaptic events — learning acts after the Extoll transport, matching
    hardware where the sensor sits in the synapse.

    Returns (new_state, record, new_w, new_stdp_state).
    """
    c = cfg.comm
    nstep, _ = _neuron_fns(cfg)
    vm = jax.vmap if fabric.batched else (lambda f: f)

    ring, in_spikes = vm(dl.pop_current)(state.ring)
    total_in = in_spikes.astype(jnp.float32) + ext_input
    currents = vm(sy.currents)(sy.Crossbar(w=w), total_in)
    nstate, spikes = vm(nstep)(state.neuron, currents, neuron_params)

    new_stdp, new_w = stdp_state, w
    if stdp_cfg is not None:
        from repro.snn import stdp as stdp_mod

        new_stdp, new_w = vm(
            lambda s, pre, post, ww: stdp_mod.step(stdp_cfg, s, pre, post, ww)
        )(stdp_state, total_in, spikes, w)

    flow = state.flow
    if fabric.flow is not None and flow is None:
        flow = fabric.init_flow()
    merge = state.merge
    if fabric.merge_enabled and merge is None:
        merge = fabric.init_merge()
    sendq = state.sendq
    if fabric.sendq_enabled and sendq is None:
        sendq = fabric.init_sendq()
    if cfg.comm_mode == "dense":
        if not fabric.batched:
            raise NotImplementedError(
                "dense comm_mode needs the explicit chip axis (local fabric)")
        ring = dense_route(c, spikes, table, ring, state.t)
        stats = _zero_stats(c)
    else:
        t = state.t
        ebs = vm(lambda s: ev.from_spikes(s > 0.5, t, c.event_capacity)[0])(
            spikes)
        res = fabric.step(ebs, table, ring, flow, merge, sendq)
        ring, stats = res.ring, res.stats
        flow, merge, sendq = res.flow, res.merge, res.sendq

    ring = vm(dl.tick)(ring)
    voltage = nstate.v if cfg.record_voltage else jnp.zeros_like(nstate.v)
    metrics = _metrics_update(cfg, fabric, state.metrics, stats,
                              merge=merge)
    new_state = NetworkState(neuron=nstate, ring=ring, t=state.t + 1,
                             flow=flow, merge=merge, sendq=sendq,
                             metrics=metrics)
    rec = StepRecord(spikes=spikes, voltage=voltage, stats=stats,
                     delivered=_popped(in_spikes))
    return new_state, rec, new_w, new_stdp


def _popped(in_spikes: jax.Array) -> jax.Array:
    """Events popped from the ring per chip (``in_spikes`` [..., n_inputs])."""
    return jnp.sum(in_spikes, axis=-1).astype(jnp.int32)


def _superstep_active(cfg: NetworkConfig) -> bool:
    """True when the scan must be restructured over B-step blocks."""
    return cfg.comm.superstep > 1 and cfg.comm_mode == "event"


def _pipeline_active(cfg: NetworkConfig) -> bool:
    """True when blocks run the pipelined (double-buffered) schedule."""
    return cfg.pipeline and cfg.comm_mode == "event"


def _blocked(cfg: NetworkConfig) -> bool:
    """True when run()/run_plastic scan whole B-step blocks (the pipelined
    schedule blocks even at B=1 — its carry spans block boundaries)."""
    return _superstep_active(cfg) or _pipeline_active(cfg)


def _block_impl(
    cfg: NetworkConfig,
    fabric: fb.PulseFabric,
    table: rt.RoutingTable,
    neuron_params: Any,
    w: jax.Array,
    state: NetworkState,
    ext_block: jax.Array,          # [B, ...] one superstep of inputs
    *,
    stdp_cfg=None,
    stdp_state=None,
):
    """One B-step superstep block — the blocked counterpart of
    :func:`_step_impl`, shared by :func:`run`, :func:`run_plastic` and
    :func:`shard_superstep` when ``cfg.comm.superstep > 1``.

    Phase 1 scans the B substeps of [pop ring, dynamics, (STDP), spikes →
    events] — no fabric call, so no collective.  Phase 2 hands the whole
    event block to :meth:`PulseFabric.superstep`: ONE fused exchange for
    the block, then per-substep merge/deposit against each substep's
    clock.  This is sound because admission guarantees no event injected
    inside the block can have a deadline inside it (slack > remaining
    deferral), so the phase-1 pops can never depend on phase-2 deposits —
    delivered spike trains stay bitwise-equal to the per-step schedule
    (tests/test_superstep.py).

    With ``cfg.pipeline`` phase 2 calls :meth:`PulseFabric.pipeline_block`
    instead: this block's exchange is *issued* (collective launched) and
    the *previous* block — carried in ``state.pending`` — is completed and
    drained, so the collective's result is only consumed one block later
    and the XLA scheduler can overlap it with the next block's phase-1
    compute.  The returned record's ``stats`` then describe the previous
    block (``spikes`` / ``voltage`` are still this block's);
    :func:`run` realigns them with the epilogue flush.

    Returns (new_state, record with leading [B] axis, new_w, new_stdp).
    """
    c = cfg.comm
    B = c.superstep
    nstep, _ = _neuron_fns(cfg)
    vm = jax.vmap if fabric.batched else (lambda f: f)

    def substep(carry, ext):
        nstate, ring, t, w_, stdp_ = carry
        ring, in_spikes = vm(dl.pop_current)(ring)
        total_in = in_spikes.astype(jnp.float32) + ext
        currents = vm(sy.currents)(sy.Crossbar(w=w_), total_in)
        nstate, spikes = vm(nstep)(nstate, currents, neuron_params)
        new_stdp, new_w = stdp_, w_
        if stdp_cfg is not None:
            from repro.snn import stdp as stdp_mod

            new_stdp, new_w = vm(
                lambda s, pre, post, ww: stdp_mod.step(stdp_cfg, s, pre,
                                                       post, ww)
            )(stdp_, total_in, spikes, w_)
        ebs = vm(lambda s: ev.from_spikes(s > 0.5, t, c.event_capacity)[0])(
            spikes)
        ring = vm(dl.tick)(ring)
        voltage = (nstate.v if cfg.record_voltage
                   else jnp.zeros_like(nstate.v))
        return ((nstate, ring, t + 1, new_w, new_stdp),
                (ebs, spikes, voltage, _popped(in_spikes)))

    carry0 = (state.neuron, state.ring, state.t, w, stdp_state)
    (nstate, ring, _, new_w, new_stdp), (ebs, spikes, voltage, popped) = \
        jax.lax.scan(substep, carry0, ext_block)

    # Flush the block through the fabric at the block-start clock (the
    # phase-1 ticks advanced ``now`` by B; substep k is judged at t0 + k).
    # Missing carries are auto-initialized by superstep itself and come
    # back in the result (run()'s _ensure_carries keeps the scan carry
    # structure fixed across iterations).
    ring0 = dl.DelayRing(ring=ring.ring, now=ring.now - B)
    if _pipeline_active(cfg):
        res = fabric.pipeline_block(
            ebs, table, ring0, state.flow, state.merge, state.sendq,
            state.pending)
    else:
        res = fabric.superstep(
            ebs, table, ring0, state.flow, state.merge, state.sendq)
    ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)

    metrics = _metrics_update(
        cfg, fabric, state.metrics, res.stats, merge=res.merge,
        pending=res.pending if _pipeline_active(cfg) else None)
    new_state = NetworkState(neuron=nstate, ring=ring, t=state.t + B,
                             flow=res.flow, merge=res.merge,
                             sendq=res.sendq, pending=res.pending,
                             metrics=metrics)
    rec = StepRecord(spikes=spikes, voltage=voltage, stats=res.stats,
                     delivered=popped)
    return new_state, rec, new_w, new_stdp


# ---------------------------------------------------------------------------
# Single-device multi-chip forms (leading chip axis)
# ---------------------------------------------------------------------------

def step(
    cfg: NetworkConfig,
    params: NetworkParams,
    state: NetworkState,
    ext_input: jax.Array,         # [n_chips, n_inputs] spike counts / rates
) -> tuple[NetworkState, StepRecord]:
    if _blocked(cfg):
        raise ValueError(
            f"comm.superstep={cfg.comm.superstep}, pipeline="
            f"{cfg.pipeline}: the exchange schedule is defined over "
            "B-step blocks — drive the network with run() (scans whole "
            "blocks) instead of single step() calls")
    new_state, rec, _, _ = _step_impl(
        cfg, local_fabric(cfg), params.table, params.neuron,
        params.crossbar.w, state, ext_input,
    )
    return new_state, rec


def _ensure_carries(fabric: fb.PulseFabric, state: NetworkState,
                    pipeline: bool = False) -> NetworkState:
    """Materialize flow/merge carries before a scan (the carry pytree
    structure must be fixed across iterations)."""
    if fabric.flow is not None and state.flow is None:
        state = state._replace(flow=fabric.init_flow())
    if fabric.merge_enabled and state.merge is None:
        state = state._replace(merge=fabric.init_merge())
    if fabric.sendq_enabled and state.sendq is None:
        state = state._replace(sendq=fabric.init_sendq())
    if pipeline and state.pending is None:
        state = state._replace(pending=fabric.init_pending())
    return state


def _flush_and_realign(
    cfg: NetworkConfig, fabric: fb.PulseFabric, final: NetworkState,
    recs: StepRecord
) -> tuple[NetworkState, StepRecord]:
    """Pipelined epilogue: drain the in-flight carry, then realign the
    per-block stats — the scan's slot f carried block f−1's stats (slot 0
    the empty prologue), so drop slot 0 and append the flush.  ``spikes``
    / ``voltage`` were never lagged (phase 1 runs in place) and stay
    untouched.

    Telemetry folds the flushed block in here too, so run-level totals
    close; the carry saw the blocks in pipeline order (an all-zero
    prologue first, the last block at the flush), which shifts the EMA
    sample sequence by one block but leaves totals/histograms exact up
    to the extra zero block."""
    res = fabric.flush_pending(final.ring, final.pending, final.flow,
                               final.merge, final.sendq)
    stats = jax.tree.map(
        lambda a, z: jnp.concatenate([a[1:], z[None]], axis=0),
        recs.stats, res.stats)
    recs = recs._replace(stats=stats)
    metrics = _metrics_update(cfg, fabric, final.metrics, res.stats,
                              merge=res.merge, pending=res.pending)
    final = final._replace(ring=res.ring, merge=res.merge,
                           pending=res.pending, metrics=metrics)
    return final, recs


def _blocked_inputs(cfg: NetworkConfig, ext_inputs: jax.Array) -> jax.Array:
    """Reshape [T, ...] inputs into [T // B, B, ...] superstep blocks."""
    B = cfg.comm.superstep
    T = ext_inputs.shape[0]
    if T % B:
        raise ValueError(
            f"run length T={T} must be a multiple of comm.superstep={B} "
            "(the exchange schedule is defined over whole blocks)")
    return ext_inputs.reshape((T // B, B) + ext_inputs.shape[1:])


def run(
    cfg: NetworkConfig,
    params: NetworkParams,
    state: NetworkState,
    ext_inputs: jax.Array,        # [T, n_chips, n_inputs]
) -> tuple[NetworkState, StepRecord]:
    """Scan the network over T steps; records stacked along time.

    With ``comm.superstep = B > 1`` (event mode) the scan is restructured
    over T/B superstep blocks — one fused exchange per block instead of
    one per step — and T must be a multiple of B.  Records keep their
    per-step [T, ...] shape either way, and the delivered spike trains are
    bitwise-equal to the B=1 schedule whenever axonal delays exceed
    ``B + path_latency`` (tests/test_superstep.py).

    With ``cfg.pipeline`` the blocks run the double-buffered schedule
    (each block's exchange issued before the previous block's drain, the
    in-flight block carried in ``state.pending``) and the run ends with
    an epilogue flush; stats are realigned so record element t still
    describes step t exactly.
    """
    fabric = local_fabric(cfg)
    state = _ensure_carries(fabric, state, pipeline=_pipeline_active(cfg))

    if _blocked(cfg):
        blocks = _blocked_inputs(cfg, ext_inputs)

        def block_body(carry, ext_block):
            new_state, rec, _, _ = _block_impl(
                cfg, fabric, params.table, params.neuron,
                params.crossbar.w, carry, ext_block,
            )
            return new_state, rec

        final, recs = jax.lax.scan(block_body, state, blocks)
        if _pipeline_active(cfg):
            final, recs = _flush_and_realign(cfg, fabric, final, recs)
        rec = jax.tree.map(
            lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
            recs)
        return final, rec

    def body(carry, ext):
        new_state, rec, _, _ = _step_impl(
            cfg, fabric, params.table, params.neuron, params.crossbar.w,
            carry, ext,
        )
        return new_state, rec

    return jax.lax.scan(body, state, ext_inputs)


def run_plastic(
    cfg: NetworkConfig,
    params: NetworkParams,
    state: NetworkState,
    ext_inputs: jax.Array,        # [T, n_chips, n_inputs]
    stdp_cfg=None,
):
    """On-chip learning run: crossbar weights evolve under STDP (BSS-2's
    correlation-sensor + PPU loop).  Returns (final_params, final_state,
    record, final_stdp_state)."""
    from repro.snn import stdp as stdp_mod

    c = cfg.comm
    scfg = stdp_cfg or stdp_mod.STDPConfig()
    sstate = jax.vmap(lambda _: stdp_mod.init(c.n_inputs_per_chip,
                                              c.neurons_per_chip))(
        jnp.arange(c.n_chips))
    fabric = local_fabric(cfg)
    state = _ensure_carries(fabric, state, pipeline=_pipeline_active(cfg))

    if _blocked(cfg):
        blocks = _blocked_inputs(cfg, ext_inputs)

        def block_body(carry, ext_block):
            net_state, w, st = carry
            new_state, rec, w, st = _block_impl(
                cfg, fabric, params.table, params.neuron, w, net_state,
                ext_block, stdp_cfg=scfg, stdp_state=st,
            )
            return (new_state, w, st), rec

        (final_state, w_final, s_final), recs = jax.lax.scan(
            block_body, (state, params.crossbar.w, sstate), blocks)
        if _pipeline_active(cfg):
            final_state, recs = _flush_and_realign(cfg, fabric,
                                                   final_state, recs)
        rec = jax.tree.map(
            lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
            recs)
        final_params = params._replace(crossbar=sy.Crossbar(w=w_final))
        return final_params, final_state, rec, s_final

    def body(carry, ext):
        net_state, w, st = carry
        new_state, rec, w, st = _step_impl(
            cfg, fabric, params.table, params.neuron, w, net_state, ext,
            stdp_cfg=scfg, stdp_state=st,
        )
        return (new_state, w, st), rec

    (final_state, w_final, s_final), rec = jax.lax.scan(
        body, (state, params.crossbar.w, sstate), ext_inputs)
    final_params = params._replace(crossbar=sy.Crossbar(w=w_final))
    return final_params, final_state, rec, s_final


# ---------------------------------------------------------------------------
# shard_map production step: chips = shards of the mesh "chip" axis
# ---------------------------------------------------------------------------

def shard_step(
    cfg: NetworkConfig,
    axis: str | tuple[str, ...],
    params: NetworkParams,        # shard-local: no chip axis
    state: NetworkState,
    ext_input: jax.Array,         # [n_inputs]
) -> tuple[NetworkState, StepRecord]:
    """Per-shard step body — call inside shard_map over ``axis``.

    Identical math to :func:`step` (it IS the same body) but with real ICI
    collectives: the all_to_all inside the fabric is the Extoll exchange.
    Credit state (when ``cfg.flow`` is set) rides in ``state.flow`` and the
    merge queue (full mode, merge_rate > 0) in ``state.merge`` — thread the
    returned state back in, exactly as for :func:`step`.

    With ``comm.superstep > 1`` use :func:`shard_superstep` (the exchange
    schedule is defined over whole blocks).
    """
    if _superstep_active(cfg):
        raise ValueError(
            f"comm.superstep={cfg.comm.superstep} batches the exchange "
            "over B-step blocks — call shard_superstep(cfg, axis, params, "
            "state, ext_block[B, n_inputs]) instead")
    new_state, rec, _, _ = _step_impl(
        cfg, shard_fabric(cfg, axis), params.table, params.neuron,
        params.crossbar.w, state, ext_input,
    )
    return new_state, rec


def shard_superstep(
    cfg: NetworkConfig,
    axis: str | tuple[str, ...],
    params: NetworkParams,        # shard-local: no chip axis
    state: NetworkState,
    ext_block: jax.Array,         # [B, n_inputs]
) -> tuple[NetworkState, StepRecord]:
    """Per-shard superstep block — call inside shard_map over ``axis``.

    The blocked counterpart of :func:`shard_step`: B substeps of neuron
    dynamics, then ONE fused exchange for the whole block (the collective
    launch rate on the ICI drops to 1/B per simulated step).  Records
    carry a leading [B] substep axis.
    """
    new_state, rec, _, _ = _block_impl(
        cfg, shard_fabric(cfg, axis), params.table, params.neuron,
        params.crossbar.w, state, ext_block,
    )
    return new_state, rec


def shard_pipeline_block(
    cfg: NetworkConfig,
    axis: str | tuple[str, ...],
    params: NetworkParams,        # shard-local: no chip axis
    state: NetworkState,
    ext_block: jax.Array,         # [B, n_inputs]
) -> tuple[NetworkState, StepRecord]:
    """Per-shard pipelined stage — call inside shard_map over ``axis``.

    The pipelined counterpart of :func:`shard_superstep` (requires
    ``cfg.pipeline``): issues this block's exchange, drains the previous
    block from ``state.pending``.  The returned record's ``stats``
    describe the previous block; finish the stream with
    :func:`shard_flush_pending` and realign as :func:`run` does.
    ``state.pending`` must be materialized (shard-local, e.g.
    ``shard_fabric(cfg, axis).init_pending()``) before the first call
    when driving this inside a scan.
    """
    if not _pipeline_active(cfg):
        raise ValueError("shard_pipeline_block needs cfg.pipeline=True "
                         "(event comm_mode)")
    fabric = shard_fabric(cfg, axis)
    state = _ensure_carries(fabric, state, pipeline=True)
    new_state, rec, _, _ = _block_impl(
        cfg, fabric, params.table, params.neuron,
        params.crossbar.w, state, ext_block,
    )
    return new_state, rec


def shard_flush_pending(
    cfg: NetworkConfig,
    axis: str | tuple[str, ...],
    state: NetworkState,
) -> tuple[NetworkState, pc.CommStats]:
    """Per-shard pipelined epilogue: drain the in-flight carry.  Returns
    the updated state (empty carry) and the flushed block's stats
    (leading [B] substep axis)."""
    fabric = shard_fabric(cfg, axis)
    res = fabric.flush_pending(state.ring, state.pending, state.flow,
                               state.merge, state.sendq)
    new_state = state._replace(ring=res.ring, merge=res.merge,
                               pending=res.pending)
    return new_state, res.stats


# ---------------------------------------------------------------------------
# The per-shard bodies as one shard_map: one simulated chip per device
# ---------------------------------------------------------------------------

_REPLICATED_STATE = ("t", "metrics")


def _map_state(state: NetworkState, on_chip, on_rep) -> NetworkState:
    """Apply ``on_chip`` to the chip-local carries and ``on_rep`` to the
    replicated fields (the clock and the fleet telemetry); None stays."""
    return NetworkState(**{
        k: None if v is None else (on_rep if k in _REPLICATED_STATE
                                   else on_chip)(v)
        for k, v in state._asdict().items()})


def shard_map_block(
    cfg: NetworkConfig,
    mesh: jax.sharding.Mesh,
    axis: str | tuple[str, ...] = "chip",
):
    """:func:`shard_step` (B=1) or :func:`shard_superstep` (B>1) wrapped
    in one ``jax.shard_map`` over ``axis`` of ``mesh``.

    The returned ``fn(params, state, ext)`` speaks the chip-stacked layout
    of the single-device forms: ``params`` and the chip-local carries of
    ``state`` lead with the chip axis (``state.t`` is replicated), ``ext``
    is ``[n_chips, n_inputs]`` (B=1) or ``[n_chips, B, n_inputs]``, and
    every record leaf leads with the chip axis (``[n_chips, B, ...]`` for
    B>1).  One simulated chip runs per device; the fabric's exchange is a
    real collective over ``axis``.
    """
    if _pipeline_active(cfg):
        raise ValueError(
            "the pipelined schedule is driven shard-locally through "
            "shard_pipeline_block / shard_flush_pending")
    per_shard = shard_superstep if _superstep_active(cfg) else shard_step
    chip, rep = P(axis), P()
    squeeze = lambda z: jax.tree.map(lambda a: a[0], z)
    expand = lambda z: jax.tree.map(lambda a: a[None], z)
    keep = lambda z: z

    def body(params, state, ext):
        new_state, rec = per_shard(cfg, axis, squeeze(params),
                                   _map_state(state, squeeze, keep), ext[0])
        return _map_state(new_state, expand, keep), expand(rec)

    def fn(params, state, ext):
        state_specs = _map_state(state, lambda _: chip, lambda _: rep)
        return jax.shard_map(
            body, mesh=mesh, in_specs=(chip, state_specs, chip),
            out_specs=(state_specs, chip), check_vma=False,
        )(params, state, ext)

    return fn


def shard_run(
    cfg: NetworkConfig,
    mesh: jax.sharding.Mesh,
    params: NetworkParams,
    state: NetworkState,
    ext_inputs: jax.Array,        # [T, n_chips, n_inputs]
) -> tuple[NetworkState, StepRecord]:
    """:func:`run` with one simulated chip per device of ``mesh`` (its
    ``"chip"`` axis).

    Same arguments, results and schedule as :func:`run` (chip-stacked
    params and state, records ``[T, n_chips, ...]``), bitwise-equal to it
    in every integer leaf.  The membrane voltages may differ in the last
    bits on the CPU backend, where the crossbar matmul batched over chips
    on one device rounds differently from the per-chip one here; on a TPU
    v5e they are bit-equal too.  The exchange runs as real collectives —
    ``all_to_all`` on the dense transport, hop-by-hop ``ppermute``
    forwarding when ``cfg.topology`` is set.
    """
    state = _ensure_carries(local_fabric(cfg), state)
    block = shard_map_block(cfg, mesh, "chip")
    blocked = _superstep_active(cfg)
    xs = (_blocked_inputs(cfg, ext_inputs).swapaxes(1, 2) if blocked
          else ext_inputs)                 # [T/B, n_chips, B, n_inputs]
    final, recs = jax.lax.scan(lambda s, x: block(params, s, x), state, xs)
    if blocked:
        recs = jax.tree.map(
            lambda r: r.swapaxes(1, 2).reshape(
                (r.shape[0] * r.shape[2], r.shape[1]) + r.shape[3:]),
            recs)
    return final, recs
