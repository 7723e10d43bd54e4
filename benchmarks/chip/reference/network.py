"""Plain reference of the simulated BrainScaleS-2 multi-chip network.

A straightforward, step-by-step implementation of the semantics the
benchmark holds the program to, written from the description and sharing
no code with it.  It runs one simulated step at a time (no superstep
blocks, no fused kernels) over every simulated chip at once:

1. pop the delay-ring slot of step ``t`` (the input spike counts);
2. crossbar: ``currents = (ring counts + external drive) @ w``;
3. AdEx membrane and adaptation update (forward Euler, exponential term
   clamped), threshold at ``v_peak``, refractory counter;
4. event interface: the first ``event_capacity`` spiking neurons of each
   chip, in ascending address order, become events;
5. inject: each event fans out to its ``fanout`` routing-table entries
   (event-major, entry-minor lane order); the deadline is ``t + delay``;
   the wrap window admits deadlines ``defer < delay < 128``, where
   ``defer`` is the number of steps left in the step's superstep block
   (``superstep - 1 - t % superstep``): an event due inside the deferred
   exchange expires at the source; buckets are bound to destination
   chips (simplified mode) or renamed by deadline window (full mode),
   filled in lane order up to ``bucket_capacity``; the rest overflows;
6. exchange: every bucket goes to its destination chip, which sees the
   lanes in (source chip, bucket, slot) order;
7. drain: in full mode with a positive ``merge_rate``, arrivals join a
   queue of ``merge_depth`` words; the queue and the arrivals, in that
   order, are stably sorted by deadline relative to ``t`` and the first
   ``merge_rate`` are emitted; what exceeds the queue drops; emitted (or,
   without a rate, all arriving) events are deposited into the ring slot
   of their deadline when ``defer < deadline - t <= ring_depth``, and
   counted expired otherwise.

Wire words are the paper's single-word event format: the 14-bit input
row in bits [8, 22), the 8-bit wrapping deadline in bits [0, 8), and -1
for an empty lane.  Per-step statistics per chip: ``sent`` (routed
events offered), ``overflow``, ``merge_dropped``, ``expired`` (source
wrap window plus deposit), ``stalled`` and ``lost_to_failure`` (always 0
here: no credit gate, no failed chips), ``utilization`` (mean bucket fill
over capacity), ``wire_bytes`` (32 bytes a non-empty packet plus 4 an
event), ``traffic`` (admitted events by destination chip) and
``link_words`` (words a chip sends to other chips; summed per superstep
block, when the real exchange moves them).

Float precision: the crossbar is a float32 product at ``HIGHEST``
precision, as the configuration states.  ``precision="high"`` computes it
as three bfloat16 passes instead (an explicit split, the same on every
platform): that is the control that must fail the comparison.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

SENTINEL = -1
TIME_MOD = 256
HEADER_BYTES = 32
EVENT_BYTES = 4
ADDR_MASK = (1 << 14) - 1


class RefConfig(NamedTuple):
    """The sizes and modes the reference needs, read from a configuration
    file's ``comm`` group."""

    n_chips: int
    neurons: int
    inputs: int
    event_capacity: int
    fanout: int
    bucket_capacity: int
    buckets_per_chip: int
    ring_depth: int
    mode: str
    merge_rate: int
    merge_depth: int
    time_window: int
    superstep: int

    @classmethod
    def from_comm(cls, comm: dict) -> "RefConfig":
        return cls(
            n_chips=comm["n_chips"], neurons=comm["neurons_per_chip"],
            inputs=comm["n_inputs_per_chip"],
            event_capacity=comm["event_capacity"], fanout=comm["fanout"],
            bucket_capacity=comm["bucket_capacity"],
            buckets_per_chip=comm["buckets_per_chip"],
            ring_depth=comm["ring_depth"], mode=comm["mode"],
            merge_rate=comm["merge_rate"], merge_depth=comm["merge_depth"],
            time_window=comm["time_window"], superstep=comm["superstep"])

    @property
    def merge_queue(self) -> bool:
        return self.mode == "full" and self.merge_rate > 0


def init_state(rc: RefConfig, neuron: dict) -> dict:
    """Membrane at rest, no adaptation, empty rings and merge queues."""
    c, n = rc.n_chips, rc.neurons
    state = {
        "v": neuron["e_l"] * jnp.ones((c, n), jnp.float32),
        "w": jnp.zeros((c, n), jnp.float32),
        "refrac": jnp.zeros((c, n), jnp.int32),
        "ring": jnp.zeros((c, rc.ring_depth, rc.inputs), jnp.int32),
        "t": jnp.asarray(0, jnp.int32),
    }
    if rc.merge_queue:
        state["queue"] = jnp.full((c, rc.merge_depth), SENTINEL, jnp.int32)
    return state


def crossbar(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """``x [C, I] @ w [C, I, N]`` per chip, in float32."""
    dot = functools.partial(jnp.einsum, "ci,cin->cn",
                            precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return dot(x, w)
    if precision == "high":
        split = lambda a: (a.astype(jnp.bfloat16).astype(jnp.float32),)
        (x_hi,), (w_hi,) = split(x), split(w)
        (x_lo,), (w_lo,) = split(x - x_hi), split(w - w_hi)
        return dot(x_hi, w_hi) + dot(x_hi, w_lo) + dot(x_lo, w_hi)
    raise ValueError(f"unknown crossbar precision {precision!r}")


def adex(p: dict, v, w, refrac, current):
    """One forward-Euler AdEx step; returns (v, w, refrac, spiked)."""
    active = refrac <= 0
    exp_term = p["g_l"] * p["delta_t"] * jnp.exp(
        jnp.clip((v - p["v_t"]) / p["delta_t"], -20.0, 10.0))
    dv = (-p["g_l"] * (v - p["e_l"]) + exp_term - w + current) / p["c_m"]
    dw = (p["a"] * (v - p["e_l"]) - w) / p["tau_w"]
    v1 = jnp.where(active, v + dv, v)
    w1 = w + dw
    spiked = (v1 - p["v_peak"] > 0) & active
    v_new = jnp.where(spiked, p["v_reset"], jnp.minimum(v1, p["v_peak"] + 1.0))
    w_new = jnp.where(spiked, w1 + p["b"], w1)
    refrac_new = jnp.where(spiked, p["refrac"], jnp.maximum(refrac - 1, 0))
    return v_new, w_new, refrac_new, spiked


def _inject(rc: RefConfig, table: dict, spiked, t):
    """Events → routed lanes → buckets.  Returns the per-source slabs
    ``[C, n_buckets, capacity]`` and the source-side statistics."""
    c, n, k = rc.n_chips, rc.neurons, rc.fanout
    cap, nb = rc.bucket_capacity, rc.n_chips * rc.buckets_per_chip
    # Event interface: the first event_capacity spikes in address order.
    rank = jnp.cumsum(spiked.astype(jnp.int32), axis=1) - 1
    is_event = spiked & (rank < rc.event_capacity)
    # Lanes in (event, fan-out entry) order == (neuron, entry) order.
    routed = is_event[:, :, None] & table["valid"]
    sent = jnp.sum(routed, axis=(1, 2)).astype(jnp.int32)
    delay = table["delay"]
    defer = rc.superstep - 1 - t % rc.superstep
    in_window = (delay > defer) & (delay < TIME_MOD // 2)
    wrap_expired = jnp.sum(routed & ~in_window, axis=(1, 2)).astype(jnp.int32)
    lane_ok = (routed & in_window).reshape(c, n * k)
    dest = table["dest_chip"].reshape(c, n * k)
    deadline = (t + delay).reshape(c, n * k)
    if rc.mode == "full":
        win = (deadline // max(rc.time_window, 1)) % rc.buckets_per_chip
    else:
        win = jnp.zeros_like(deadline)
    bucket = dest * rc.buckets_per_chip + win
    # Rank of each lane among the earlier admitted lanes of its bucket.
    onehot = (bucket[:, :, None] == jnp.arange(nb)) & lane_ok[:, :, None]
    onehot = onehot.astype(jnp.int32)
    before = jnp.cumsum(onehot, axis=1) - onehot
    slot = jnp.take_along_axis(before, bucket[:, :, None], axis=2)[:, :, 0]
    counts = jnp.sum(onehot, axis=1)                          # [C, nb]
    keep = lane_ok & (slot < cap)
    overflow = jnp.sum(lane_ok & (slot >= cap), axis=1).astype(jnp.int32)
    traffic = jnp.sum((dest[:, :, None] == jnp.arange(c)) & lane_ok[:, :, None],
                      axis=1).astype(jnp.int32)
    addr = table["dest_addr"].reshape(c, n * k)
    word = ((addr & ADDR_MASK) << 8) | (deadline & (TIME_MOD - 1))
    src = jnp.broadcast_to(jnp.arange(c)[:, None], (c, n * k))
    slab = jnp.full((c, nb, cap), SENTINEL, jnp.int32).at[
        jnp.where(keep, src, c), jnp.where(keep, bucket, nb),
        jnp.where(keep, slot, cap)].set(word, mode="drop")
    fill = jnp.minimum(counts, cap)
    packets = jnp.sum(counts > 0, axis=1).astype(jnp.int32)
    wire = packets * HEADER_BYTES + jnp.sum(fill, axis=1) * EVENT_BYTES
    utilization = fill.astype(jnp.float32).mean(axis=1) / float(cap)
    own = dest == jnp.arange(c)[:, None]
    link_words = jnp.sum(keep & ~own, axis=1).astype(jnp.int32)
    stats = {"sent": sent, "overflow": overflow, "wrap_expired": wrap_expired,
             "traffic": traffic, "wire_bytes": wire.astype(jnp.int32),
             "utilization": utilization, "link_words": link_words}
    return slab, stats


def _exchange(rc: RefConfig, slab):
    """``[src, dest * bpc, cap]`` → arrivals ``[dest, src * bpc * cap]``."""
    c, bpc, cap = rc.n_chips, rc.buckets_per_chip, rc.bucket_capacity
    per_dest = slab.reshape(c, c, bpc, cap).transpose(1, 0, 2, 3)
    return per_dest.reshape(c, c * bpc * cap)


def _merge(rc: RefConfig, queue, arrivals, t):
    """Rate-limited merge for every chip.  Returns (queue, emitted,
    dropped)."""
    rate, depth = rc.merge_rate, rc.merge_depth
    pad = jnp.full((rc.n_chips, rate), SENTINEL, jnp.int32)
    words = jnp.concatenate([queue, arrivals, pad], axis=1)
    key = jnp.where(words >= 0, (words - t + TIME_MOD // 2) & (TIME_MOD - 1),
                    TIME_MOD)
    order = jnp.argsort(key, axis=1, stable=True)
    words = jnp.take_along_axis(words, order, axis=1)
    n_valid = jnp.sum(words >= 0, axis=1)
    dropped = jnp.maximum(n_valid - jnp.minimum(n_valid, rate) - depth, 0)
    return words[:, rate:rate + depth], words[:, :rate], dropped.astype(jnp.int32)


def _deposit(rc: RefConfig, ring, words, t):
    """Deposit words into their deadline slots; returns (ring, expired)."""
    d = rc.ring_depth
    defer = rc.superstep - 1 - t % rc.superstep
    valid = words >= 0
    diff = ((words & (TIME_MOD - 1)) - (t & (TIME_MOD - 1))) & (TIME_MOD - 1)
    ahead = jnp.where(diff >= TIME_MOD // 2, diff - TIME_MOD, diff)
    ok = valid & (ahead > defer) & (ahead <= d)
    expired = jnp.sum(valid & ~ok, axis=1).astype(jnp.int32)
    chip = jnp.broadcast_to(jnp.arange(rc.n_chips)[:, None], words.shape)
    slot = (t + ahead) % d
    row = jnp.clip(words >> 8, 0, rc.inputs - 1)
    ring = ring.at[jnp.where(ok, chip, rc.n_chips), slot, row].add(
        1, mode="drop")
    return ring, expired


def step(rc: RefConfig, data: dict, state: dict, ext, precision="highest"):
    """One simulated step of every chip.  ``ext`` is ``[C, inputs]``."""
    t = state["t"]
    slot = t % rc.ring_depth
    inputs = state["ring"][:, slot, :]
    ring = state["ring"].at[:, slot, :].set(0)
    current = crossbar(inputs.astype(jnp.float32) + ext, data["w"], precision)
    v, w, refrac, spiked = adex(data["neuron"], state["v"], state["w"],
                                state["refrac"], current)
    slab, st = _inject(rc, data["table"], spiked, t)
    arrivals = _exchange(rc, slab)
    new = {"v": v, "w": w, "refrac": refrac, "t": t + 1}
    if rc.merge_queue:
        new["queue"], arrivals, merge_dropped = _merge(
            rc, state["queue"], arrivals, t)
    else:
        merge_dropped = jnp.zeros((rc.n_chips,), jnp.int32)
    new["ring"], dep_expired = _deposit(rc, ring, arrivals, t)
    zeros = jnp.zeros((rc.n_chips,), jnp.int32)
    rec = {
        "spikes": spiked, "voltage": v,
        "delivered": jnp.sum(inputs, axis=1).astype(jnp.int32),
        "sent": st["sent"], "overflow": st["overflow"],
        "merge_dropped": merge_dropped,
        "expired": st["wrap_expired"] + dep_expired,
        "stalled": zeros, "lost_to_failure": zeros,
        "utilization": st["utilization"], "wire_bytes": st["wire_bytes"],
        "traffic": st["traffic"], "link_words": st["link_words"],
        "link_backlog": zeros,
    }
    return new, rec


def run_chunk(rc: RefConfig, data: dict, state: dict, ext, precision="highest"):
    """Scan :func:`step` over ``ext [T, C, inputs]``; records stacked over
    time, with ``link_words`` / ``link_backlog`` summed per superstep
    block (``[T // superstep, C]``)."""
    state, rec = jax.lax.scan(
        lambda s, x: step(rc, data, s, x, precision), state, ext)
    b = rc.superstep
    for name in ("link_words", "link_backlog"):
        x = rec[name]
        rec[name] = x.reshape((x.shape[0] // b, b) + x.shape[1:]).sum(axis=1)
    return state, rec
