"""Pipelined superstep: double-buffered flush exchange overlapping compute.

Pins the tentpole contracts:
  * ``run_pipelined`` delivery (spike words, ring contents, CommStats) is
    **bitwise-equal** to the serial ``superstep()`` schedule for
    B ∈ {1, 2, 4} across the dense, torus2d and switch_tree transports
    (slack-sufficient workloads: delay + path latency > 2B−1);
  * streaming ``pipeline_block`` + ``flush_pending`` ≡ ``run_pipelined``;
  * the conservation identity extends over the in-flight carry:
    Σ sent == deposited + expired + overflow + merge_dropped + stalled
    + lost_to_failure + queue occupancies + pending.occupancy();
  * a straggler with less slack than the two-block wait is *expired with
    accounting*, never deposited into an already-popped slot;
  * fault drill: a chip killed at a block boundary with a non-empty
    in-flight slab — the degraded fabric drains the carry, culls arrivals
    at the dead chip into ``lost_to_failure`` (no silent loss), and the
    identity still closes;
  * HLO pin (shard_map): the pipelined stage still lowers to exactly ONE
    ``all_to_all``, *issued before* the drain's ring-scatter ops, and
    shard_map ≡ local stays bitwise;
  * the snn.network pipelined run matches the serial run record-for-record
    and config-time rejection of wrap-unsafe pipelines works.
"""

import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import delays as dl
from repro.core import events as ev
from repro.core import fabric as fb
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.core import topology as tpo


def _setup(B, *, n_chips=4, n=32, cap=8, bpc=2, mode="simplified",
           merge_rate=0, merge_depth=64, F=5, key=0, rate=0.4,
           min_delay=8, max_delay=12, ring_depth=16):
    """F blocks of B per-step event buffers plus a matching config.

    Delays start at ``min_delay`` — above 2B−1 minus the test topologies'
    path latencies for B ≤ 4 — so the pipelined deposit guard
    (``min_ahead = B + defer``) expires nothing the serial schedule would
    have delivered and the two schedules are comparable bitwise.
    """
    k = jax.random.PRNGKey(key)
    cfg = pc.PulseCommConfig(
        n_chips=n_chips, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=cap, buckets_per_chip=bpc,
        ring_depth=ring_depth, mode=mode, merge_rate=merge_rate,
        merge_depth=merge_depth, superstep=B)
    table = rt.random_table(k, n, n_chips, max_delay=max_delay,
                            min_delay=min_delay)
    tables = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_chips,) + x.shape), table)
    ks = jax.random.split(k, F * B)
    ebs = [jax.vmap(lambda s: ev.from_spikes(s, t, n)[0])(
        jax.random.uniform(ks[t], (n_chips, n)) < rate)
        for t in range(F * B)]
    blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *ebs)
    blocks = jax.tree.map(
        lambda a: a.reshape((F, B) + a.shape[1:]), blocks)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n))(
        jnp.arange(n_chips))
    return cfg, blocks, tables, rings


def _run_serial(fab, blocks, tables, rings):
    """F serial superstep blocks; returns (ring, delivered[F], stats[F])."""
    B = fab.cfg.superstep
    F = blocks.addr.shape[0]
    ring, merge = rings, fab.init_merge()
    dels, stats = [], []
    for f in range(F):
        blk = jax.tree.map(lambda a: a[f], blocks)
        res = fab.superstep(blk, tables, ring, None, merge)
        merge = res.merge
        ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)
        dels.append(res.delivered)
        stats.append(res.stats)
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
    return ring, stack(dels), stack(stats)


_TOPOS = [
    ("dense", None),
    ("torus2d", tpo.torus2d(2, 2, link_latency=1)),
    ("switch_tree", tpo.switch_tree(2, 2, link_latency=1,
                                    trunk_latency=1)),
]


def _fabric(cfg, topo, **kw):
    if topo is None:
        return fb.PulseFabric(cfg, transport="local", **kw)
    return fb.PulseFabric(cfg, transport=topo, **kw)


def _assert_stats_equal(a, b, msg=""):
    for fld in pc.CommStats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, fld)), np.asarray(getattr(b, fld)),
            err_msg=f"{msg}{fld}")


# ---------------------------------------------------------------------------
# Bitwise equality with the serial superstep schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo_name,topo", _TOPOS,
                         ids=[t[0] for t in _TOPOS])
@pytest.mark.parametrize("B", [1, 2, 4])
def test_run_pipelined_matches_serial_bitwise(B, topo_name, topo):
    cfg, blocks, tables, rings = _setup(B)
    fab = _fabric(cfg, topo)
    ring_s, del_s, stats_s = _run_serial(fab, blocks, tables, rings)
    res = fab.run_pipelined(blocks, tables, rings, None, fab.init_merge())
    np.testing.assert_array_equal(np.asarray(ring_s.ring),
                                  np.asarray(res.ring.ring))
    np.testing.assert_array_equal(np.asarray(ring_s.now),
                                  np.asarray(res.ring.now))
    np.testing.assert_array_equal(np.asarray(del_s.words),
                                  np.asarray(res.delivered.words))
    _assert_stats_equal(stats_s, res.stats)
    assert int(np.asarray(res.pending.occupancy()).sum()) == 0


@pytest.mark.parametrize("mode,merge_rate,merge_depth,min_delay", [
    ("full", 0, 64, 8),
    # Stateful merge: a queued word's slack erodes by its wait, so the
    # bitwise contract needs the wait bounded below min_delay − (2B−1).
    # depth ≤ 2·rate drains the queue within two steps (drops still
    # exercise the congestion path — see the deviation test below).
    ("full", 8, 16, 10),
], ids=["full-stateless", "full-merge-bounded-wait"])
def test_run_pipelined_matches_serial_full_mode(mode, merge_rate,
                                                merge_depth, min_delay):
    cfg, blocks, tables, rings = _setup(
        4, mode=mode, merge_rate=merge_rate, merge_depth=merge_depth,
        min_delay=min_delay, max_delay=min_delay + 2, ring_depth=20)
    fab = fb.PulseFabric(cfg, transport="local")
    ring_s, del_s, stats_s = _run_serial(fab, blocks, tables, rings)
    res = fab.run_pipelined(blocks, tables, rings, None, fab.init_merge())
    np.testing.assert_array_equal(np.asarray(ring_s.ring),
                                  np.asarray(res.ring.ring))
    np.testing.assert_array_equal(np.asarray(del_s.words),
                                  np.asarray(res.delivered.words))
    _assert_stats_equal(stats_s, res.stats)
    if merge_rate:
        assert int(np.asarray(stats_s.merge_dropped).sum()) > 0


def test_merge_congestion_straggler_expires_with_accounting():
    """Unbounded merge-queue waits erode slack below the pipelined
    two-block contract: a long-delayed emission is expired WITH
    accounting (deviating from serial delivery), never ghost-deposited —
    the pipelined analogue of the serial congestion-straggler pin in
    tests/test_superstep.py."""
    cfg, blocks, tables, rings = _setup(4, mode="full", merge_rate=3,
                                        merge_depth=64)
    fab = fb.PulseFabric(cfg, transport="local")
    ring_s, _, stats_s = _run_serial(fab, blocks, tables, rings)
    res = fab.run_pipelined(blocks, tables, rings, None, fab.init_merge())
    ser_sent, ser_acc = _totals(stats_s)
    pip_sent, pip_acc = _totals(res.stats)
    assert ser_sent == pip_sent
    dep_p = int(np.asarray(res.ring.ring).sum())
    q_p = int(np.asarray(res.merge.occupancy()).sum())
    assert pip_sent == dep_p + pip_acc + q_p    # identity closes
    # stragglers only ever expire (visibly) — never ghost extra deposits
    assert dep_p <= int(np.asarray(ring_s.ring).sum())


def test_streaming_pipeline_blocks_match_run_pipelined():
    """pipeline_block + flush_pending (the snn.network / recovery driver
    form) reproduces run_pipelined exactly, including the one-block lag
    and realignment."""
    B = 4
    cfg, blocks, tables, rings = _setup(B)
    fab = fb.PulseFabric(cfg, transport="local")
    F = blocks.addr.shape[0]

    ref = fab.run_pipelined(blocks, tables, rings, None, fab.init_merge())

    ring, merge, pending = rings, fab.init_merge(), fab.init_pending()
    dels, stats = [], []
    for f in range(F):
        blk = jax.tree.map(lambda a: a[f], blocks)
        res = fab.pipeline_block(blk, tables, ring, None, merge, None,
                                 pending)
        merge, pending = res.merge, res.pending
        ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)
        dels.append(res.delivered)
        stats.append(res.stats)
    fres = fab.flush_pending(ring, pending, None, merge)
    ring, pending = fres.ring, fres.pending
    # realign: slot 0 drained the empty prologue; append the flush
    dels = dels[1:] + [fres.delivered]
    stats = stats[1:] + [fres.stats]
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)

    np.testing.assert_array_equal(np.asarray(ref.ring.ring),
                                  np.asarray(ring.ring))
    np.testing.assert_array_equal(np.asarray(ref.delivered.words),
                                  np.asarray(stack(dels).words))
    _assert_stats_equal(ref.stats, stack(stats))
    assert int(np.asarray(pending.occupancy()).sum()) == 0


# ---------------------------------------------------------------------------
# Conservation: the identity extends over the in-flight carry
# ---------------------------------------------------------------------------

def _totals(stats):
    g = lambda f: int(np.asarray(getattr(stats, f)).sum())
    return (g("sent"), g("overflow") + g("expired") + g("stalled")
            + g("merge_dropped") + g("lost_to_failure"))


@pytest.mark.parametrize("mode,merge_rate", [("simplified", 0),
                                             ("full", 3)])
def test_conservation_includes_in_flight_carry(mode, merge_rate):
    """Mid-stream (no flush), every sent word is in a ring, a stats
    bucket, a queue — or the in-flight pipeline carry."""
    B = 4
    cfg, blocks, tables, rings = _setup(B, mode=mode,
                                        merge_rate=merge_rate)
    fab = fb.PulseFabric(cfg, transport="local")
    F = blocks.addr.shape[0]
    ring, merge, pending = rings, fab.init_merge(), fab.init_pending()
    before = int(np.asarray(ring.ring).sum())

    tot = {fld: 0 for fld in ("sent", "overflow", "expired", "stalled",
                              "merge_dropped", "lost_to_failure")}

    def _acc(stats):
        for fld in tot:
            tot[fld] += int(np.asarray(getattr(stats, fld)).sum())

    for f in range(F):
        blk = jax.tree.map(lambda a: a[f], blocks)
        res = fab.pipeline_block(blk, tables, ring, None, merge, None,
                                 pending)
        merge, pending = res.merge, res.pending
        ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)
        _acc(res.stats)
        # the carried block's inject-side legs are not yet reported:
        # add them (and its surviving words) from the carry itself.
        carried_sent = int(np.asarray(pending.inject.sent).sum())
        carried_acc = sum(
            int(np.asarray(getattr(pending.inject, fld)).sum())
            for fld in ("overflow", "stalled", "wrap_expired", "lost"))
        in_flight = int(np.asarray(pending.occupancy()).sum())
        assert in_flight > 0, f"carry empty after block {f}"
        deposited = int(np.asarray(ring.ring).sum()) - before
        queued = (0 if merge is None
                  else int(np.asarray(merge.occupancy()).sum()))
        obs.check_conservation(tot, delivered=deposited, queued=queued,
                               in_flight=in_flight,
                               extra_injected=carried_sent,
                               extra_accounted=carried_acc)

    fres = fab.flush_pending(ring, pending, None, merge)
    _acc(fres.stats)
    deposited = int(np.asarray(fres.ring.ring).sum()) - before
    queued = (0 if fres.merge is None
              else int(np.asarray(fres.merge.occupancy()).sum()))
    assert int(np.asarray(fres.pending.occupancy()).sum()) == 0
    obs.check_conservation(tot, delivered=deposited, queued=queued)


def test_straggler_expires_with_accounting_never_ghosts():
    """A word whose slack does not cover the two-block pipelined wait is
    expired WITH accounting at deposit — the pipelined schedule loses it
    (visibly) rather than depositing into an already-popped slot."""
    B = 4
    # delays 5..6 <= 2B-1 = 7: serial delivers them, pipelined must expire
    cfg, blocks, tables, rings = _setup(B, min_delay=5, max_delay=6)
    fab = fb.PulseFabric(cfg, transport="local")
    ring_s, _, stats_s = _run_serial(fab, blocks, tables, rings)
    res = fab.run_pipelined(blocks, tables, rings, None, fab.init_merge())
    ser_sent, ser_acc = _totals(stats_s)
    pip_sent, pip_acc = _totals(res.stats)
    assert ser_sent == pip_sent
    dep_s = int(np.asarray(ring_s.ring).sum())
    dep_p = int(np.asarray(res.ring.ring).sum())
    assert ser_sent == dep_s + ser_acc
    assert pip_sent == dep_p + pip_acc          # identity still closes
    assert dep_p < dep_s                        # stragglers were expired
    assert int(np.asarray(res.stats.expired).sum()) > int(
        np.asarray(stats_s.expired).sum())


# ---------------------------------------------------------------------------
# Fault drill: chip dies at a block boundary with a non-empty carry
# ---------------------------------------------------------------------------

def test_fault_at_block_boundary_with_in_flight_slab():
    """Kill a chip between pipelined blocks while its traffic is in
    flight: the degraded fabric (recompiled routes) drains the restored
    carry, arrivals at the dead chip land in ``lost_to_failure`` — no
    silent loss, the conservation identity closes over the whole run."""
    B, dead = 4, 2
    topo = tpo.torus2d(2, 2, link_latency=1)
    cfg, blocks, tables, rings = _setup(B, rate=0.6)
    healthy = tuple(c for c in range(cfg.n_chips) if c != dead)
    fab = fb.PulseFabric(cfg, transport=topo)
    F = blocks.addr.shape[0]
    ring, merge, pending = rings, fab.init_merge(), fab.init_pending()
    before = int(np.asarray(ring.ring).sum())

    sent = accounted = 0
    for f in range(2):                           # healthy prefix
        blk = jax.tree.map(lambda a: a[f], blocks)
        res = fab.pipeline_block(blk, tables, ring, None, merge, None,
                                 pending)
        merge, pending = res.merge, res.pending
        ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)
        s, a = _totals(res.stats)
        sent, accounted = sent + s, accounted + a
    # words bound for the dead chip sit in its slab of the carry
    pend_words = np.asarray(pending.words)
    dead_in_flight = int(ev.word_valid(
        jnp.asarray(pend_words[dead])).astype(jnp.int32).sum())
    assert dead_in_flight > 0, "drill needs traffic in flight to the dead chip"
    assert int(np.asarray(pending.occupancy()).sum()) > 0

    # recovery boundary: plan recompiled around the failure; the carries
    # (ring / merge / pending) thread straight across.
    degraded = fab.degrade(healthy=healthy)
    for f in range(2, F):
        blk = jax.tree.map(lambda a: a[f], blocks)
        res = degraded.pipeline_block(blk, tables, ring, None, merge,
                                      None, pending)
        merge, pending = res.merge, res.pending
        ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)
        s, a = _totals(res.stats)
        sent, accounted = sent + s, accounted + a
    fres = degraded.flush_pending(ring, pending, None, merge)
    s, a = _totals(fres.stats)
    sent, accounted = sent + s, accounted + a
    ring = fres.ring

    lost = (int(np.asarray(fres.stats.lost_to_failure).sum())
            + int(np.asarray(res.stats.lost_to_failure).sum()))
    assert lost > 0, "in-flight words to the dead chip must be accounted"
    deposited = int(np.asarray(ring.ring).sum()) - before
    assert int(np.asarray(fres.pending.occupancy()).sum()) == 0
    assert sent == deposited + accounted, (
        "conservation must close across the recovery boundary")


# ---------------------------------------------------------------------------
# Wrap guard + driver rejection
# ---------------------------------------------------------------------------

def test_pipeline_guard_rejects_wrap_unsafe_config():
    cfg = pc.PulseCommConfig(
        n_chips=4, neurons_per_chip=16, n_inputs_per_chip=16,
        event_capacity=16, bucket_capacity=4, ring_depth=100,
        superstep=14)
    fab = fb.PulseFabric(cfg, transport="local")
    ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, 16)[0])(
        jnp.zeros((4, 16), bool))
    blk = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (14,) + a.shape), ebs)
    blocks = jax.tree.map(lambda a: a[None], blk)
    table = rt.random_table(jax.random.PRNGKey(0), 16, 4, max_delay=8)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (4,) + x.shape),
                          table)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, 16))(jnp.arange(4))
    # serial superstep is fine (14 + 0 + 100 < 128) ...
    fab.superstep(blk, tables, rings)
    # ... but the pipelined wait is 2B and 28 + 0 + 100 >= 128
    with pytest.raises(ValueError, match="wrap half-window"):
        fab.run_pipelined(blocks, tables, rings)
    with pytest.raises(ValueError, match="wrap half-window"):
        fab.pipeline_block(blk, tables, rings)


def test_network_config_rejects_pipelined_dense_mode():
    from repro.snn import network as nw
    comm = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=8, n_inputs_per_chip=8,
        event_capacity=8, bucket_capacity=4, ring_depth=8)
    with pytest.raises(ValueError, match="dense"):
        nw.NetworkConfig(comm=comm, comm_mode="dense", pipeline=True)


def test_network_step_rejects_pipelined_driving():
    from repro.snn import network as nw
    comm = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=8, n_inputs_per_chip=8,
        event_capacity=8, bucket_capacity=4, ring_depth=8)
    cfg = nw.NetworkConfig(comm=comm, pipeline=True)
    params = nw.init_params(jax.random.PRNGKey(0), cfg)
    state = nw.init_state(cfg, params)
    with pytest.raises(ValueError, match="run\\(\\)"):
        nw.step(cfg, params, state, jnp.zeros((2, 8)))


# ---------------------------------------------------------------------------
# snn.network: pipelined run ≡ serial run, records stay [T, ...]
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", [None, tpo.torus2d(2, 2, link_latency=1)],
                         ids=["dense", "torus2d"])
def test_network_run_pipelined_matches_serial(topo):
    from repro.snn import network as nw
    n, N, B, T = 4, 32, 4, 24
    comm = pc.PulseCommConfig(
        n_chips=n, neurons_per_chip=N, n_inputs_per_chip=N,
        event_capacity=64, bucket_capacity=8, ring_depth=20, superstep=B)
    cfg = nw.NetworkConfig(comm=comm, topology=topo)
    cfgp = dataclasses.replace(cfg, pipeline=True)
    table = rt.random_table(jax.random.PRNGKey(0), N, n,
                            max_delay=14, min_delay=9)
    table = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                         table)
    params = nw.init_params(jax.random.PRNGKey(1), cfg, table=table)
    ext = (jax.random.uniform(jax.random.PRNGKey(2), (T, n, N)) < 0.25
           ).astype(jnp.float32) * 3.0
    f1, r1 = nw.run(cfg, params, nw.init_state(cfg, params), ext)
    f2, r2 = nw.run(cfgp, params, nw.init_state(cfgp, params), ext)
    assert r2.spikes.shape[0] == T          # records stay [T, ...]
    np.testing.assert_array_equal(np.asarray(r1.spikes),
                                  np.asarray(r2.spikes))
    np.testing.assert_array_equal(np.asarray(r1.voltage),
                                  np.asarray(r2.voltage))
    _assert_stats_equal(r1.stats, r2.stats)
    np.testing.assert_array_equal(np.asarray(f1.ring.ring),
                                  np.asarray(f2.ring.ring))
    assert int(np.asarray(f2.pending.occupancy()).sum()) == 0
    assert int(np.asarray(r1.spikes).sum()) > 0


# ---------------------------------------------------------------------------
# HLO pin: one collective per block, issued BEFORE the drain's scatters,
# and shard_map ≡ local under the pipelined schedule
# ---------------------------------------------------------------------------

_HLO_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import delays as dl, events as ev, fabric as fb
    from repro.core import pulse_comm as pc, routing as rt
    from repro.launch import hlo_stats

    n, N, B = 4, 16, 4
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("chip",))
    key = jax.random.PRNGKey(0)
    cfg = pc.PulseCommConfig(
        n_chips=n, neurons_per_chip=N, n_inputs_per_chip=N,
        event_capacity=N, bucket_capacity=4, buckets_per_chip=2,
        ring_depth=16, superstep=B)
    ks = jax.random.split(key, B)
    ebs = jax.tree.map(lambda *xs: jnp.stack(xs), *[
        jax.vmap(lambda s: ev.from_spikes(s, t, N)[0])(
            jax.random.uniform(ks[t], (n, N)) < 0.6) for t in range(B)])
    table = rt.random_table(key, N, n, max_delay=12, min_delay=8)
    tables = jax.tree.map(lambda z: jnp.broadcast_to(z, (n,) + z.shape),
                          table)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, N))(jnp.arange(n))
    shard = fb.PulseFabric(cfg, transport="shard_map")
    local = fb.PulseFabric(cfg, transport="local")

    # a NON-EMPTY in-flight carry as a real input: the lowering must both
    # issue this block's exchange and drain the carried block.
    seed = local.pipeline_block(ebs, tables, rings)
    pending = seed.pending

    def body(e, t, r, p):
        sq = lambda z: jax.tree.map(lambda a: a[0], z)
        eb = jax.tree.map(lambda a: a[:, 0], e)
        res = shard.pipeline_block(eb, sq(t), sq(r), None, None, None,
                                   sq(p))
        ring = jax.tree.map(lambda a: a[None], res.ring)
        delv = jax.tree.map(lambda a: a[:, None], res.delivered)
        stats = jax.tree.map(lambda a: a[:, None], res.stats)
        pend = jax.tree.map(lambda a: a[None], res.pending)
        return ring, delv, stats, pend

    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "chip"), P("chip"), P("chip"), P("chip")),
        out_specs=(P("chip"), P(None, "chip"), P(None, "chip"),
                   P("chip")),
        check_vma=False)
    compiled = jax.jit(f).lower(ebs, tables, rings, pending).compile()
    counts = hlo_stats.count_collectives(compiled)
    assert hlo_stats.count_collectives(compiled, "all-to-all") == 1, counts
    assert sum(counts.values()) == 1, counts
    print("ONE_COLLECTIVE_PER_PIPELINED_BLOCK")

    # Scheduling pin: the issue (all_to_all on this block's slab) is
    # traced BEFORE the drain (the carried block's ring-deposit
    # scatter-adds — identifiable as the only scatter-adds writing the
    # ring-shaped [D, n_inputs] operand).  Jaxpr equations print in
    # program order, so the exchange must come first; XLA's scheduler is
    # then free to overlap the collective with the next block's compute.
    lines = str(jax.make_jaxpr(f)(ebs, tables, rings, pending)).splitlines()
    a2a = [i for i, ln in enumerate(lines) if "all_to_all" in ln]
    ring_shape = f"i32[{cfg.ring_depth},{N}] = scatter-add"
    deposits = [i for i, ln in enumerate(lines) if ring_shape in ln]
    assert len(a2a) == 1, a2a
    assert len(deposits) == B, (ring_shape, deposits)
    assert a2a[0] < min(deposits), (a2a, deposits)
    print("ISSUE_BEFORE_DRAIN")

    # shard_map == local, bitwise, through a full drain of the carry
    got = f(ebs, tables, rings, pending)
    ref = local.pipeline_block(ebs, tables, rings, None, None, None,
                               pending)
    np.testing.assert_array_equal(np.asarray(got[0].ring),
                                  np.asarray(ref.ring.ring))
    np.testing.assert_array_equal(np.asarray(got[1].words),
                                  np.asarray(ref.delivered.words))
    for fld in pc.CommStats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got[2], fld)),
            np.asarray(getattr(ref.stats, fld)), err_msg=fld)
    np.testing.assert_array_equal(np.asarray(got[3].words),
                                  np.asarray(ref.pending.words))
    print("PIPELINE_HLO_OK")
""")


def test_pipelined_block_hlo_one_collective_issued_before_drain():
    out = subprocess.run(
        [sys.executable, "-c", _HLO_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "PIPELINE_HLO_OK" in out.stdout, (out.stdout[-2000:],
                                             out.stderr[-3000:])


# ---------------------------------------------------------------------------
# Profile-based overlap check (accelerator only)
# ---------------------------------------------------------------------------

def test_pipelined_overlap_on_accelerator():
    """On a real accelerator the pipelined schedule must not be slower
    than the serial one-jit scan (the collective leaves the critical
    path).  Dispatch-bound CPU runs cannot show thunk-level overlap, so
    this check auto-skips off-accelerator."""
    if jax.devices()[0].platform not in ("tpu", "gpu"):
        pytest.skip("overlap is only observable on an accelerator "
                    f"(platform={jax.devices()[0].platform})")
    import time
    B = 4
    cfg, blocks, tables, rings = _setup(B, n_chips=4, n=128, F=8)
    fab = fb.PulseFabric(cfg, transport="local")

    def serial_all(blocks, tables, rings):
        def body(carry, blk):
            ring, merge = carry
            res = fab.superstep(blk, tables, ring, None, merge)
            ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)
            return (ring, res.merge), res.delivered
        (ring, _), dels = jax.lax.scan(
            body, (rings, fab.init_merge()), blocks)
        return ring, dels

    jser = jax.jit(serial_all)
    jpip = fab.jit_run_pipelined()

    def time_one(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 10

    t_serial = time_one(jser, blocks, tables, rings)
    t_piped = time_one(jpip, blocks, tables, rings)
    assert t_piped <= t_serial * 1.10, (t_piped, t_serial)
