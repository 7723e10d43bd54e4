"""Superstep exchange batching: one fused collective per B simulated steps.

Pins the tentpole contracts:
  * delivered spike trains and ring contents are **bitwise-equal** to the
    B=1 schedule for B ∈ {1, 2, 4} across the dense, torus2d and
    switch_tree transports (slack-sufficient workloads: every axonal delay
    exceeds B + path latency, so the tightened injection window admits
    exactly what B=1 admits);
  * a B-step superstep lowers to exactly ONE ``all_to_all`` on the dense
    shard_map transport (HLO-verified), and shard_map ≡ local stays
    bitwise under the blocked schedule;
  * the flush-slab pack writes substep columns identical to the per-step
    ``bk.pack`` (jnp reference and Pallas kernel agree);
  * config-time rejection of wrap-unsafe supersteps (B + path latency +
    ring depth must stay inside the 128-step half-window) and of per-step
    driving when the schedule is blocked;
  * conservation under merge congestion: a straggler emitted with less
    slack than the remaining deferral is *expired with accounting*, never
    deposited into an already-popped slot (no ghosts one revolution late);
  * the cached jitted drivers do not re-trace across same-shape calls.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import buckets as bk
from repro.core import delays as dl
from repro.core import events as ev
from repro.core import fabric as fb
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.core import topology as tpo


def _setup(B, *, n_chips=4, n=32, cap=8, bpc=2, mode="simplified",
           merge_rate=0, merge_depth=64, T=8, key=0, rate=0.4,
           min_delay=8, max_delay=12):
    """T per-step event buffers plus a config with the given superstep.

    Delays start at ``min_delay`` — above B + the test topologies' path
    latencies — so the tightened injection window admits every event and
    the B=1 / B>1 schedules are comparable bitwise.
    """
    k = jax.random.PRNGKey(key)
    cfg = pc.PulseCommConfig(
        n_chips=n_chips, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=cap, buckets_per_chip=bpc,
        ring_depth=16, mode=mode, merge_rate=merge_rate,
        merge_depth=merge_depth, superstep=B)
    table = rt.random_table(k, n, n_chips, max_delay=max_delay,
                            min_delay=min_delay)
    tables = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_chips,) + x.shape), table)
    ks = jax.random.split(k, T)
    ebs = [jax.vmap(lambda s: ev.from_spikes(s, t, n)[0])(
        jax.random.uniform(ks[t], (n_chips, n)) < rate) for t in range(T)]
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n))(
        jnp.arange(n_chips))
    return cfg, ebs, tables, rings


def _run_b1(fab, ebs, tables, rings):
    """T steps of the per-step schedule; returns (ring, delivered trains)."""
    ring, merge = rings, fab.init_merge()
    delivered = []
    for t in range(len(ebs)):
        res = fab.step(ebs[t], tables, ring, None, merge)
        ring, merge = res.ring, res.merge
        delivered.append(np.asarray(res.delivered.words))
        ring = jax.vmap(dl.tick)(ring)
    return ring, delivered


def _run_blocks(fab, ebs, tables, rings):
    """The same T steps as T/B superstep blocks."""
    B = fab.cfg.superstep
    ring, merge = rings, fab.init_merge()
    delivered = []
    for blk in range(len(ebs) // B):
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *ebs[blk * B:(blk + 1) * B])
        res = fab.superstep(block, tables, ring, None, merge)
        ring, merge = res.ring, res.merge
        for k in range(B):
            delivered.append(np.asarray(res.delivered.words[k]))
        ring = dl.DelayRing(ring=ring.ring, now=ring.now + B)
    return ring, delivered


# ---------------------------------------------------------------------------
# Bitwise equality with the B=1 schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,merge_rate", [("simplified", 0), ("full", 0),
                                             ("full", 3)])
@pytest.mark.parametrize("B", [2, 4])
def test_superstep_matches_b1_schedule_bitwise(mode, merge_rate, B):
    cfg1, ebs, tables, rings = _setup(1, mode=mode, merge_rate=merge_rate)
    ring1, del1 = _run_b1(fb.PulseFabric(cfg1, transport="local"),
                          ebs, tables, rings)
    cfgB, _, _, ringsB = _setup(B, mode=mode, merge_rate=merge_rate)
    ringB, delB = _run_blocks(fb.PulseFabric(cfgB, transport="local"),
                              ebs, tables, ringsB)
    np.testing.assert_array_equal(np.asarray(ring1.ring),
                                  np.asarray(ringB.ring))
    for t, (a, b) in enumerate(zip(del1, delB)):
        np.testing.assert_array_equal(a, b, err_msg=f"delivered step {t}")


@pytest.mark.parametrize("topo", [
    tpo.torus2d(2, 2, link_latency=1),
    tpo.switch_tree(2, 2, link_latency=1, trunk_latency=1),
], ids=["torus2d", "switch_tree"])
@pytest.mark.parametrize("B", [2, 4])
def test_superstep_matches_b1_on_routed_topologies(topo, B):
    cfg1, ebs, tables, rings = _setup(1)
    ring1, del1 = _run_b1(fb.PulseFabric(cfg1, transport=topo),
                          ebs, tables, rings)
    cfgB, _, _, ringsB = _setup(B)
    ringB, delB = _run_blocks(fb.PulseFabric(cfgB, transport=topo),
                              ebs, tables, ringsB)
    np.testing.assert_array_equal(np.asarray(ring1.ring),
                                  np.asarray(ringB.ring))
    for t, (a, b) in enumerate(zip(del1, delB)):
        np.testing.assert_array_equal(a, b, err_msg=f"delivered step {t}")


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4]),
       st.sampled_from(["dense", "torus"]), st.floats(0.1, 0.9))
def test_superstep_equality_property(seed, B, transport, rate):
    """Any slack-sufficient load delivers identically under deferral."""
    topo = (tpo.torus2d(2, 2, link_latency=1) if transport == "torus"
            else "local")
    cfg1, ebs, tables, rings = _setup(1, key=seed, rate=rate, T=B * 2)
    ring1, del1 = _run_b1(fb.PulseFabric(cfg1, transport=topo),
                          ebs, tables, rings)
    cfgB, _, _, ringsB = _setup(B, key=seed, rate=rate, T=B * 2)
    ringB, delB = _run_blocks(fb.PulseFabric(cfgB, transport=topo),
                              ebs, tables, ringsB)
    np.testing.assert_array_equal(np.asarray(ring1.ring),
                                  np.asarray(ringB.ring))
    for a, b in zip(del1, delB):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# One collective per block (HLO) + local ≡ shard_map under superstep
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
    for mode, merge_rate in [("simplified", 0), ("full", 3)]:
        cfg = pc.PulseCommConfig(
            n_chips=n, neurons_per_chip=N, n_inputs_per_chip=N,
            event_capacity=N, bucket_capacity=4, buckets_per_chip=2,
            ring_depth=16, mode=mode, merge_rate=merge_rate,
            merge_depth=8, superstep=B)
        spikes = jax.random.uniform(key, (B, n, N)) < 0.6
        ebs = jax.vmap(jax.vmap(lambda s: ev.from_spikes(s, 0, N)[0]))(
            spikes)
        table = rt.random_table(key, N, n, max_delay=12, min_delay=8)
        tables = jax.tree.map(lambda z: jnp.broadcast_to(z, (n,) + z.shape),
                              table)
        rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, N))(
            jnp.arange(n))
        shard = fb.PulseFabric(cfg, transport="shard_map")
        local = fb.PulseFabric(cfg, transport="local")
        merge_b = local.init_merge()

        def body(e, t, r, m):
            sq = lambda z: jax.tree.map(lambda a: a[0], z)
            opt = lambda z: None if z is None else sq(z)
            eb = jax.tree.map(lambda a: a[:, 0], e)
            res = shard.superstep(eb, sq(t), sq(r), None, opt(m))
            ring, delv, stats, merge = (
                res.ring, res.delivered, res.stats, res.merge)
            ring = jax.tree.map(lambda a: a[None], ring)
            delv = jax.tree.map(lambda a: a[:, None], delv)
            stats = jax.tree.map(lambda a: a[:, None], stats)
            merge = (None if merge is None
                     else jax.tree.map(lambda a: a[None], merge))
            return fb.FabricResult(ring, delv, stats, None, merge, None)

        f = shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "chip"), P("chip"), P("chip"), P("chip")),
            out_specs=fb.FabricResult(
                ring=P("chip"), delivered=P(None, "chip"),
                stats=P(None, "chip"), flow=None,
                merge=P("chip") if merge_rate else None, sendq=None),
            check_vma=False)
        compiled = jax.jit(f).lower(ebs, tables, rings, merge_b).compile()
        counts = hlo_stats.count_collectives(compiled)
        count = hlo_stats.count_collectives(compiled, "all-to-all")
        assert count == 1, (mode, merge_rate, counts)
        assert sum(counts.values()) == count, (mode, merge_rate, counts)

        got = f(ebs, tables, rings, merge_b)
        ref = local.superstep(ebs, tables, rings, None, merge_b)
        np.testing.assert_array_equal(np.asarray(got.ring.ring),
                                      np.asarray(ref.ring.ring))
        np.testing.assert_array_equal(np.asarray(got.delivered.words),
                                      np.asarray(ref.delivered.words))
        for fld in pc.CommStats._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got.stats, fld)),
                np.asarray(getattr(ref.stats, fld)), err_msg=fld)
        print(f"ONE_ALL_TO_ALL_PER_BLOCK mode={mode} merge={merge_rate}")
    print("SUPERSTEP_HLO_OK")
""")


def test_superstep_issues_one_all_to_all_per_block_and_matches_local():
    out = subprocess.run(
        [sys.executable, "-c", _HLO_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "SUPERSTEP_HLO_OK" in out.stdout, out.stderr[-3000:]


# ---------------------------------------------------------------------------
# Flush-slab pack
# ---------------------------------------------------------------------------

def test_flush_pack_matches_per_step_pack():
    key = jax.random.PRNGKey(3)
    e, n_buckets, cap, B = 64, 6, 4, 3
    bid = jax.random.randint(key, (e,), 0, n_buckets)
    addr = jax.random.randint(key, (e,), 0, 100)
    dead = jax.random.randint(key, (e,), 0, 300)
    valid = jax.random.uniform(key, (e,)) < 0.7
    ref = bk.pack(bid, addr, dead, valid, n_buckets=n_buckets, capacity=cap)
    for k in range(B):
        slab = ev.sentinel_words((n_buckets, B, cap))
        slab, counts, overflow = bk.flush_pack(
            bid, addr, dead, valid, slab=slab, capacity=cap, substep=k)
        np.testing.assert_array_equal(np.asarray(slab[:, k, :]),
                                      np.asarray(ref.words))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(ref.counts))
        assert int(overflow) == int(ref.overflow)
        # the other substep columns stay untouched sentinels
        others = np.delete(np.asarray(slab), k, axis=1)
        assert (others == ev.WORD_SENTINEL).all()


def test_flush_pack_pallas_matches_reference():
    from repro.kernels.bucket_pack import ops as bp_ops

    key = jax.random.PRNGKey(4)
    e, n_buckets, cap, B = 128, 4, 8, 2
    bid = jax.random.randint(key, (e,), 0, n_buckets)
    addr = jax.random.randint(key, (e,), 0, 50)
    dead = jax.random.randint(key, (e,), 0, 256)
    valid = jax.random.uniform(key, (e,)) < 0.8
    for k in range(B):
        slab0 = ev.sentinel_words((n_buckets, B, cap))
        want = bk.flush_pack(bid, addr, dead, valid, slab=slab0,
                             capacity=cap, substep=k)
        got = bp_ops.flush_pack(bid, addr, dead, valid, slab=slab0,
                                capacity=cap, substep=k, interpret=True)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_flushbuf_carry_protocol():
    """The FlushBuffer carry: one slab column per substep, phase counting
    accumulated substeps, occupancy counting held words — batched over
    chips by init_flushbuf on the local path."""
    cfg, ebs, tables, _ = _setup(2)
    fab = fb.PulseFabric(cfg, transport="local")
    buf = fab.init_flushbuf()
    assert buf.slab.shape == (cfg.n_chips, cfg.n_buckets, 2,
                              cfg.bucket_capacity)
    assert buf.superstep == 2
    assert (np.asarray(buf.occupancy()) == 0).all()
    # per-chip: aggregate one substep into column 0 and check the protocol
    chip = pc.flush_init(cfg)
    assert int(chip.phase) == 0
    routed = rt.route(jax.tree.map(lambda x: x[0], ebs[0]),
                      jax.tree.map(lambda x: x[0], tables))
    chip, counts, overflow, _ = pc.aggregate_into(cfg, routed, chip, 0)
    assert int(chip.phase) == 1
    held = int(np.asarray(chip.occupancy()))
    assert held == int(np.asarray(counts).clip(
        max=cfg.bucket_capacity).sum()) and held > 0
    assert (np.asarray(chip.slab[:, 1, :]) == ev.WORD_SENTINEL).all()


# ---------------------------------------------------------------------------
# Guards: wrap safety, blocked driving, divisibility
# ---------------------------------------------------------------------------

def test_config_rejects_wrap_unsafe_superstep():
    ok = dict(n_chips=2, neurons_per_chip=16, n_inputs_per_chip=16,
              event_capacity=16, bucket_capacity=4, ring_depth=16)
    pc.PulseCommConfig(**ok, superstep=8)            # sane value
    with pytest.raises(ValueError, match="superstep"):
        pc.PulseCommConfig(**ok, superstep=0)
    with pytest.raises(ValueError, match="superstep"):
        pc.PulseCommConfig(**{**ok, "ring_depth": 120}, superstep=9)
    # boundary: B + D == 127 still fits the half-window
    pc.PulseCommConfig(**{**ok, "ring_depth": 120}, superstep=7)


def test_fabric_rejects_superstep_plus_latency_across_wrap():
    cfg = pc.PulseCommConfig(
        n_chips=4, neurons_per_chip=16, n_inputs_per_chip=16,
        event_capacity=16, bucket_capacity=4, ring_depth=16, superstep=100)
    # config alone passes (100 + 16 < 128) ...
    fb.PulseFabric(cfg, transport="local")
    # ... but a 2-hop ring at link_latency=6 adds 12 steps of path latency
    with pytest.raises(ValueError, match="superstep.*path latency"):
        fb.PulseFabric(cfg, transport=tpo.ring(4, link_latency=6))


def test_step_requires_unbatched_schedule():
    cfg, ebs, tables, rings = _setup(2)
    fab = fb.PulseFabric(cfg, transport="local")
    with pytest.raises(ValueError, match="superstep"):
        fab.step(ebs[0], tables, rings)
    # and superstep() validates the block size
    with pytest.raises(ValueError, match="substeps"):
        block = jax.tree.map(lambda *xs: jnp.stack(xs), *ebs[:4])
        fab.superstep(block, tables, rings)


def test_network_guards():
    from repro.snn import network as net

    comm = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=16, n_inputs_per_chip=16,
        event_capacity=16, bucket_capacity=16, ring_depth=8, superstep=2)
    cfg = net.NetworkConfig(comm=comm)
    params = net.init_params(jax.random.PRNGKey(0), cfg)
    state = net.init_state(cfg, params)
    ext = jnp.zeros((3, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="superstep"):
        net.step(cfg, params, state, ext[0])
    with pytest.raises(ValueError, match="multiple"):
        net.run(cfg, params, state, ext)


# ---------------------------------------------------------------------------
# Network: blocked scan ≡ per-step scan
# ---------------------------------------------------------------------------

def _ff_network(B, n=32, delay=4, T=40):
    from repro.snn import network as net

    comm = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=n, ring_depth=8, superstep=B)
    cfg = net.NetworkConfig(comm=comm, neuron_model="lif")
    table = rt.feedforward_table(n, src_chip=0, dst_chip=1, delay=delay)
    params = net.init_params(jax.random.PRNGKey(0), cfg, table=table)
    w = np.zeros((2, n, n), np.float32)
    w[0] = 1.5 * np.eye(n)
    w[1] = 0.6 * np.eye(n)
    params = params._replace(
        crossbar=params.crossbar._replace(w=jnp.asarray(w)))
    state = net.init_state(cfg, params)
    ext = np.zeros((T, 2, n), np.float32)
    ext[::4, 0, :] = 1.0
    return cfg, params, state, jnp.asarray(ext)


@pytest.mark.parametrize("B", [2, 4])
def test_network_run_blocked_matches_per_step(B):
    from repro.snn import network as net

    cfg1, p1, s1, e1 = _ff_network(1)
    _, rec1 = net.run(cfg1, p1, s1, e1)
    cfgB, pB, sB, eB = _ff_network(B)
    finB, recB = net.run(cfgB, pB, sB, eB)
    assert recB.spikes.shape == rec1.spikes.shape     # records stay [T,...]
    np.testing.assert_array_equal(np.asarray(rec1.spikes),
                                  np.asarray(recB.spikes))
    np.testing.assert_array_equal(np.asarray(rec1.voltage),
                                  np.asarray(recB.voltage))
    assert (int(np.asarray(recB.stats.sent).sum())
            == int(np.asarray(rec1.stats.sent).sum()))
    assert int(np.asarray(recB.stats.expired).sum()) == 0
    # the fused exchange fired once per block: per-step link words are only
    # attributed to flush substeps, but block totals match the B=1 run
    assert (int(np.asarray(recB.stats.link_words).sum())
            == int(np.asarray(rec1.stats.link_words).sum()))


def test_network_run_plastic_blocked_matches_per_step():
    from repro.snn import network as net

    cfg1, p1, s1, e1 = _ff_network(1, T=24)
    fp1, _, rp1, _ = net.run_plastic(cfg1, p1, s1, e1)
    cfg2, p2, s2, e2 = _ff_network(2, T=24)
    fp2, _, rp2, _ = net.run_plastic(cfg2, p2, s2, e2)
    np.testing.assert_array_equal(np.asarray(rp1.spikes),
                                  np.asarray(rp2.spikes))
    np.testing.assert_array_equal(np.asarray(fp1.crossbar.w),
                                  np.asarray(fp2.crossbar.w))


# ---------------------------------------------------------------------------
# Conservation under deferral (flow control + merge congestion)
# ---------------------------------------------------------------------------

def test_flow_control_with_sendq_conserves_under_superstep():
    B = 2
    cfg, ebs, tables, rings = _setup(B, rate=0.9)
    fab = fb.PulseFabric(
        cfg, transport="local",
        flow=fb.FlowControlConfig(capacity=2, drain_rate=1,
                                  retransmit_depth=64))
    ring, flow, sendq = rings, None, None
    tot = dict(sent=0, stalled=0, expired=0, overflow=0)
    for blk in range(len(ebs) // B):
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *ebs[blk * B:(blk + 1) * B])
        res = fab.superstep(block, tables, ring, flow, None, sendq)
        ring = dl.DelayRing(res.ring.ring, res.ring.now + B)
        flow, sendq = res.flow, res.sendq
        for f in tot:
            tot[f] += int(np.asarray(getattr(res.stats, f)).sum())
    deposited = int(np.asarray(ring.ring).sum())
    queued = int(np.asarray(sendq.occupancy()).sum())
    assert tot["sent"] == (deposited + tot["expired"] + tot["overflow"]
                           + tot["stalled"] + queued)


def test_merge_congestion_stragglers_expire_never_ghost():
    """With slack barely above the deferral and a rate-1 merge, congested
    events can only be emitted *after* their slot was popped — they must
    land in ``expired``, and the ring must never carry a deposit in a slot
    whose pop already passed (ghost one revolution later)."""
    B = 4
    T = 16
    cfg, ebs, tables, rings = _setup(
        B, mode="full", merge_rate=1, merge_depth=64, T=T,
        min_delay=B + 1, max_delay=B + 3, rate=0.9, bpc=1, cap=32)
    fab = fb.PulseFabric(cfg, transport="local")
    ring, merge = rings, fab.init_merge()
    tot = dict(sent=0, expired=0, overflow=0, merge_dropped=0)
    deposited = 0
    for blk in range(T // B):
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *ebs[blk * B:(blk + 1) * B])
        res = fab.superstep(block, tables, ring, None, merge)
        merge = res.merge
        for f in tot:
            tot[f] += int(np.asarray(getattr(res.stats, f)).sum())
        # pop every substep's slot like the network does: anything the
        # flush left behind in a passed slot would surface as a ghost one
        # ring revolution later
        ring = dl.DelayRing(res.ring.ring, res.ring.now)
        for _ in range(B):
            ring, spikes = jax.vmap(dl.pop_current)(ring)
            deposited += int(np.asarray(spikes).sum())
            ring = jax.vmap(dl.tick)(ring)
    # drain the remaining ring horizon — every deliverable spike pops
    for _ in range(cfg.ring_depth):
        ring, spikes = jax.vmap(dl.pop_current)(ring)
        deposited += int(np.asarray(spikes).sum())
        ring = jax.vmap(dl.tick)(ring)
    assert int(np.asarray(ring.ring).sum()) == 0, "ghost deposits remain"
    queued = int(np.asarray(merge.occupancy()).sum())
    assert tot["sent"] == (deposited + tot["expired"] + tot["overflow"]
                           + tot["merge_dropped"] + queued)
    assert tot["expired"] > 0, "congested stragglers must expire"


# ---------------------------------------------------------------------------
# Cached jitted drivers: no per-call retracing
# ---------------------------------------------------------------------------

def test_jitted_drivers_trace_once_per_signature():
    cfg, ebs, tables, rings = _setup(1)
    fab = fb.PulseFabric(cfg, transport="local")
    step = fab.jit_step()
    assert step is fab.jit_step()        # one wrapper per fabric
    r1 = step(ebs[0], tables, rings)
    r2 = step(ebs[1], tables, r1.ring)
    step(ebs[2], tables, r2.ring)
    assert fab.trace_counts["step"] == 1

    cfgB, ebsB, tablesB, ringsB = _setup(2)
    fabB = fb.PulseFabric(cfgB, transport="local")
    sstep = fabB.jit_superstep()
    block = jax.tree.map(lambda *xs: jnp.stack(xs), *ebsB[:2])
    res = sstep(block, tablesB, ringsB)
    sstep(block, tablesB, res.ring)
    assert fabB.trace_counts["superstep"] == 1
