"""Resilient pod-scale fabric: degraded route compilation must detour
around dead chips and cut links, the degraded executors must stay
bitwise-equal to the dense reference over surviving pairs, culled traffic
must be conserved in ``CommStats.lost_to_failure``, failure detection must
fire from the heartbeat / credit observables, and the headline drill —
kill chip c at step t under :class:`ResilientRunner` — must deliver spike
trains bitwise-equal to an uninterrupted degraded-topology run resumed
from the same committed checkpoint."""

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
from repro.core import resilience as rsl
from repro.core import routing as rt
from repro.core import topology as tpo
from repro.core import transport as tp
from repro.runtime import ChipFailure, RecoveryEvent, ResilientRunner
from repro.snn import network as net

AXIS = "_test_resil_chip"


def _exchange_local(transport, x):
    return jax.vmap(lambda s: transport.exchange_words(s),
                    axis_name=AXIS)(x)


def _word_slabs(key, n, lanes, p_valid=0.7):
    ks = jax.random.split(key, 3)
    addr = jax.random.randint(ks[0], (n, n, lanes), 0, 1 << ev.ADDR_BITS,
                              dtype=jnp.int32)
    time = jax.random.randint(ks[1], (n, n, lanes), 0, 4 * ev.TIME_MOD,
                              dtype=jnp.int32)
    valid = jax.random.uniform(ks[2], (n, n, lanes)) < p_valid
    return ev.encode_word(addr, time, valid)


def _mask_pairs(x, healthy, n):
    """Sentinel out every slab whose source or destination is dead — the
    fabric's culling guarantees the transport only sees such traffic."""
    alive = np.zeros(n, bool)
    alive[list(healthy)] = True
    keep = jnp.asarray(alive[:, None] & alive[None, :])
    return jnp.where(keep[:, :, None], x, ev.WORD_SENTINEL)


# ---------------------------------------------------------------------------
# Degraded route compiler
# ---------------------------------------------------------------------------

def test_normalize_health_forms():
    assert tpo.normalize_healthy(4, None) is None
    assert tpo.normalize_healthy(4, [3, 1]) == (1, 3)
    assert tpo.normalize_healthy(4, (0, 1, 2, 3)) is None     # full set
    assert tpo.normalize_healthy(4, np.array([True, False, True, True])) \
        == (0, 2, 3)
    assert tpo.normalize_dead_links([(2, 1), (0, 3)]) == ((0, 3), (2, 1))


def test_degraded_torus_routes_detour_around_dead_chip():
    """Kill the center of a 3x3 torus: every surviving pair still routes,
    the walk never enters the dead chip, and hop counts stay minimal
    under BFS (so paths only lengthen where the dead chip was on the
    unique shortest route)."""
    topo = tpo.torus2d(3, 3)
    dead = 4
    healthy = tuple(c for c in range(9) if c != dead)
    plan = tpo.compile_routes(topo, healthy=healthy)
    base = tpo.compile_routes(topo)
    for s in healthy:
        for d in healthy:
            if s == d:
                continue
            c, h = s, 0
            while c != d:
                assert c != dead, f"route {s}->{d} enters dead chip"
                h += 1
                assert h <= 9, "routing loop"
                c = int(plan.next[c, d])
            assert h == plan.hops[s, d]
            assert plan.hops[s, d] >= base.hops[s, d]   # detours only add
    # rows/cols of the dead chip are unreachable
    for c in healthy:
        assert plan.hops[c, dead] == -1 and plan.port[c, dead] == -1
        assert plan.hops[dead, c] == -1


def test_degraded_ring_cut_link_goes_the_long_way():
    """Cutting one ring link (bidirectionally) forces the full detour:
    the 1-hop neighbor pair becomes an (n-1)-hop path."""
    n = 6
    topo = tpo.ring(n)
    plan = tpo.compile_routes(topo, dead_links=(((0, 0)),))  # 0's fwd link
    assert plan.hops[0, 1] == n - 1     # backward all the way around
    assert plan.hops[1, 0] == n - 1     # reverse direction is cut too
    assert plan.hops[0, 5] == 1         # untouched direction still short
    # latency follows the recompiled path
    assert plan.latency[0, 1] == (n - 1) * topo.link_latency


def test_degraded_direct_link_kill_isolates_chip():
    plan = tpo.compile_routes(tpo.direct(4), dead_links=((2, 0),))
    for s in range(4):
        if s == 2:
            continue
        assert plan.hops[s, 2] == -1
        assert plan.hops[2, s] == -1
        for d in range(4):
            if d not in (2, s):
                assert plan.hops[s, d] == 1     # others unaffected


def test_degraded_tree_rehomes_trunk_carrier():
    """Killing a group's trunk carrier re-homes the group's uplink share
    to the lowest-index healthy sibling; cross-group routes survive."""
    topo = tpo.switch_tree(3, 4)
    up, down = tpo.tree_carriers(topo)
    carrier = int(up[0])                # group 0's uplink carrier
    healthy = tuple(c for c in range(12) if c != carrier)
    plan = tpo.compile_routes(topo, healthy=healthy)
    up2, down2 = tpo.tree_carriers(topo, healthy)
    assert int(up2[0]) != carrier and int(up2[0]) // 4 == 0
    for s in healthy:
        for d in healthy:
            want = 0 if s == d else (2 if s // 4 == d // 4 else 4)
            assert plan.hops[s, d] == want


def test_degraded_plan_is_cached():
    a = tpo.compile_routes(tpo.torus2d(3, 3), healthy=(0, 1, 2, 3, 5, 6, 7, 8))
    b = tpo.compile_routes(tpo.torus2d(3, 3),
                           healthy=np.array([1, 1, 1, 1, 0, 1, 1, 1, 1],
                                            bool))
    assert a is b                       # same normalized key


# ---------------------------------------------------------------------------
# Degraded executors: delivery + occupancy
# ---------------------------------------------------------------------------

DEGRADED_CASES = [
    (tpo.torus2d(3, 3, link_latency=0), (0, 1, 2, 3, 5, 6, 7, 8), ()),
    (tpo.torus2d(3, 3, link_latency=1), (0, 1, 2, 3, 5, 6, 7, 8), ()),
    (tpo.ring(6, link_latency=1), (0, 1, 2, 3, 4, 5), ((0, 0),)),
    (tpo.torus3d(2, 2, 2, link_latency=1), (0, 1, 2, 3, 4, 6, 7), ()),
    (tpo.switch_tree(3, 4, link_latency=1, trunk_latency=2),
     (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11), ()),
]


@pytest.mark.parametrize("topo,healthy,dead_links", DEGRADED_CASES,
                         ids=lambda v: str(v)[:24])
def test_degraded_delivery_matches_dense_over_survivors(topo, healthy,
                                                        dead_links):
    """The degraded executor (cube relay on the torus, re-homed trunk on
    the tree) delivers surviving-pair traffic bitwise-equal to the dense
    exchange with the DEGRADED plan's path latency on the timestamp."""
    n = topo.n_chips
    x = _mask_pairs(_word_slabs(jax.random.PRNGKey(n), n, 5), healthy, n)
    dense = tp.LocalTransport(n_chips=n).all_to_all(x)
    tr = tpo.RoutedTransport(topology=topo, axis=AXIS, healthy=healthy,
                             dead_links=dead_links)
    got, _, _ = _exchange_local(tr, x)
    lat = tr.plan.latency
    dt = jnp.asarray(np.maximum(lat.T, 0)[:, :, None], jnp.int32)
    t8 = ((dense & ev.WORD_TIME_MASK) + dt) & ev.WORD_TIME_MASK
    want = jnp.where(dense >= 0, (dense & ~ev.WORD_TIME_MASK) | t8, dense)
    hz = list(healthy)
    np.testing.assert_array_equal(
        np.asarray(got)[hz][:, hz], np.asarray(want)[hz][:, hz])


@pytest.mark.parametrize("topo,healthy,dead_links", DEGRADED_CASES,
                         ids=lambda v: str(v)[:24])
def test_degraded_occupancy_matches_reference_walk(topo, healthy,
                                                   dead_links):
    n = topo.n_chips
    x = _mask_pairs(_word_slabs(jax.random.PRNGKey(n + 7), n, 6,
                                p_valid=0.5), healthy, n)
    tr = tpo.RoutedTransport(topology=topo, axis=AXIS, healthy=healthy,
                             dead_links=dead_links)
    _, link_words, _ = _exchange_local(tr, x)
    traffic = np.asarray((x >= 0).sum(axis=-1))
    want = tpo.reference_link_words(topo, traffic, healthy=healthy,
                                    dead_links=dead_links)
    np.testing.assert_array_equal(np.asarray(link_words), want)


def test_pod_delivery_matches_dense_modulo_latency():
    """Two-level pod composition on the local path: dense intra-pod tier
    + routed pod graph delivers bitwise-equal to one flat dense exchange
    (with the compiled two-level latency on the timestamp)."""
    for pg, cpp in [(tpo.ring(3), 2), (tpo.direct(2), 3),
                    (tpo.switch_tree(1, 2), 4)]:
        topo = tpo.pod(pg, cpp)
        n = topo.n_chips
        x = _word_slabs(jax.random.PRNGKey(n), n, 4)
        dense = tp.LocalTransport(n_chips=n).all_to_all(x)
        got, link_words, _ = _exchange_local(
            tpo.RoutedTransport(topology=topo, axis=AXIS), x)
        lat = tpo.compile_routes(topo).latency
        dt = jnp.asarray(lat.T[:, :, None], jnp.int32)
        t8 = ((dense & ev.WORD_TIME_MASK) + dt) & ev.WORD_TIME_MASK
        want = jnp.where(dense >= 0, (dense & ~ev.WORD_TIME_MASK) | t8,
                         dense)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        traffic = np.asarray((x >= 0).sum(axis=-1))
        np.testing.assert_array_equal(
            np.asarray(link_words),
            tpo.reference_link_words(topo, traffic))


# ---------------------------------------------------------------------------
# Fabric: culling + lost_to_failure conservation
# ---------------------------------------------------------------------------

def _fabric_setup(n, n_neurons=24, key=0):
    k = jax.random.PRNGKey(key)
    cfg = pc.PulseCommConfig(
        n_chips=n, neurons_per_chip=n_neurons, n_inputs_per_chip=n_neurons,
        event_capacity=n_neurons, bucket_capacity=8, ring_depth=16)
    spikes = jax.random.uniform(k, (n, n_neurons)) < 0.5
    ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, n_neurons)[0])(spikes)
    table = rt.random_table(k, n_neurons, n, max_delay=8, min_delay=6)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                          table)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n_neurons))(
        jnp.arange(n))
    return cfg, ebs, tables, rings


def test_lost_to_failure_conservation():
    """With a dead chip, everything sent is still accounted for AT EVERY
    STEP: sent == overflow + expired + deposited + lost_to_failure, the
    lost bucket is non-empty, and no traffic crosses the dead chip."""
    n, dead = 6, 3
    healthy = tuple(c for c in range(n) if c != dead)
    cfg, ebs, tables, rings = _fabric_setup(n)
    fab = fb.PulseFabric(cfg, transport=tpo.ring(n, link_latency=0),
                         healthy=healthy)
    total_lost = 0
    res = None
    for step in range(4):
        _, ebs_t, *_ = _fabric_setup(n, key=step)
        before = int(np.asarray(rings.ring).sum())
        res = fab.step(ebs_t, tables, rings)
        rings = res.ring
        lost = int(np.asarray(res.stats.lost_to_failure).sum())
        deposited = int(np.asarray(rings.ring).sum()) - before
        obs.check_conservation(res.stats, delivered=deposited)
        traffic = np.asarray(res.stats.traffic)
        assert traffic[dead].sum() == 0 and traffic[:, dead].sum() == 0
        total_lost += lost
    assert total_lost > 0
    # the healthy baseline loses nothing
    ref = fb.PulseFabric(cfg, transport=tpo.ring(n, link_latency=0)).step(
        ebs, tables, rings)
    assert int(np.asarray(ref.stats.lost_to_failure).sum()) == 0


def test_degrade_swaps_plan_and_preserves_survivor_streams():
    """``degrade()`` at a recovery boundary: the degraded fabric delivers
    the same words to surviving chips as a fabric constructed degraded
    from scratch (plan swap is pure), and full health is the identity."""
    n, dead = 6, 2
    healthy = tuple(c for c in range(n) if c != dead)
    cfg, ebs, tables, rings = _fabric_setup(n)
    base = fb.PulseFabric(cfg, transport=tpo.ring(n, link_latency=0))
    a = base.degrade(healthy=healthy).step(ebs, tables, rings)
    b = fb.PulseFabric(cfg, transport=tpo.ring(n, link_latency=0),
                       healthy=healthy).step(ebs, tables, rings)
    np.testing.assert_array_equal(np.asarray(a.ring.ring),
                                  np.asarray(b.ring.ring))
    c = base.degrade().step(ebs, tables, rings)
    d = base.step(ebs, tables, rings)
    np.testing.assert_array_equal(np.asarray(c.ring.ring),
                                  np.asarray(d.ring.ring))


def test_dense_transport_rejects_dead_links():
    cfg, *_ = _fabric_setup(4)
    with pytest.raises(ValueError, match="dead_links"):
        fb.PulseFabric(cfg, transport="local", dead_links=((0, 0),))


# ---------------------------------------------------------------------------
# Detection: heartbeat + credit watch + injector
# ---------------------------------------------------------------------------

def test_heartbeat_observe_declares_silent_chip_dead():
    hc = rsl.HealthConfig(n_chips=4, credit_timeout=2)
    st = rsl.health_init(hc)
    truth = rsl.FabricFaultInjector(n_chips=4, chip_failures=((1, 3),))
    declared_at = None
    for t in range(10):
        beats = rsl.beats_local(truth.alive_at(t))
        st = rsl.observe(hc, st, beats, t)
        if declared_at is None and not bool(st.alive[1]):
            declared_at = t
    # silent from step 3 -> last_heard 2 -> declared when t - 2 > 2
    assert declared_at == 5
    assert np.asarray(st.alive).tolist() == [True, False, True, True]
    # sticky-false: a late beat must not resurrect the chip
    st2 = rsl.observe(hc, st, jnp.ones(4, jnp.int32), 20)
    assert not bool(st2.alive[1])


def test_heartbeat_psum_matches_local_beats():
    """The one-psum shard_map heartbeat (here under the fabric's internal
    vmap axis) reduces to exactly the local alive-bit vector."""
    n = 4
    alive = jnp.asarray([True, True, False, True])
    got = jax.vmap(
        lambda b: rsl.heartbeat(tp.ShardMapTransport(axis=AXIS, n_chips=n),
                                b),
        axis_name=AXIS)(alive.astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(rsl.beats_local(alive)))


def test_credit_watch_suspects_stalled_outstanding_chip():
    """A chip with packets outstanding whose notification counter stops
    advancing is suspected after the timeout; an idle chip never is."""
    from repro.core import flowcontrol as fc

    hc = rsl.HealthConfig(n_chips=3, credit_timeout=2)
    w = rsl.credit_watch_init(hc)
    mk = lambda head, tail, notif: fc.RingState(
        head=jnp.asarray(head, jnp.int32), tail=jnp.asarray(tail, jnp.int32),
        notifications=jnp.asarray(notif, jnp.int32),
        capacity=jnp.asarray([8, 8, 8], jnp.int32))
    suspected = None
    for t in range(8):
        # chip 0: progressing; chip 1: outstanding + frozen; chip 2: idle
        flow = mk([4, 4, 0], [1, 1, 0], [t, 1, 0])
        w, suspected = rsl.credit_watch(hc, w, flow, t)
    assert np.asarray(suspected).tolist() == [False, True, False]


def test_fault_injector_masks_and_statics():
    inj = rsl.FabricFaultInjector(n_chips=4, chip_failures=((2, 5),),
                                  link_failures=((1, 0, 7),))
    np.testing.assert_array_equal(np.asarray(inj.alive_at(4)),
                                  [True, True, True, True])
    np.testing.assert_array_equal(np.asarray(jax.jit(inj.alive_at)(5)),
                                  [True, True, False, True])
    assert inj.healthy_after(4) == (0, 1, 2, 3)
    assert inj.healthy_after(5) == (0, 1, 3)
    assert inj.dead_links_after(6) == ()
    assert inj.dead_links_after(7) == ((1, 0),)
    with pytest.raises(ValueError, match="out of range"):
        rsl.FabricFaultInjector(n_chips=2, chip_failures=((5, 0),))


# ---------------------------------------------------------------------------
# Headline drill: kill chip c at step t under ResilientRunner
# ---------------------------------------------------------------------------

N_DRILL, NN_DRILL, DEAD, KILL_AT, T_DRILL = 4, 16, 2, 7, 12


def _drill_network(telemetry=None):
    topo = tpo.ring(N_DRILL, link_latency=0)
    comm = pc.PulseCommConfig(
        n_chips=N_DRILL, neurons_per_chip=NN_DRILL,
        n_inputs_per_chip=NN_DRILL, event_capacity=NN_DRILL,
        bucket_capacity=NN_DRILL, ring_depth=16)
    cfg = net.NetworkConfig(comm=comm, topology=topo, telemetry=telemetry)
    key = jax.random.PRNGKey(11)
    params = net.init_params(key, cfg)
    return cfg, params, net.init_state(cfg, params)


def _ext_at(t):
    return 1.5 * (jax.random.uniform(jax.random.PRNGKey(100 + t),
                                     (N_DRILL, NN_DRILL)) < 0.4)


def _drill_make_step(cfg, params, injector):
    """make_step(healthy) for the drill: the injector's masks emulate the
    real death (dead chips stop emitting and their carries freeze); the
    degraded cfg culls their traffic."""
    import dataclasses as _dc

    def make_step(healthy):
        hcfg = _dc.replace(cfg, healthy=tuple(healthy))

        def step_fn(state, t):
            alive = injector.alive_at(t)
            ext = _ext_at(t) * alive[:, None]
            new_state, rec = net.step(hcfg, params, state, ext)
            per_chip = ((state.neuron, state.ring),
                        (new_state.neuron, new_state.ring))
            fzn, fzr = rsl.freeze(alive, *per_chip)
            new_state = new_state._replace(neuron=fzn, ring=fzr)
            rec = rec._replace(
                spikes=rec.spikes * alive[:, None].astype(rec.spikes.dtype))
            return new_state, rec

        return step_fn

    def detect(state, t, healthy):
        surviving = tuple(c for c in injector.healthy_after(t)
                          if c in healthy)
        return surviving if surviving != tuple(healthy) else None

    return make_step, detect


def test_resilient_runner_drill_matches_degraded_reference(tmp_path):
    """Kill chip DEAD at step KILL_AT.  The recovered run's spike trains
    from the resume point on must be bitwise-equal to an uninterrupted
    run on the degraded topology resumed from the same committed
    checkpoint — the replayed SendQueue/ring state carries the in-flight
    events across the recovery boundary."""
    from repro import checkpoint as ckpt

    cfg, params, init_state = _drill_network()
    injector = rsl.FabricFaultInjector(n_chips=N_DRILL,
                                       chip_failures=((DEAD, KILL_AT),))
    make_step, detect = _drill_make_step(cfg, params, injector)

    runner = ResilientRunner(make_step=make_step, detect=detect,
                             ckpt_dir=str(tmp_path / "drill"),
                             n_chips=N_DRILL, ckpt_every=3)
    final, healthy = runner.run(init_state, T_DRILL)
    assert healthy == tuple(c for c in range(N_DRILL) if c != DEAD)
    assert runner.recoveries == [RecoveryEvent(
        detected_at=KILL_AT, resumed_from=6, healthy=healthy)]
    assert sorted(runner.records) == list(range(T_DRILL))

    # uninterrupted degraded reference from the same committed checkpoint
    resume_at = runner.recoveries[0].resumed_from
    ref_state = ckpt.restore(str(tmp_path / "drill"), resume_at - 1,
                             jax.tree.map(jnp.zeros_like, init_state))
    ref_step = make_step(healthy)
    spikes_ok = 0
    for t in range(resume_at, T_DRILL):
        ref_state, ref_rec = ref_step(ref_state, t)
        got = np.asarray(runner.records[t].spikes)
        want = np.asarray(ref_rec.spikes)
        np.testing.assert_array_equal(got, want, err_msg=f"step {t}")
        if t >= KILL_AT:
            assert got[DEAD].sum() == 0       # modulo chip-c events
        spikes_ok += got.sum()
    assert spikes_ok > 0                      # the drill exercised traffic
    for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(ref_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # post-recovery steps culled traffic toward the dead chip
    lost = sum(int(np.asarray(runner.records[t].stats.lost_to_failure).sum())
               for t in range(resume_at, T_DRILL))
    assert lost > 0


def test_flight_recorder_dumps_on_chip_failure(tmp_path):
    """Acceptance pin: the :class:`ChipFailure` path emits a
    flight-recorder JSONL post-mortem whose last K blocks are exactly the
    per-step stats the failing trajectory recorded (steps
    KILL_AT-K+1..KILL_AT), plus the failure row — and the run still
    recovers and finishes."""
    K = 4
    cfg, params, init_state = _drill_network(
        telemetry=obs.MetricsConfig(flight_depth=K))
    assert init_state.metrics is not None
    injector = rsl.FabricFaultInjector(n_chips=N_DRILL,
                                       chip_failures=((DEAD, KILL_AT),))
    make_step, detect = _drill_make_step(cfg, params, injector)
    runner = ResilientRunner(make_step=make_step, detect=detect,
                             ckpt_dir=str(tmp_path / "drill"),
                             n_chips=N_DRILL, ckpt_every=3,
                             flight_of=lambda s: s.metrics.flight,
                             flight_dir=str(tmp_path))
    final, healthy = runner.run(init_state, T_DRILL)
    assert healthy == tuple(c for c in range(N_DRILL) if c != DEAD)
    assert len(runner.flight_dumps) == 1

    dump = obs.load_flight(runner.flight_dumps[0])
    assert dump["meta"]["depth"] == K
    assert dump["meta"]["n_chips"] == N_DRILL
    assert dump["failure"]["step"] == KILL_AT
    blocks = dump["blocks"]
    assert [b["seq"] for b in blocks] == list(
        range(KILL_AT - K + 1, KILL_AT + 1))

    # The dump snapshots the FAILING trajectory (full-health step fn up
    # to KILL_AT); runner.records beyond the resume point were replayed
    # on the degraded mesh, so rebuild the reference by replaying the
    # deterministic pre-failure steps directly.
    ref_step = make_step(tuple(range(N_DRILL)))
    state, ref_stats = init_state, {}
    for t in range(KILL_AT + 1):
        state, rec = ref_step(state, t)
        ref_stats[t] = rec.stats
    for b in blocks:
        for fld in ("sent", "overflow", "expired", "stalled",
                    "lost_to_failure"):
            want = np.asarray(getattr(ref_stats[b["seq"]], fld))
            want = want.sum(0) if want.ndim > 1 else want
            np.testing.assert_array_equal(
                np.asarray(b["per_chip"][fld]), want,
                err_msg=f"flight block {b['seq']} field {fld}")
        for fld, fleet in b["fleet"].items():
            assert fleet == sum(b["per_chip"][fld]), (b["seq"], fld)


def test_resilient_runner_gives_up_after_max_recoveries(tmp_path):
    cfg, params, init_state = _drill_network()
    injector = rsl.FabricFaultInjector(
        n_chips=N_DRILL, chip_failures=((0, 1), (1, 2), (2, 3)))
    make_step, detect = _drill_make_step(cfg, params, injector)
    runner = ResilientRunner(make_step=make_step, detect=detect,
                             ckpt_dir=str(tmp_path / "giveup"),
                             n_chips=N_DRILL, ckpt_every=100,
                             max_recoveries=1)
    with pytest.raises(ChipFailure):
        runner.run(init_state, 8)
    assert len(runner.recoveries) == 1


# ---------------------------------------------------------------------------
# local == shard_map on the recovery path + the (pod, chip) mesh
# ---------------------------------------------------------------------------

_DEGRADED_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import delays as dl, events as ev, fabric as fb
    from repro.core import pulse_comm as pc, routing as rt, topology as tpo

    n, N = 8, 16
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("chip",))
    key = jax.random.PRNGKey(0)
    healthy = (0, 1, 2, 4, 5, 6, 7)       # chip 3 dead

    for topo in [tpo.torus2d(2, 4, link_latency=1),
                 tpo.switch_tree(2, 4, link_latency=1, trunk_latency=1)]:
        cfg = pc.PulseCommConfig(
            n_chips=n, neurons_per_chip=N, n_inputs_per_chip=N,
            event_capacity=N, bucket_capacity=4, buckets_per_chip=2,
            ring_depth=16)
        spikes = jax.random.uniform(key, (n, N)) < 0.6
        ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, N)[0])(spikes)
        table = rt.random_table(key, N, n, max_delay=8, min_delay=4)
        tables = jax.tree.map(lambda z: jnp.broadcast_to(z, (n,) + z.shape),
                              table)
        rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, N))(jnp.arange(n))

        ref = fb.PulseFabric(cfg, transport=topo, healthy=healthy).step(
            ebs, tables, rings)

        shard = fb.PulseFabric(cfg, transport=topo.transport(axis="chip"),
                               healthy=healthy)
        def body(e, t, r):
            sq = lambda z: jax.tree.map(lambda a: a[0], z)
            out = shard.step(sq(e), sq(t), sq(r))
            return jax.tree.map(lambda a: a[None] if hasattr(a, "ndim")
                                else a, out)
        got = shard_map(body, mesh=mesh, in_specs=(P("chip"),) * 3,
                        out_specs=P("chip"), check_vma=False)(
            ebs, tables, rings)

        np.testing.assert_array_equal(np.asarray(got.ring.ring),
                                      np.asarray(ref.ring.ring))
        np.testing.assert_array_equal(np.asarray(got.delivered.words),
                                      np.asarray(ref.delivered.words))
        for f in pc.CommStats._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got.stats, f)),
                np.asarray(getattr(ref.stats, f)), err_msg=f)
        assert int(np.asarray(ref.stats.lost_to_failure).sum()) > 0
        print(f"DEGRADED_EQUIV_OK {topo.kind}")
    print("DEGRADED_SHARD_EQUIVALENCE_OK")
""")


def test_degraded_local_and_shard_map_bitwise_equal():
    out = subprocess.run(
        [sys.executable, "-c", _DEGRADED_SHARD_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "DEGRADED_SHARD_EQUIVALENCE_OK" in out.stdout, out.stderr[-3000:]


_POD_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import events as ev, topology as tpo

    npods, cpp = 2, 4
    topo = tpo.pod(tpo.ring(npods), cpp)
    n = topo.n_chips
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    addr = jax.random.randint(ks[0], (n, n, 4), 0, 1 << ev.ADDR_BITS,
                              dtype=jnp.int32)
    time = jax.random.randint(ks[1], (n, n, 4), 0, 4 * ev.TIME_MOD,
                              dtype=jnp.int32)
    valid = jax.random.uniform(ks[2], (n, n, 4)) < 0.7
    x = ev.encode_word(addr, time, valid)

    AX = "_pod_test_chip"
    ref = jax.vmap(
        lambda s: tpo.RoutedTransport(topology=topo, axis=AX)
        .exchange_words(s), axis_name=AX)(x)

    mesh = Mesh(np.asarray(jax.devices()).reshape(npods, cpp),
                ("pod", "chip"))
    tr = topo.transport(axis=("pod", "chip"))
    def body(s):
        out = tr.exchange_words(jax.tree.map(lambda a: a[0], s))
        return jax.tree.map(lambda a: a[None], out)
    got = shard_map(body, mesh=mesh, in_specs=P(("pod", "chip")),
                    out_specs=P(("pod", "chip")), check_vma=False)(x)

    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("POD_MESH_EQUIVALENCE_OK")
""")


def test_pod_two_level_mesh_matches_local():
    out = subprocess.run(
        [sys.executable, "-c", _POD_MESH_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "POD_MESH_EQUIVALENCE_OK" in out.stdout, out.stderr[-3000:]
