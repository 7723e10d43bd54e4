"""Switched-topology subsystem: the route compiler must emit consistent
forwarding tables, the hop-by-hop RoutedTransport must deliver contents
bitwise-equal to the dense exchange (modulo the modeled hop latency on the
on-wire timestamp), per-link occupancy must match a pure-numpy walk of the
compiled routes, and the fabric over a torus / switch tree must stay
bitwise-identical between local and shard_map execution."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import delays as dl
from repro.core import events as ev
from repro.core import fabric as fb
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.core import topology as tpo
from repro.core import transport as tp

AXIS = "_test_topo_chip"


def _exchange_local(transport, x):
    """Run the routed exchange on [n_chips, n_chips, ...] data under the
    same internal-vmap named axis the fabric's local path uses."""
    return jax.vmap(lambda s: transport.exchange_words(s),
                    axis_name=AXIS)(x)


def _word_slabs(key, n, lanes, p_valid=0.7):
    """Random wire-word slabs [n, n, lanes] (holder, dest, lane)."""
    ks = jax.random.split(key, 3)
    addr = jax.random.randint(ks[0], (n, n, lanes), 0, 1 << ev.ADDR_BITS,
                              dtype=jnp.int32)
    time = jax.random.randint(ks[1], (n, n, lanes), 0, 4 * ev.TIME_MOD,
                              dtype=jnp.int32)
    valid = jax.random.uniform(ks[2], (n, n, lanes)) < p_valid
    return ev.encode_word(addr, time, valid)


TOPOLOGIES = [
    tpo.direct(6),
    tpo.ring(5),
    tpo.ring(6),
    tpo.torus2d(3, 4),
    tpo.torus2d(4, 4),
    tpo.torus3d(2, 2, 2),
    tpo.switch_tree(3, 4),
    tpo.switch_tree(1, 4),
    tpo.torus2d(1, 4),
]


# ---------------------------------------------------------------------------
# Route compiler
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", TOPOLOGIES, ids=lambda t: f"{t.kind}{t.dims}")
def test_route_tables_walk_to_destination(topo):
    """Following next[] from any source reaches the destination in exactly
    hops[] steps, each hop leaving on a valid port."""
    plan = tpo.compile_routes(topo)
    n = topo.n_chips
    for s in range(n):
        assert plan.port[s, s] == -1 and plan.hops[s, s] == 0
        for d in range(n):
            if s == d:
                continue
            assert 0 <= plan.port[s, d] < topo.n_ports
            if topo.kind == "switch_tree":
                continue   # tree hops traverse FPGA/switch, not chips
            c, h = s, 0
            while c != d:
                h += 1
                assert h <= n, "routing loop"
                c = int(plan.next[c, d])
            assert h == plan.hops[s, d]


def test_torus_routing_is_dimension_ordered():
    """DOR: the x (dim 0) displacement is corrected before dim 1 moves."""
    topo = tpo.torus2d(4, 4)
    plan = tpo.compile_routes(topo)
    for s in range(16):
        for d in range(16):
            c = s
            seen_dim1 = False
            while c != d:
                port = int(plan.port[c, d])
                if port // 2 == 1:
                    seen_dim1 = True
                else:
                    assert not seen_dim1, "dim0 hop after dim1 hop"
                c = int(plan.next[c, d])


def test_torus_hops_are_min_ring_distances():
    topo = tpo.torus2d(4, 4)
    plan = tpo.compile_routes(topo)
    for s in range(16):
        for d in range(16):
            sx, sy, dx, dy = s // 4, s % 4, d // 4, d % 4
            want = (min((dx - sx) % 4, (sx - dx) % 4)
                    + min((dy - sy) % 4, (sy - dy) % 4))
            assert plan.hops[s, d] == want
    assert plan.hops.max() == 4   # >= 3 hops: the multi-hop regime


def test_switch_tree_up_down_latency():
    topo = tpo.switch_tree(3, 4, link_latency=2, trunk_latency=5)
    plan = tpo.compile_routes(topo)
    for s in range(12):
        for d in range(12):
            if s == d:
                want_h, want_l = 0, 0
            elif s // 4 == d // 4:
                want_h, want_l = 2, 4            # chip→FPGA→chip
            else:
                want_h, want_l = 4, 14           # + switch up/down
            assert plan.hops[s, d] == want_h
            assert plan.latency[s, d] == want_l


def test_topology_constructor_validation():
    with pytest.raises(ValueError):
        tpo.Topology(kind="torus", n_chips=6, dims=(2, 2))
    with pytest.raises(ValueError):
        tpo.Topology(kind="switch_tree", n_chips=7, chips_per_group=4)
    with pytest.raises(ValueError):
        tpo.Topology(kind="mesh", n_chips=4)
    with pytest.raises(TypeError, match="single axis"):
        tpo.RoutedTransport(topology=tpo.ring(4), axis=("a", "b"))


# ---------------------------------------------------------------------------
# RoutedTransport: dense-equivalent delivery + modeled latency
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", TOPOLOGIES, ids=lambda t: f"{t.kind}{t.dims}")
def test_routed_delivery_matches_dense_modulo_latency(topo):
    n = topo.n_chips
    x = _word_slabs(jax.random.PRNGKey(n), n, 5)
    dense = tp.LocalTransport(n_chips=n).all_to_all(x)
    got, _, _ = _exchange_local(
        tpo.RoutedTransport(topology=topo, axis=AXIS), x)
    # delivered block from source s at chip d is the dense block with the
    # on-wire timestamp shifted by the compiled path latency
    lat = tpo.compile_routes(topo).latency
    dt = jnp.asarray(lat.T[:, :, None], jnp.int32)       # [dest, src, 1]
    t8 = ((dense & ev.WORD_TIME_MASK) + dt) & ev.WORD_TIME_MASK
    want = jnp.where(dense >= 0, (dense & ~ev.WORD_TIME_MASK) | t8, dense)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_zero_latency_is_bitwise_dense():
    topo = tpo.torus2d(4, 4, link_latency=0)
    n = topo.n_chips
    x = _word_slabs(jax.random.PRNGKey(3), n, 6)
    dense = tp.LocalTransport(n_chips=n).all_to_all(x)
    got, _, _ = _exchange_local(
        tpo.RoutedTransport(topology=topo, axis=AXIS), x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))


@pytest.mark.parametrize("topo", [
    tpo.ring(6, link_latency=1),
    tpo.torus2d(3, 4, link_latency=1),
    tpo.torus3d(2, 3, 2, link_latency=1),
    tpo.switch_tree(3, 4, link_latency=1, trunk_latency=2),
    tpo.direct(5, link_latency=2),
], ids=lambda t: f"{t.kind}{t.dims}")
def test_link_occupancy_matches_route_walk(topo):
    """The transport's traced per-port counters equal the pure-numpy walk
    of the compiled forwarding tables over the offered traffic matrix —
    including transit words a chip forwards on behalf of others."""
    n = topo.n_chips
    x = _word_slabs(jax.random.PRNGKey(n + 31), n, 6, p_valid=0.5)
    _, link_words, link_backlog = _exchange_local(
        tpo.RoutedTransport(topology=topo, axis=AXIS), x)
    traffic = np.asarray((x >= 0).sum(axis=-1))
    want = tpo.reference_link_words(topo, traffic)
    np.testing.assert_array_equal(np.asarray(link_words), want)
    assert int(np.asarray(link_backlog).sum()) == 0   # unbounded links


def test_link_backlog_counts_capacity_excess():
    n = 4
    topo = tpo.ring(n, link_bandwidth=2)
    x = _word_slabs(jax.random.PRNGKey(0), n, 8, p_valid=1.0)
    _, words, backlog = _exchange_local(
        tpo.RoutedTransport(topology=topo, axis=AXIS), x)
    assert int(np.asarray(backlog).sum()) > 0
    assert (np.asarray(backlog) <= np.asarray(words)).all()
    # credits are an alternative cap: the tighter one wins
    assert tpo.ring(n, link_bandwidth=4, link_credits=2).link_capacity == 2
    assert tpo.ring(n).link_capacity == 0


def test_transit_traffic_is_counted():
    """A 1-D ring: traffic from chip 0 to chip 2 must occupy chip 1's
    forward port even though chip 1 neither sends nor receives it."""
    n = 4
    topo = tpo.ring(n)
    x = jnp.full((n, n, 2), ev.WORD_SENTINEL, jnp.int32)
    x = x.at[0, 2].set(ev.encode_word(jnp.asarray([5, 9]),
                                      jnp.asarray([1, 2]),
                                      jnp.asarray([True, True])))
    _, words, _ = _exchange_local(
        tpo.RoutedTransport(topology=topo, axis=AXIS), x)
    words = np.asarray(words)
    np.testing.assert_array_equal(words[0], [2, 0])   # injects fwd
    np.testing.assert_array_equal(words[1], [2, 0])   # forwards in transit
    np.testing.assert_array_equal(words[2], [0, 0])   # destination
    np.testing.assert_array_equal(words[3], [0, 0])


# ---------------------------------------------------------------------------
# PulseFabric over a topology
# ---------------------------------------------------------------------------

def _fabric_setup(topo, n_neurons=24, mode="simplified", bpc=1, rate=0.5,
                  key=0, max_delay=8):
    n = topo.n_chips
    k = jax.random.PRNGKey(key)
    cfg = pc.PulseCommConfig(
        n_chips=n, neurons_per_chip=n_neurons, n_inputs_per_chip=n_neurons,
        event_capacity=n_neurons, bucket_capacity=8, buckets_per_chip=bpc,
        ring_depth=16, mode=mode, merge_rate=0)
    spikes = jax.random.uniform(k, (n, n_neurons)) < rate
    ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, n_neurons)[0])(spikes)
    table = rt.random_table(k, n_neurons, n, max_delay=max_delay,
                            min_delay=6)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                          table)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n_neurons))(
        jnp.arange(n))
    return cfg, ebs, tables, rings


@pytest.mark.parametrize("topo", [
    tpo.torus2d(4, 4, link_latency=0),
    tpo.switch_tree(4, 4, link_latency=0, trunk_latency=0),
    tpo.ring(16, link_latency=0),
    tpo.torus3d(4, 2, 2, link_latency=0),
], ids=lambda t: f"{t.kind}{t.dims}")
def test_fabric_over_topology_zero_latency_matches_dense(topo):
    """Acceptance: PulseFabric over a >= 3-hop torus2d and a switch_tree
    delivers the same spike trains as the dense transport (zero modeled
    latency -> bitwise: rings, delivered words, drop accounting)."""
    cfg, ebs, tables, rings = _fabric_setup(topo)
    dense = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    routed = fb.PulseFabric(cfg, transport=topo).step(ebs, tables, rings)
    np.testing.assert_array_equal(np.asarray(routed.ring.ring),
                                  np.asarray(dense.ring.ring))
    np.testing.assert_array_equal(np.asarray(routed.delivered.words),
                                  np.asarray(dense.delivered.words))
    for f in ("sent", "overflow", "expired", "wire_bytes", "traffic"):
        np.testing.assert_array_equal(
            np.asarray(getattr(routed.stats, f)),
            np.asarray(getattr(dense.stats, f)), err_msg=f)
    # per-link stats reflect the topology's ports, not the single dense one
    assert routed.stats.link_words.shape == (cfg.n_chips, topo.n_ports)
    assert int(np.asarray(routed.stats.link_words).sum()) > 0


@pytest.mark.parametrize("topo", [
    tpo.torus2d(4, 4, link_latency=1),
    tpo.switch_tree(4, 4, link_latency=1, trunk_latency=1),
    tpo.ring(16, link_latency=1),
    tpo.torus3d(4, 2, 2, link_latency=1),
], ids=lambda t: f"{t.kind}{t.dims}")
def test_fabric_topology_latency_equals_compensated_dense_spike_trains(topo):
    """Acceptance (latency half): a routed network with per-hop latency
    delivers exactly the spike trains of a DENSE network whose routing
    table already adds the compiled per-pair path latency to every axonal
    delay — modeled hop latency lands on event deadlines, nothing else
    changes."""
    from repro.snn import network as net

    n, nn = topo.n_chips, 16
    comm = pc.PulseCommConfig(
        n_chips=n, neurons_per_chip=nn, n_inputs_per_chip=nn,
        event_capacity=nn, bucket_capacity=nn, ring_depth=16)
    key = jax.random.PRNGKey(5)
    table = rt.random_table(key, nn, n, max_delay=8, min_delay=4)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                          table)

    lat = jnp.asarray(tpo.compile_routes(topo).latency)    # [src, dst]
    # per source chip c: entry (i, k) toward dest_chip d gains lat[c, d]
    comp_delay = tables.delay + lat[
        jnp.arange(n)[:, None, None], tables.dest_chip]
    comp_tables = tables._replace(delay=comp_delay)

    cfg_routed = net.NetworkConfig(comm=comm, topology=topo)
    cfg_dense = net.NetworkConfig(comm=comm)
    params_r = net.init_params(key, cfg_routed, table=tables)
    params_d = params_r._replace(table=comp_tables)
    state_r = net.init_state(cfg_routed, params_r)
    state_d = net.init_state(cfg_dense, params_d)
    ext = 1.5 * (jax.random.uniform(key, (10, n, nn)) < 0.4)

    _, rec_r = net.run(cfg_routed, params_r, state_r, ext)
    _, rec_d = net.run(cfg_dense, params_d, state_d, ext)
    assert int(np.asarray(rec_d.spikes).sum()) > 0
    np.testing.assert_array_equal(np.asarray(rec_r.spikes),
                                  np.asarray(rec_d.spikes))


def test_fabric_rejects_wrap_breaking_latency():
    topo = tpo.ring(4, link_latency=100)   # max path latency 200 >= 128
    cfg, *_ = _fabric_setup(tpo.ring(4))
    with pytest.raises(ValueError, match="wrap"):
        fb.PulseFabric(cfg, transport=topo)


def test_fabric_rejects_chip_count_mismatch():
    cfg, *_ = _fabric_setup(tpo.ring(4))
    with pytest.raises(ValueError, match="chips"):
        fb.PulseFabric(cfg, transport=tpo.ring(8))


def test_overlong_path_latency_expires_instead_of_ghosting():
    """An event whose deadline + path latency leaves the ring horizon is
    counted expired at deposit — hop latency consumes delay budget, the
    paper's loss mode when aggregation (here: transit) outruns it."""
    topo = tpo.ring(8, link_latency=6)     # up to 24 steps of transit
    cfg, ebs, tables, rings = _fabric_setup(topo, max_delay=8)
    dense = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    routed = fb.PulseFabric(cfg, transport=topo).step(ebs, tables, rings)
    assert int(np.asarray(routed.stats.expired).sum()) > \
        int(np.asarray(dense.stats.expired).sum())
    # conservation: everything sent is still accounted for
    sent = int(np.asarray(routed.stats.sent).sum())
    acc = (int(np.asarray(routed.stats.overflow).sum())
           + int(np.asarray(routed.stats.expired).sum())
           + int(np.asarray(routed.ring.ring).sum()))
    assert sent == acc


# ---------------------------------------------------------------------------
# local == shard_map over real (forced host) devices
# ---------------------------------------------------------------------------

_SHARD_EQUIV_SCRIPT = textwrap.dedent("""
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

        ref = fb.PulseFabric(cfg, transport=topo).step(ebs, tables, rings)

        shard = fb.PulseFabric(cfg, transport=topo.transport(axis="chip"))
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
        assert int(np.asarray(ref.stats.link_words).sum()) > 0
        print(f"TOPO_EQUIV_OK {topo.kind}")
    print("TOPOLOGY_SHARD_EQUIVALENCE_OK")
""")


def test_topology_local_and_shard_map_bitwise_equal():
    out = subprocess.run(
        [sys.executable, "-c", _SHARD_EQUIV_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "TOPOLOGY_SHARD_EQUIVALENCE_OK" in out.stdout, out.stderr[-3000:]
