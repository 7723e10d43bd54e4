"""Transport-layer equivalence: LocalTransport (single device, explicit chip
axis) must match ShardMapTransport (real collectives).  The shard_map side
needs >1 device, so it runs in a subprocess with forced host devices —
keeping this process at 1 device for the smoke tests."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transport as tp


def test_local_all_to_all_is_transpose():
    n = 4
    x = jnp.arange(n * n * 2).reshape(n, n, 2)
    t = tp.LocalTransport(n_chips=n)
    y = t.all_to_all(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x).swapaxes(0, 1))
    # involution
    np.testing.assert_array_equal(np.asarray(t.all_to_all(y)), np.asarray(x))


def test_local_put_permutes():
    n = 3
    x = jnp.arange(n * 2).reshape(n, 2)
    t = tp.LocalTransport(n_chips=n)
    y = t.put(x, [(0, 1), (1, 2), (2, 0)])
    np.testing.assert_array_equal(np.asarray(y)[1], np.asarray(x)[0])
    np.testing.assert_array_equal(np.asarray(y)[0], np.asarray(x)[2])


def test_local_psum_broadcasts_to_all_chips():
    """Regression: LocalTransport.psum must hand EVERY chip the cross-chip
    sum (ShardMapTransport semantics), not a collapsed [1, ...] row."""
    n = 4
    x = jnp.arange(n * 3, dtype=jnp.int32).reshape(n, 3)
    t = tp.LocalTransport(n_chips=n)
    y = t.psum(x)
    assert y.shape == x.shape
    want = np.broadcast_to(np.asarray(x).sum(axis=0, keepdims=True), x.shape)
    np.testing.assert_array_equal(np.asarray(y), want)


def test_exchange_matrix_counts():
    dest = jnp.asarray([0, 1, 1, 2, 0], jnp.int32)
    valid = jnp.asarray([1, 1, 0, 1, 1], dtype=bool)
    m = tp.exchange_matrix(dest, valid, 3)
    np.testing.assert_array_equal(np.asarray(m), [2, 1, 1])


def test_exchange_matrix_scatter_matches_onehot_reference():
    """Regression for the O(E) scatter-add rewrite: identical to the one-hot
    O(E·n_chips) reduction on random streams, including out-of-range and
    negative destinations (both implementations must ignore them) and
    all-invalid streams."""
    key = jax.random.PRNGKey(7)
    for n_chips in (1, 3, 8):
        for e in (1, 17, 256):
            k1, k2 = jax.random.split(jax.random.fold_in(key, n_chips * e))
            dest = jax.random.randint(k1, (e,), -2, n_chips + 2,
                                      dtype=jnp.int32)
            valid = jax.random.uniform(k2, (e,)) < 0.6
            got = tp.exchange_matrix(dest, valid, n_chips)
            want = tp._exchange_matrix_onehot(dest, valid, n_chips)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # all-invalid
    dest = jnp.asarray([0, 1], jnp.int32)
    got = tp.exchange_matrix(dest, jnp.zeros((2,), bool), 2)
    np.testing.assert_array_equal(np.asarray(got), [0, 0])
    # the jit static-argname contract survives: n_chips stays static
    jitted = jax.jit(lambda d, v: tp.exchange_matrix(d, v, 4))
    np.testing.assert_array_equal(
        np.asarray(jitted(jnp.asarray([3, 3], jnp.int32),
                          jnp.asarray([True, True]))),
        [0, 0, 0, 2])


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import transport as tp

    n = 8
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("chip",))
    x = jnp.arange(n * n * 4, dtype=jnp.int32).reshape(n, n, 4)

    local = tp.LocalTransport(n_chips=n)
    want = local.all_to_all(x)

    sm_t = tp.ShardMapTransport(axis="chip", n_chips=n)
    f = shard_map(lambda s: sm_t.all_to_all(s), mesh=mesh,
                  in_specs=P("chip"), out_specs=P("chip"))
    got = f(x.reshape(n * n, 4)).reshape(n, n, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # ppermute ring == local put
    perm = [(i, (i + 1) % n) for i in range(n)]
    want_p = local.put(x[:, 0, :], perm)
    g = shard_map(lambda s: sm_t.put(s, perm), mesh=mesh,
                  in_specs=P("chip"), out_specs=P("chip"))
    got_p = g(x[:, 0, :])
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))

    # multi-chip SNN comm step: shard_map path == local path
    from repro.core import delays as dl, events as ev, pulse_comm as pc, routing as rt
    key = jax.random.PRNGKey(0)
    N, E = 16, 16
    cfg = pc.PulseCommConfig(n_chips=n, neurons_per_chip=N, n_inputs_per_chip=N,
                             event_capacity=E, bucket_capacity=8, ring_depth=16)
    spikes = jax.random.uniform(key, (n, N)) < 0.3
    ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, E)[0])(spikes)
    table = rt.random_table(key, N, n, max_delay=8)
    tables = jax.tree.map(lambda z: jnp.broadcast_to(z, (n,) + z.shape), table)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, N))(jnp.arange(n))
    ref_rings, _, ref_stats = pc.multi_chip_step(cfg, ebs, tables, rings)

    def shard_body(e, t, r):
        tr = tp.ShardMapTransport(axis="chip", n_chips=n)
        sq = lambda z: jax.tree.map(lambda a: a[0], z)
        ring, delivered, stats = pc.comm_step(cfg, tr, sq(e), sq(t), sq(r))
        ex = lambda z: jax.tree.map(lambda a: a[None], z)
        return ex(ring), ex(stats)

    f2 = shard_map(shard_body, mesh=mesh,
                   in_specs=(P("chip"), P("chip"), P("chip")),
                   out_specs=(P("chip"), P("chip")),
                   check_vma=False)
    got_rings, got_stats = f2(ebs, tables, rings)
    np.testing.assert_array_equal(np.asarray(got_rings.ring),
                                  np.asarray(ref_rings.ring))
    np.testing.assert_array_equal(np.asarray(got_stats.sent),
                                  np.asarray(ref_stats.sent))
    np.testing.assert_array_equal(np.asarray(got_stats.overflow),
                                  np.asarray(ref_stats.overflow))
    print("SHARD_MAP_TRANSPORT_OK")
""")


def test_shard_map_transport_matches_local():
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "SHARD_MAP_TRANSPORT_OK" in out.stdout, out.stderr[-3000:]


_HIERARCHICAL_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import transport as tp

    # 2 pods x 4 chips: the two-stage exchange must equal the flat one
    n = 8
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("pod", "chip"))
    x = jnp.arange(n * n * 3, dtype=jnp.int32).reshape(n, n, 3)
    want = tp.LocalTransport(n_chips=n).all_to_all(x)

    tr = tp.ShardMapTransport(axis=("pod", "chip"), n_chips=n)
    f = shard_map(lambda s: tr.all_to_all(s), mesh=mesh,
                  in_specs=P(("pod", "chip")), out_specs=P(("pod", "chip")))
    got = f(x.reshape(n * n, 3)).reshape(n, n, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("HIERARCHICAL_TRANSPORT_OK")
""")


def test_hierarchical_two_stage_exchange():
    """Multi-pod tier: pod-local stage then cross-pod stage == flat
    all_to_all (Extoll dimension-ordered routing analogue)."""
    out = subprocess.run(
        [sys.executable, "-c", _HIERARCHICAL_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "HIERARCHICAL_TRANSPORT_OK" in out.stdout, out.stderr[-3000:]


_THREE_AXIS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import transport as tp

    # 2 pods x 2 boards x 2 chips: the three-stage exchange must equal the
    # flat all_to_all (regression: the old implementation only ran the
    # FIRST inner axis, silently skipping the rest of the tuple).
    n = 8
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
                ("pod", "board", "chip"))
    x = jnp.arange(n * n * 3, dtype=jnp.int32).reshape(n, n, 3)
    want = tp.LocalTransport(n_chips=n).all_to_all(x)

    tr = tp.ShardMapTransport(axis=("pod", "board", "chip"), n_chips=n)
    axes = ("pod", "board", "chip")
    f = shard_map(lambda s: tr.all_to_all(s), mesh=mesh,
                  in_specs=P(axes), out_specs=P(axes))
    got = f(x.reshape(n * n, 3)).reshape(n, n, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # chip_index composes all three axes most-significant-first
    g = shard_map(lambda s: s + tr.chip_index(), mesh=mesh,
                  in_specs=P(axes), out_specs=P(axes))
    idx = g(jnp.zeros((n,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(idx), np.arange(n))
    print("THREE_AXIS_TRANSPORT_OK")
""")


def test_hierarchical_three_axis_exchange():
    """Satellite pin: a 3-axis mesh tuple (pod x board x chip) exchanges
    correctly — every axis gets its stage, innermost first."""
    out = subprocess.run(
        [sys.executable, "-c", _THREE_AXIS_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "THREE_AXIS_TRANSPORT_OK" in out.stdout, out.stderr[-3000:]
