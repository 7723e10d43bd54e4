import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.bss2 import CONFIG as BSS2
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.snn import network as net


def _ff_network(n=32, delay=2, w_target=0.6, drive_period=4, T=40,
                comm_mode="event", capacity=None):
    comm = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=capacity or n, ring_depth=8,
    )
    cfg = net.NetworkConfig(comm=comm, neuron_model="lif",
                            comm_mode=comm_mode)
    table = rt.feedforward_table(n, src_chip=0, dst_chip=1, delay=delay)
    params = net.init_params(jax.random.PRNGKey(0), cfg, table=table)
    w = np.zeros((2, n, n), np.float32)
    w[0] = 1.5 * np.eye(n)           # chip0: one input spike -> fire
    w[1] = w_target * np.eye(n)      # chip1: needs 2 spikes to fire
    params = params._replace(
        crossbar=params.crossbar._replace(w=jnp.asarray(w)))
    state = net.init_state(cfg, params)
    ext = np.zeros((T, 2, n), np.float32)
    ext[::drive_period, 0, :] = 1.0
    return cfg, params, state, jnp.asarray(ext)


def test_feedforward_isi_doubling():
    """The paper's NICE demo (§4, Fig. 2): target neurons need two input
    spikes per output spike, so the inter-spike interval doubles from the
    source to the destination chip."""
    cfg, params, state, ext = _ff_network()
    _, rec = jax.jit(lambda p, s, e: net.run(cfg, p, s, e))(params, state, ext)
    src = np.nonzero(np.asarray(rec.spikes[:, 0, 0]))[0]
    dst = np.nonzero(np.asarray(rec.spikes[:, 1, 0]))[0]
    isi_src = np.diff(src)
    isi_dst = np.diff(dst)
    assert np.all(isi_src == 4)
    assert np.all(isi_dst == 8), f"ISI must double, got {isi_dst}"
    assert int(rec.stats.expired.sum()) == 0


def test_feedforward_latency_matches_axonal_delay():
    for delay in (1, 2, 4):
        cfg, params, state, ext = _ff_network(delay=delay, w_target=1.5,
                                              drive_period=16, T=20)
        _, rec = net.run(cfg, params, state, ext)
        src = np.nonzero(np.asarray(rec.spikes[:, 0, 0]))[0]
        dst = np.nonzero(np.asarray(rec.spikes[:, 1, 0]))[0]
        assert dst[0] - src[0] == delay


def test_event_path_matches_dense_path():
    """With no drops, the discrete event pipeline and the differentiable
    dense bypass deliver identical spike trains."""
    outs = {}
    for mode in ("event", "dense"):
        cfg, params, state, ext = _ff_network(comm_mode=mode, T=24)
        _, rec = net.run(cfg, params, state, ext)
        outs[mode] = np.asarray(rec.spikes)
    np.testing.assert_array_equal(outs["event"], outs["dense"])


def _delivered(final, rec):
    """Events that reached a ring: popped during the run + still queued."""
    return int(np.asarray(rec.delivered).sum()) + int(
        np.asarray(final.ring.ring).sum())


def test_overflow_loses_spikes_but_accounts_them():
    cfg, params, state, ext = _ff_network(capacity=8)  # 32 spikes/step, cap 8
    final, rec = net.run(cfg, params, state, ext)
    assert int(rec.stats.overflow.sum()) > 0
    obs.check_conservation(rec.stats, delivered=_delivered(final, rec))
    # the target chip fires strictly less than in the ample case
    cfg2, p2, s2, e2 = _ff_network()
    _, rec2 = net.run(cfg2, p2, s2, e2)
    assert rec.spikes[:, 1].sum() < rec2.spikes[:, 1].sum()


def test_adex_network_runs():
    comm = pc.PulseCommConfig(n_chips=2, neurons_per_chip=16,
                              n_inputs_per_chip=16, event_capacity=16,
                              bucket_capacity=16, ring_depth=8)
    cfg = net.NetworkConfig(comm=comm, neuron_model="adex")
    params = net.init_params(jax.random.PRNGKey(1), cfg)
    state = net.init_state(cfg, params)
    ext = 0.5 * jnp.ones((10, 2, 16), jnp.float32)
    final, rec = net.run(cfg, params, state, ext)
    assert np.isfinite(np.asarray(rec.voltage)).all()


def test_surrogate_training_reduces_loss():
    """BPTT through the dense path: teach chip-1 rate to match a target."""
    comm = pc.PulseCommConfig(n_chips=2, neurons_per_chip=8,
                              n_inputs_per_chip=8, event_capacity=8,
                              bucket_capacity=8, ring_depth=4)
    cfg = net.NetworkConfig(comm=comm, comm_mode="dense")
    table = rt.feedforward_table(8, src_chip=0, dst_chip=1, delay=1)
    params = net.init_params(jax.random.PRNGKey(2), cfg, table=table)
    ext = jnp.tile(jnp.asarray([1.0, 0.0])[None, :, None], (12, 1, 8))

    target_rate = 0.5

    def loss_fn(w):
        p = params._replace(crossbar=params.crossbar._replace(w=w))
        state = net.init_state(cfg, p)
        _, rec = net.run(cfg, p, state, ext)
        rate = jnp.mean(rec.spikes[:, 1])
        return (rate - target_rate) ** 2

    w = params.crossbar.w
    l0 = float(loss_fn(w))
    g = jax.grad(loss_fn)(w)
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).sum()) > 0
    for _ in range(20):
        w = w - 5.0 * jax.grad(loss_fn)(w)
    l1 = float(loss_fn(w))
    assert l1 < l0


@pytest.mark.parametrize("B,mode,merge_rate", [(1, "simplified", 0),
                                               (4, "simplified", 0),
                                               (4, "full", 8)])
def test_run_records_close_event_conservation(B, mode, merge_rate):
    """Σ sent == popped + left in the rings + queued + every drop leg, over
    a whole run of the reduced wafer module."""
    comm = dataclasses.replace(BSS2.reduced().comm, superstep=B, mode=mode,
                               merge_rate=merge_rate)
    cfg = net.NetworkConfig(comm=comm, neuron_model="adex")
    params = net.init_params(jax.random.PRNGKey(0), cfg)
    ext = (jax.random.uniform(jax.random.PRNGKey(1),
                              (16, comm.n_chips, comm.n_inputs_per_chip))
           < 0.1).astype(jnp.float32)
    final, rec = net.run(cfg, params, net.init_state(cfg, params), ext)
    queued = 0 if final.merge is None else final.merge.occupancy()
    rep = obs.check_conservation(rec.stats, delivered=_delivered(final, rec),
                                 queued=queued)
    assert rep.delivered > 0 and rep.injected > rep.delivered


_SHARD_RUN_SCRIPT = textwrap.dedent("""
    import dataclasses
    import sys
    import chip_smoke
    from repro.configs.bss2 import CONFIG
    comm = dataclasses.replace(CONFIG.reduced().comm, superstep=4)
    failures = chip_smoke.four_chips(comm, seed=0, steps=16,
                                     float_tol=chip_smoke.CPU_FLOAT_TOL)
    assert not failures, failures
    print("SHARD_RUN_OK")
""")


def test_shard_run_matches_run_on_four_devices():
    """chip_smoke.py --chips 4 at reduced widths on four virtual CPU
    devices: shard_run on the dense and torus2d(2, 2) transports equals
    run on one device (float leaves within the CPU bound), with outputs
    spread over the mesh."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    out = subprocess.run([sys.executable, "-c", _SHARD_RUN_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=root)
    assert "SHARD_RUN_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]
