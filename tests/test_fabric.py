"""PulseFabric engine: the single step body must reproduce BOTH legacy
paths bitwise (the explicit-transpose local path and the shard_map
collective path), define full-mode semantics once, and account for credit
flow control without losing events."""

import os
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import delays as dl
from repro.core import events as ev
from repro.core import fabric as fb
from repro.core import merge as mg
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.core import transport as tp


def _setup(n_chips, n_neurons, capacity, mode="simplified", bpc=1, key=0,
           rate=0.4, merge_rate=0, merge_depth=64):
    k = jax.random.PRNGKey(key)
    cfg = pc.PulseCommConfig(
        n_chips=n_chips, neurons_per_chip=n_neurons,
        n_inputs_per_chip=n_neurons, event_capacity=n_neurons,
        bucket_capacity=capacity, buckets_per_chip=bpc, ring_depth=16,
        mode=mode, merge_rate=merge_rate, merge_depth=merge_depth,
    )
    spikes = jax.random.uniform(k, (n_chips, n_neurons)) < rate
    ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, cfg.event_capacity)[0])(spikes)
    table = rt.random_table(k, n_neurons, n_chips, max_delay=8)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_chips,) + x.shape),
                          table)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n_neurons))(
        jnp.arange(n_chips))
    return cfg, ebs, tables, rings


class _SoADelivered(NamedTuple):
    """Pre-refactor delivered lanes: three separate arrays."""

    addr: jax.Array
    deadline: jax.Array
    valid: jax.Array


def _soa_pack(bucket_id, addr, deadline, valid, *, n_buckets, capacity):
    """Frozen pre-word-format bucket packing: three scatters, full-width
    deadlines (the seed's bk.pack).  benchmarks/aggregation.py carries the
    same frozen baseline for timing — keep the two in sync if the recorded
    pre-refactor semantics ever need correcting."""
    from repro.core import buckets as bk

    slot, counts = bk.compute_slots(bucket_id, valid, n_buckets)
    keep = valid & (slot < capacity)
    b = jnp.where(keep, bucket_id, n_buckets)
    s = jnp.where(keep, slot, capacity)
    out_addr = jnp.full((n_buckets, capacity), ev.ADDR_SENTINEL, jnp.int32)
    out_dead = jnp.zeros((n_buckets, capacity), jnp.int32)
    out_valid = jnp.zeros((n_buckets, capacity), bool)
    out_addr = out_addr.at[b, s].set(jnp.where(keep, addr, ev.ADDR_SENTINEL),
                                     mode="drop")
    out_dead = out_dead.at[b, s].set(jnp.where(keep, deadline, 0), mode="drop")
    out_valid = out_valid.at[b, s].set(keep, mode="drop")
    overflow = jnp.sum(valid & (slot >= capacity)).astype(jnp.int32)
    return out_addr, out_dead, out_valid, counts, overflow


def _legacy_local_oracle(cfg, events, table, rings):
    """The pre-refactor single-device path, frozen: SoA packing, THREE
    chip-axis transposes (one per lane array), full-width-deadline merge,
    SoA deposit.  Kept here as the event-semantics oracle the fabric's
    single-word path must match under the 8-bit wrap contract."""
    from repro.core import buckets as bk

    transport = tp.LocalTransport(n_chips=cfg.n_chips)
    routed = jax.vmap(rt.route)(events, table)

    def one_chip_pack(r):
        if cfg.mode == "simplified":
            bid = bk.static_bucket_ids(r.dest_chip, n_chips=cfg.n_chips,
                                       streams=cfg.buckets_per_chip)
        else:
            bid = bk.dynamic_bucket_ids(
                r.dest_chip, r.deadline, n_chips=cfg.n_chips,
                pool_per_chip=cfg.buckets_per_chip, window=cfg.time_window)
        slabs = _soa_pack(bid, r.dest_addr, r.deadline, r.valid,
                          n_buckets=cfg.n_buckets,
                          capacity=cfg.bucket_capacity)
        traffic = tp._exchange_matrix_onehot(r.dest_chip, r.valid,
                                             cfg.n_chips)
        return slabs, traffic

    (addr_s, dead_s, val_s, counts, overflow), traffic = jax.vmap(
        one_chip_pack)(routed)
    shape = (cfg.n_chips, cfg.n_chips, cfg.buckets_per_chip,
             cfg.bucket_capacity)
    addr = transport.all_to_all(addr_s.reshape(shape))
    dead = transport.all_to_all(dead_s.reshape(shape))
    val = transport.all_to_all(val_s.reshape(shape))
    lanes = cfg.lanes_in
    delivered = _SoADelivered(
        addr=addr.reshape(cfg.n_chips, lanes),
        deadline=dead.reshape(cfg.n_chips, lanes),
        valid=val.reshape(cfg.n_chips, lanes),
    )
    if cfg.mode == "full":
        a, d, v = jax.vmap(mg.merge_streams)(
            delivered.addr, delivered.deadline, delivered.valid)
        delivered = _SoADelivered(addr=a, deadline=d, valid=v)
    new_rings, expired = jax.vmap(
        lambda r, d: dl.deposit(r, d.addr, d.deadline, d.valid)
    )(rings, delivered)
    sent = jax.vmap(lambda r: jnp.sum(r.valid.astype(jnp.int32)))(routed)
    n_packets = jnp.sum((counts > 0).astype(jnp.int32), axis=-1)
    payload = jnp.sum(jnp.minimum(counts, cfg.bucket_capacity), axis=-1)
    wire = (n_packets * pc.HEADER_BYTES + payload * pc.EVENT_BYTES)
    return new_rings, delivered, {
        "sent": sent, "overflow": overflow, "expired": expired,
        "wire_bytes": wire.astype(jnp.int32), "traffic": traffic,
    }


@pytest.mark.parametrize("mode,bpc", [("simplified", 1), ("simplified", 2),
                                      ("full", 1), ("full", 2)])
def test_local_fabric_matches_legacy_path_bitwise(mode, bpc):
    cfg, ebs, tables, rings = _setup(4, 32, 8, mode=mode, bpc=bpc)
    res = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    oring, odel, ostats = _legacy_local_oracle(cfg, ebs, tables, rings)
    np.testing.assert_array_equal(np.asarray(res.ring.ring),
                                  np.asarray(oring.ring))
    np.testing.assert_array_equal(np.asarray(res.delivered.addr),
                                  np.asarray(odel.addr), err_msg="addr")
    np.testing.assert_array_equal(np.asarray(res.delivered.valid),
                                  np.asarray(odel.valid), err_msg="valid")
    # the word carries the 8-bit on-wire timestamp: equal modulo wrap8
    np.testing.assert_array_equal(np.asarray(res.delivered.deadline),
                                  np.asarray(ev.wrap8(odel.deadline)),
                                  err_msg="deadline")
    for name, want in ostats.items():
        np.testing.assert_array_equal(
            np.asarray(getattr(res.stats, name)), np.asarray(want),
            err_msg=name)
    assert int(res.stats.stalled.sum()) == 0  # no flow control configured


def test_comm_step_vs_local_full_mode_parity():
    """Satellite pin: per-chip comm_step (the shard-side body, run here
    under a vmapped axis) and the local fabric must agree in mode="full"
    WITH merge rate-limiting — previously the local path hard-zeroed
    merge_dropped and skipped the rate limit entirely."""
    cfg, ebs, tables, rings = _setup(4, 32, 8, mode="full", bpc=2,
                                     rate=0.9, merge_rate=4, merge_depth=2)
    res = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)

    per_chip = tp.ShardMapTransport(axis="c", n_chips=cfg.n_chips)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        got_rings, got_del, got_stats = jax.vmap(
            lambda e, t, r: pc.comm_step(cfg, per_chip, e, t, r),
            axis_name="c",
        )(ebs, tables, rings)

    np.testing.assert_array_equal(np.asarray(got_rings.ring),
                                  np.asarray(res.ring.ring))
    np.testing.assert_array_equal(np.asarray(got_del.valid),
                                  np.asarray(res.delivered.valid))
    np.testing.assert_array_equal(np.asarray(got_stats.merge_dropped),
                                  np.asarray(res.stats.merge_dropped))
    # the rate limit actually bit: real drops, and <= merge_rate delivered
    assert int(res.stats.merge_dropped.sum()) > 0
    assert (np.asarray(res.delivered.valid).sum(axis=1)
            <= cfg.merge_rate).all()


def test_deprecated_shims_return_identical_results():
    cfg, ebs, tables, rings = _setup(3, 16, 8)
    res = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    with pytest.warns(DeprecationWarning):
        rings2, delivered2, stats2 = pc.multi_chip_step(cfg, ebs, tables,
                                                        rings)
    np.testing.assert_array_equal(np.asarray(rings2.ring),
                                  np.asarray(res.ring.ring))
    np.testing.assert_array_equal(np.asarray(delivered2.valid),
                                  np.asarray(res.delivered.valid))
    np.testing.assert_array_equal(np.asarray(stats2.sent),
                                  np.asarray(res.stats.sent))
    np.testing.assert_array_equal(np.asarray(stats2.stalled),
                                  np.asarray(res.stats.stalled))


# ---------------------------------------------------------------------------
# Flow control
# ---------------------------------------------------------------------------

def test_flow_control_conserves_events():
    """sent == overflow + stalled + expired + delivered-to-rings: the credit
    gate holds events back, it never loses them."""
    cfg, ebs, tables, rings = _setup(4, 64, 4, rate=0.9, bpc=2)
    fab = fb.PulseFabric(cfg, transport="local",
                         flow=fb.FlowControlConfig(capacity=2, drain_rate=1))
    res = fab.step(ebs, tables, rings)
    sent = int(res.stats.sent.sum())
    accounted = (int(res.stats.overflow.sum()) + int(res.stats.stalled.sum())
                 + int(res.stats.expired.sum()) + int(res.ring.ring.sum()))
    assert int(res.stats.stalled.sum()) > 0, "tight credits must stall"
    assert sent == accounted


def test_flow_control_credits_thread_across_steps():
    """Credits drain and return: with capacity C and drain_rate R, at most C
    packets are ever in flight and R credits come back per step."""
    cfg, ebs, tables, rings = _setup(2, 32, 4, rate=0.9, bpc=4)
    fcfg = fb.FlowControlConfig(capacity=3, drain_rate=1)
    fab = fb.PulseFabric(cfg, transport="local", flow=fcfg)
    flow = fab.init_flow()
    for _ in range(4):
        res = fab.step(ebs, tables, rings, flow)
        rings, flow = res.ring, res.flow
        in_flight = np.asarray(flow.head - flow.tail)
        assert (in_flight <= fcfg.capacity).all()
        assert (in_flight >= 0).all()
    # the consumer returned credits via notifications
    assert (np.asarray(flow.notifications) > 0).all()


def test_ample_credits_match_no_flow_bitwise():
    """A credit budget that never runs out must be a bitwise no-op."""
    cfg, ebs, tables, rings = _setup(4, 32, 8, mode="full", bpc=2)
    base = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    ample = fb.PulseFabric(
        cfg, transport="local",
        flow=fb.FlowControlConfig(capacity=cfg.n_buckets + 1,
                                  drain_rate=cfg.n_buckets + 1),
    ).step(ebs, tables, rings)
    np.testing.assert_array_equal(np.asarray(ample.ring.ring),
                                  np.asarray(base.ring.ring))
    np.testing.assert_array_equal(np.asarray(ample.stats.wire_bytes),
                                  np.asarray(base.stats.wire_bytes))
    assert int(ample.stats.stalled.sum()) == 0


def test_network_threads_credit_state_across_steps():
    """Regression: the credit state rides in NetworkState.flow, so both
    run() and repeated step() calls accumulate back-pressure instead of
    resetting credits every step."""
    from repro.snn import network as net

    comm = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=16, n_inputs_per_chip=16,
        event_capacity=16, bucket_capacity=4, buckets_per_chip=4,
        ring_depth=8)
    cfg = net.NetworkConfig(
        comm=comm, flow=fb.FlowControlConfig(capacity=2, drain_rate=1))
    params = net.init_params(jax.random.PRNGKey(0), cfg)
    state = net.init_state(cfg, params)
    assert state.flow is not None

    ext = jnp.ones((6, 2, 16), jnp.float32)
    final, rec = net.run(cfg, params, state, ext)
    in_flight = np.asarray(final.flow.head - final.flow.tail)
    assert (in_flight >= 0).all() and (in_flight <= 2).all()
    # drain_rate < injected packets -> credits must have been exhausted at
    # least once over the run (stall observed), proving state threaded
    assert int(np.asarray(rec.stats.stalled).sum()) > 0

    s1, _ = net.step(cfg, params, state, ext[0])
    s2, _ = net.step(cfg, params, s1, ext[1])
    assert int(np.asarray(s2.flow.tail).sum()) >= \
        int(np.asarray(s1.flow.tail).sum())


# ---------------------------------------------------------------------------
# Retransmit send queue (flow control with retransmit_depth > 0)
# ---------------------------------------------------------------------------

def _tick(ring):
    return dl.DelayRing(ring=ring.ring, now=ring.now + 1)


def _run_flow(flowcfg, steps=12, key=1):
    """Drive one burst through tight credits, then drain; returns the
    cumulative accounting dict."""
    cfg, ebs, tables, rings = _setup(4, 64, 4, rate=0.9, bpc=2, key=key)
    zeros = jax.tree.map(jnp.zeros_like, ebs)
    fab = fb.PulseFabric(cfg, transport="local", flow=flowcfg)
    ring, flow, merge, sendq = rings, None, None, None
    tot = dict(sent=0, overflow=0, expired=0, stalled=0)
    for t in range(steps):
        res = fab.step(ebs if t == 0 else zeros, tables, ring, flow, merge,
                       sendq)
        ring, flow, merge, sendq = res.ring, res.flow, res.merge, res.sendq
        for f in tot:
            tot[f] += int(np.asarray(getattr(res.stats, f)).sum())
        ring = _tick(ring)   # advance the clock so queued deadlines age
    tot["deposited"] = int(np.asarray(ring.ring).sum())
    tot["queued"] = (0 if sendq is None
                     else int(np.asarray(sendq.occupancy()).sum()))
    return tot


def test_retransmit_requeues_instead_of_dropping():
    """Satellite pin: with a roomy send queue, credit-stalled events are
    re-offered on later steps — zero stalled drops, and conservation
    injected == delivered + expired + overflow + queued + stalled holds
    over the whole run."""
    tot = _run_flow(fb.FlowControlConfig(capacity=2, drain_rate=1,
                                         retransmit_depth=128))
    assert tot["stalled"] == 0
    assert tot["queued"] == 0   # drained once credits returned
    obs.check_conservation(tot, delivered=tot["deposited"],
                           queued=tot["queued"])
    # and it delivers strictly more than the historical drop-and-account
    dropped = _run_flow(fb.FlowControlConfig(capacity=2, drain_rate=1))
    assert dropped["stalled"] > 0
    assert tot["deposited"] + tot["expired"] > dropped["deposited"] + \
        dropped["expired"]


def test_retransmit_bounded_queue_overflow_is_accounted():
    """A too-small send queue drops the surplus into ``stalled`` — never
    silently — and conservation still holds."""
    tot = _run_flow(fb.FlowControlConfig(capacity=1, drain_rate=1,
                                         retransmit_depth=4))
    assert tot["stalled"] > 0
    obs.check_conservation(tot, delivered=tot["deposited"],
                           queued=tot["queued"])


def test_retransmit_queued_events_expire_when_stalled_too_long():
    """A queued event is re-judged against the injection window every step:
    starved of credits long enough it lands in ``expired``, not on the
    wire (and never aliases across the 8-bit wrap)."""
    tot = _run_flow(fb.FlowControlConfig(capacity=0, drain_rate=0,
                                         retransmit_depth=512), steps=24)
    assert tot["queued"] == 0 and tot["deposited"] == 0
    assert tot["expired"] > 0
    obs.check_conservation(tot, delivered=tot["deposited"],
                           queued=tot["queued"])


def test_ample_credits_with_retransmit_match_no_flow_bitwise():
    cfg, ebs, tables, rings = _setup(4, 32, 8, bpc=2)
    base = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    q = fb.PulseFabric(
        cfg, transport="local",
        flow=fb.FlowControlConfig(capacity=cfg.n_buckets + 1,
                                  drain_rate=cfg.n_buckets + 1,
                                  retransmit_depth=32),
    ).step(ebs, tables, rings)
    np.testing.assert_array_equal(np.asarray(q.ring.ring),
                                  np.asarray(base.ring.ring))
    assert int(np.asarray(q.sendq.occupancy()).sum()) == 0
    assert int(np.asarray(q.stats.stalled).sum()) == 0


# ---------------------------------------------------------------------------
# Transport registry
# ---------------------------------------------------------------------------

def test_unknown_transport_raises():
    cfg, *_ = _setup(2, 8, 4)
    with pytest.raises(ValueError, match="unknown transport"):
        fb.PulseFabric(cfg, transport="carrier-pigeon")
    with pytest.raises(TypeError):
        fb.PulseFabric(cfg, transport=42)


def test_register_custom_transport():
    cfg, ebs, tables, rings = _setup(2, 8, 4)
    name = "local-alias-for-test"
    fb.register_transport(
        name,
        lambda c: fb.TransportBinding(
            tp.ShardMapTransport(axis=fb.LOCAL_AXIS, n_chips=c.n_chips),
            batched=True,
        ),
    )
    try:
        assert name in fb.available_transports()
        got = fb.PulseFabric(cfg, transport=name).step(ebs, tables, rings)
        want = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
        np.testing.assert_array_equal(np.asarray(got.ring.ring),
                                      np.asarray(want.ring.ring))
    finally:
        fb._REGISTRY.pop(name, None)


def test_transport_instance_binding_is_unbatched():
    cfg, *_ = _setup(2, 8, 4)
    inst = tp.ShardMapTransport(axis="chip", n_chips=2)
    fab = fb.PulseFabric(cfg, transport=inst)
    assert fab.transport is inst and not fab.batched
    assert fb.PulseFabric(cfg, transport=("pod", "chip")).transport.axis == \
        ("pod", "chip")


# ---------------------------------------------------------------------------
# Local vs shard_map: bitwise equivalence of the two fabric bindings
# (the acceptance criterion), including with flow control enabled.
# ---------------------------------------------------------------------------

_EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import delays as dl, events as ev, fabric as fb
    from repro.core import pulse_comm as pc, routing as rt, transport as tp

    import sys

    n, N = 4, 16
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("chip",))
    key = jax.random.PRNGKey(0)

    CASES = [
        ("simplified", 1, None, 0), ("full", 2, None, 0),
        ("simplified", 2, fb.FlowControlConfig(capacity=2, drain_rate=1), 0),
        ("simplified", 2,
         fb.FlowControlConfig(capacity=2, drain_rate=1, retransmit_depth=16),
         0),
        ("full", 2, None, 3)]
    for mode, bpc, flow, merge_rate in [CASES[int(sys.argv[1])]]:
        cfg = pc.PulseCommConfig(
            n_chips=n, neurons_per_chip=N, n_inputs_per_chip=N,
            event_capacity=N, bucket_capacity=4, buckets_per_chip=bpc,
            ring_depth=16, mode=mode, merge_rate=merge_rate, merge_depth=8)
        spikes = jax.random.uniform(key, (n, N)) < 0.6
        ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, N)[0])(spikes)
        table = rt.random_table(key, N, n, max_delay=8)
        tables = jax.tree.map(lambda z: jnp.broadcast_to(z, (n,) + z.shape),
                              table)
        rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, N))(jnp.arange(n))

        local = fb.PulseFabric(cfg, transport="local", flow=flow)
        # two steps so the stateful merge/send queues actually carry over
        ref1 = local.step(ebs, tables, rings, local.init_flow(),
                          local.init_merge(), local.init_sendq())
        ref = local.step(ebs, tables, ref1.ring, ref1.flow, ref1.merge,
                         ref1.sendq)

        shard = fb.PulseFabric(cfg, transport="shard_map", flow=flow)
        flow_b = local.init_flow()  # batched [n] state, split per shard
        merge_b = local.init_merge()
        sendq_b = local.init_sendq()

        def body(e, t, r, f, m, q):
            sq = lambda z: jax.tree.map(lambda a: a[0], z)
            opt = lambda z: None if z is None else sq(z)
            out1 = shard.step(sq(e), sq(t), sq(r), opt(f), opt(m), opt(q))
            out = shard.step(sq(e), sq(t), out1.ring, out1.flow, out1.merge,
                             out1.sendq)
            return jax.tree.map(lambda a: a[None] if hasattr(a, "ndim")
                                else a, out)

        specs = (P("chip"),) * 6
        got = shard_map(body, mesh=mesh, in_specs=specs,
                        out_specs=P("chip"), check_vma=False)(
            ebs, tables, rings, flow_b, merge_b, sendq_b)

        np.testing.assert_array_equal(np.asarray(got.ring.ring),
                                      np.asarray(ref.ring.ring))
        for lane in ("addr", "deadline", "valid"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got.delivered, lane)),
                np.asarray(getattr(ref.delivered, lane)))
        for f in pc.CommStats._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got.stats, f)),
                np.asarray(getattr(ref.stats, f)), err_msg=f)
        if flow is not None:
            np.testing.assert_array_equal(np.asarray(got.flow.head),
                                          np.asarray(ref.flow.head))
            np.testing.assert_array_equal(np.asarray(got.flow.tail),
                                          np.asarray(ref.flow.tail))
        if merge_rate > 0:
            for f in ("addr", "deadline", "valid"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(got.merge, f)),
                    np.asarray(getattr(ref.merge, f)), err_msg="merge." + f)
            assert int(np.asarray(ref.merge.valid).sum()) > 0, \
                "merge case must actually queue events"
        if flow is not None and flow.retransmit_depth > 0:
            for f in ("words", "dest"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(got.sendq, f)),
                    np.asarray(getattr(ref.sendq, f)), err_msg="sendq." + f)
            assert int(np.asarray(ref.sendq.occupancy()).sum()) > 0, \
                "retransmit case must actually queue events"
        print(f"EQUIV_OK mode={mode} bpc={bpc} flow={flow is not None} "
              f"merge={merge_rate}")
    print("FABRIC_EQUIVALENCE_OK")
""")


@pytest.mark.parametrize("case", range(5), ids=[
    "simplified", "full", "credit", "credit-retransmit", "full-merge"])
def test_local_and_shard_map_fabrics_bitwise_equal(case):
    """One configuration per subprocess (4 host devices each), so every
    case has the whole timeout to itself."""
    root = Path(__file__).resolve().parents[1]
    env = {"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    if "HOME" in os.environ:
        env["HOME"] = os.environ["HOME"]
    out = subprocess.run(
        [sys.executable, "-c", _EQUIV_SCRIPT, str(case)],
        capture_output=True, text=True, timeout=300, env=env, cwd=root,
    )
    assert "FABRIC_EQUIVALENCE_OK" in out.stdout, out.stderr[-3000:]
