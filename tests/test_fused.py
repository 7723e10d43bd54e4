"""Fused substep megakernels: VMEM-resident inject and drain paths.

Pins the tentpole contracts:
  * ``use_pallas=True`` superstep delivery (spike words, ring contents,
    every CommStats field) is **bitwise-equal** to the unfused op chain
    for B ∈ {1, 2, 4, 8} on the dense local transport and for the routed
    torus2d / switch_tree topologies — including hostile regimes (low
    slack → wrap expiries, tiny buckets → overflow, rate-limited merge →
    congestion drops) where every counter is non-trivially exercised;
  * the pipelined schedule (streaming ``pipeline_block`` +
    ``flush_pending``) stays bitwise under the fused drain's in-kernel
    gate handling (no host-side queue revert);
  * a credit-gated fabric falls back to the unfused inject loop (the
    gate's feedback is sequential) and stays bitwise — the fused drain
    still runs;
  * the conservation identity Σ sent == deposited + accounted + queued
    closes under ``use_pallas=True`` merge congestion;
  * launch-count pin: one superstep block traces exactly TWO pallas_call
    equations — one fused inject, one fused drain — regardless of B and
    of the fan-out (counted in the jaxpr, nested scopes included);
  * fan-out 4 (the wafer module's): the fused inject ranks the K routed
    lanes of an event in the e-major order of ``routing.route``, so when
    a bucket overflows inside one event's entries the same entries drop.

Everything runs in Pallas interpret mode on CPU (repro.kernels.common
resolves the backend; REPRO_FORCE_INTERPRET=1 pins it in CI).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro import obs
from repro.core import delays as dl
from repro.core import events as ev
from repro.core import fabric as fb
from repro.core import pulse_comm as pc
from repro.core import routing as rt
from repro.core import topology as tpo

_TOPOS = [
    ("torus2d", tpo.torus2d(2, 2, link_latency=1)),
    ("switch_tree", tpo.switch_tree(2, 2, link_latency=1,
                                    trunk_latency=1)),
]


def _setup(B, *, n_chips=4, n=16, cap=4, bpc=2, mode="simplified",
           merge_rate=0, merge_depth=16, T=None, key=0, rate=0.6,
           min_delay=2, max_delay=12, ring_depth=16, fanout=1, table=None):
    """T per-step event buffers plus fused/unfused config twins.

    Unlike the superstep-vs-B=1 suites this one compares the SAME blocked
    schedule with and without the megakernels, so no slack constraint
    applies — the default delay range deliberately straddles the wrap
    window (min_delay < B for the larger B) to drive wrap_expired, and
    the tiny buckets drive overflow.  ``table`` replaces the random
    routing table (shared by every chip).
    """
    T = 2 * B if T is None else T
    k = jax.random.PRNGKey(key)
    cfg = pc.PulseCommConfig(
        n_chips=n_chips, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=cap, buckets_per_chip=bpc,
        ring_depth=ring_depth, mode=mode, merge_rate=merge_rate,
        merge_depth=merge_depth, superstep=B)
    cfgp = dataclasses.replace(cfg, use_pallas=True)
    if table is None:
        table = rt.random_table(k, n, n_chips, fanout=fanout,
                                max_delay=max_delay, min_delay=min_delay)
    tables = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_chips,) + x.shape), table)
    ks = jax.random.split(k, T)
    ebs = [jax.vmap(lambda s: ev.from_spikes(s, t, n)[0])(
        jax.random.uniform(ks[t], (n_chips, n)) < rate) for t in range(T)]
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n))(
        jnp.arange(n_chips))
    return cfg, cfgp, ebs, tables, rings


def _run_blocks(fab, ebs, tables, rings, flow_cfg=None):
    B = fab.cfg.superstep
    ring, merge = rings, fab.init_merge()
    flow, sendq = fab.init_flow(), fab.init_sendq()
    delivered, stats = [], []
    for blk in range(len(ebs) // B):
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *ebs[blk * B:(blk + 1) * B])
        res = fab.superstep(block, tables, ring, flow, merge, sendq)
        ring, merge = res.ring, res.merge
        flow, sendq = res.flow, res.sendq
        delivered.append(np.asarray(res.delivered.words))
        stats.append(res.stats)
        ring = dl.DelayRing(ring=ring.ring, now=ring.now + B)
    return ring, delivered, stats


def _assert_run_equal(r0, r1, msg=""):
    ring0, del0, st0 = r0
    ring1, del1, st1 = r1
    np.testing.assert_array_equal(np.asarray(ring0.ring),
                                  np.asarray(ring1.ring),
                                  err_msg=f"{msg}ring")
    for t, (a, b) in enumerate(zip(del0, del1)):
        np.testing.assert_array_equal(a, b, err_msg=f"{msg}delivered {t}")
    for blk, (a, b) in enumerate(zip(st0, st1)):
        for fld in pc.CommStats._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, fld)), np.asarray(getattr(b, fld)),
                err_msg=f"{msg}stats[{blk}].{fld}")


# ---------------------------------------------------------------------------
# Bitwise equality: fused vs unfused on the same blocked schedule
# ---------------------------------------------------------------------------

def _fanout_cases(cases):
    """``(B, fanout)`` parameters; fan-out 1 keeps the bare ``B`` id."""
    return pytest.mark.parametrize(
        "B,fanout", cases,
        ids=[f"{b}" if k == 1 else f"{b}-K{k}" for b, k in cases])


@pytest.mark.parametrize("mode,merge_rate", [("simplified", 0),
                                             ("full", 0), ("full", 3)])
@_fanout_cases([(1, 1), (2, 1), (4, 1), (8, 1), (1, 4), (4, 4)])
def test_fused_superstep_matches_unfused_bitwise(mode, merge_rate, B,
                                                 fanout):
    cfg, cfgp, ebs, tables, rings = _setup(B, mode=mode,
                                           merge_rate=merge_rate,
                                           fanout=fanout)
    r0 = _run_blocks(fb.PulseFabric(cfg, transport="local"),
                     ebs, tables, rings)
    r1 = _run_blocks(fb.PulseFabric(cfgp, transport="local"),
                     ebs, tables, rings)
    _assert_run_equal(r0, r1, msg=f"{mode}/r{merge_rate}/B{B}/K{fanout} ")
    if merge_rate:
        # the hostile load must actually exercise the congestion path
        assert sum(int(np.asarray(s.merge_dropped).sum())
                   for s in r0[2]) > 0
    if B >= 4:
        assert sum(int(np.asarray(s.expired).sum()) for s in r0[2]) > 0


@pytest.mark.parametrize("topo_name,topo", _TOPOS,
                         ids=[t[0] for t in _TOPOS])
@pytest.mark.parametrize("B", [2, 8])
def test_fused_superstep_matches_on_routed_topologies(topo_name, topo, B):
    cfg, cfgp, ebs, tables, rings = _setup(B, min_delay=6)
    r0 = _run_blocks(fb.PulseFabric(cfg, transport=topo),
                     ebs, tables, rings)
    r1 = _run_blocks(fb.PulseFabric(cfgp, transport=topo),
                     ebs, tables, rings)
    _assert_run_equal(r0, r1, msg=f"{topo_name}/B{B} ")


# ---------------------------------------------------------------------------
# Pipelined schedule: the in-kernel gate replaces the queue revert
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "mode,merge_rate,fanout",
    [("simplified", 0, 1), ("full", 3, 1), ("simplified", 0, 4),
     ("full", 3, 4)],
    ids=["simplified-0", "full-3", "simplified-0-K4", "full-3-K4"])
def test_fused_pipeline_matches_unfused(mode, merge_rate, fanout):
    B, F = 4, 3
    cfg, cfgp, ebs, tables, rings = _setup(
        B, T=B * F, mode=mode, merge_rate=merge_rate, min_delay=10,
        max_delay=12, ring_depth=20, fanout=fanout)
    blocks = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[jax.tree.map(lambda *ys: jnp.stack(ys),
                       *ebs[f * B:(f + 1) * B]) for f in range(F)])

    def run(c):
        fab = fb.PulseFabric(c, transport="local")
        ring, merge, pending = rings, fab.init_merge(), fab.init_pending()
        delivered, stats = [], []
        for f in range(F):
            blk = jax.tree.map(lambda a: a[f], blocks)
            res = fab.pipeline_block(blk, tables, ring, None, merge, None,
                                     pending)
            merge, pending = res.merge, res.pending
            ring = dl.DelayRing(ring=res.ring.ring, now=res.ring.now + B)
            delivered.append(np.asarray(res.delivered.words))
            stats.append(res.stats)
        fres = fab.flush_pending(ring, pending, None, merge)
        delivered.append(np.asarray(fres.delivered.words))
        stats.append(fres.stats)
        return fres.ring, delivered, stats

    _assert_run_equal(run(cfg), run(cfgp),
                      msg=f"pipeline/{mode}/r{merge_rate}/K{fanout} ")


# ---------------------------------------------------------------------------
# Credit gate: sequential feedback → fused inject falls back, stays bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fanout", [1, 4])
def test_fused_credit_gate_falls_back_and_matches(fanout):
    cfg, cfgp, ebs, tables, rings = _setup(2, rate=0.9, fanout=fanout)
    flow = fb.FlowControlConfig(capacity=2, drain_rate=1)
    r0 = _run_blocks(fb.PulseFabric(cfg, transport="local", flow=flow),
                     ebs, tables, rings)
    r1 = _run_blocks(fb.PulseFabric(cfgp, transport="local", flow=flow),
                     ebs, tables, rings)
    _assert_run_equal(r0, r1, msg="flow ")
    assert sum(int(np.asarray(s.stalled).sum()) for s in r0[2]) > 0, \
        "tight credits must stall"


# ---------------------------------------------------------------------------
# Fan-out order: an overflow that splits the K entries of one event
# ---------------------------------------------------------------------------

def _alternating_table(n, fanout, *, n_chips, delay):
    """Entry j of every neuron goes to chip ``j % 2`` at a distinct
    address: each event puts K/2 entries into each of two buckets, and
    e-major and entry-major orders put different words in the slab."""
    j = np.arange(fanout)[None, :]
    i = np.arange(n)[:, None]
    full = lambda x: jnp.asarray(np.broadcast_to(x, (n, fanout)),
                                 jnp.int32)
    return rt.RoutingTable(
        dest_chip=full((j % 2) % n_chips),
        dest_addr=full((i * fanout + j) % n),
        delay=full(delay), valid=jnp.ones((n, fanout), bool))


@pytest.mark.parametrize("B", [1, 4])
def test_fused_overflow_splits_an_events_fanout_entries(B):
    """Capacity 3 with two entries per event and bucket: the second
    event's entries straddle the capacity, so the first one is packed and
    the second one overflows — as in the unfused chain, bit for bit."""
    n, fanout = 16, 4
    table = _alternating_table(n, fanout, n_chips=4, delay=B + 2)
    cfg, cfgp, ebs, tables, rings = _setup(
        B, cap=3, bpc=1, table=table, min_delay=B + 2)
    r0 = _run_blocks(fb.PulseFabric(cfg, transport="local"),
                     ebs, tables, rings)
    r1 = _run_blocks(fb.PulseFabric(cfgp, transport="local"),
                     ebs, tables, rings)
    _assert_run_equal(r0, r1, msg=f"split/B{B} ")
    # Two entries per event and bucket against an odd capacity: every
    # overflowing bucket cuts through one event's entries.
    assert sum(int(np.asarray(s.overflow).sum()) for s in r0[2]) > 0, \
        "some bucket must overflow inside one event's entries"


# ---------------------------------------------------------------------------
# Conservation under use_pallas=True merge congestion
# ---------------------------------------------------------------------------

def test_fused_conservation_under_merge_congestion():
    B = 4
    _, cfgp, ebs, tables, rings = _setup(
        B, mode="full", merge_rate=2, merge_depth=8, rate=0.9)
    fab = fb.PulseFabric(cfgp, transport="local")
    ring, merge = rings, fab.init_merge()
    before = int(np.asarray(ring.ring).sum())
    tot = {f: 0 for f in ("sent", "overflow", "expired", "stalled",
                          "merge_dropped", "lost_to_failure")}
    for blk in range(len(ebs) // B):
        block = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *ebs[blk * B:(blk + 1) * B])
        res = fab.superstep(block, tables, ring, None, merge)
        ring, merge = res.ring, res.merge
        for f in tot:
            tot[f] += int(np.asarray(getattr(res.stats, f)).sum())
        ring = dl.DelayRing(ring=ring.ring, now=ring.now + B)
    deposited = int(np.asarray(ring.ring).sum()) - before
    queued = int(np.asarray(merge.occupancy()).sum())
    report = obs.check_conservation(tot, delivered=deposited, queued=queued)
    assert sum(report.legs.values()) > 0, \
        "hostile load must drop/expire something"


# ---------------------------------------------------------------------------
# Launch-count pin: one pallas_call per phase, regardless of B
# ---------------------------------------------------------------------------

def _count_pallas_calls(jaxpr) -> int:
    """pallas_call equations in a jaxpr, nested scopes (pjit /
    closed_call / scan / custom_* bodies) included."""
    def subs(v):
        if isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from subs(x)

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in subs(v):
                n += _count_pallas_calls(sub)
    return n


@pytest.mark.parametrize("mode,merge_rate", [("simplified", 0),
                                             ("full", 3)])
@_fanout_cases([(1, 1), (4, 1), (1, 4), (4, 4)])
def test_superstep_traces_one_pallas_call_per_phase(mode, merge_rate, B,
                                                    fanout):
    _, cfgp, ebs, tables, rings = _setup(B, mode=mode,
                                         merge_rate=merge_rate,
                                         fanout=fanout)
    fab = fb.PulseFabric(cfgp, transport="local")
    block = jax.tree.map(lambda *xs: jnp.stack(xs), *ebs[:B])
    merge = fab.init_merge()
    jaxpr = jax.make_jaxpr(
        lambda e, t, r, m: fab.superstep(e, t, r, None, m)
    )(block, tables, rings, merge)
    n = _count_pallas_calls(jaxpr.jaxpr)
    assert n == 2, (
        f"expected exactly 1 inject + 1 drain pallas_call per block, "
        f"traced {n} (mode={mode}, merge_rate={merge_rate}, B={B}, "
        f"fanout={fanout})")
