"""The single-word wire format (paper §2): 14-bit address | 8-bit wrap
timestamp in one int32, threaded end-to-end through the fabric hot path.

Pins the tentpole contracts:
  * encode/decode roundtrip over the full address and time ranges, and the
    reserved all-ones sentinel can never collide with a real event;
  * the wrap-aware sort key is monotone in the true deadline inside the
    aggregation window (|deadline - now| < 128);
  * a deadline crossing the 255 -> 0 wraparound survives
    exchange + merge + deposit (both merge flavours);
  * `pc.exchange` issues exactly ONE all_to_all per step (HLO-verified via
    the repo's own loop-aware analyzer) where the SoA format issued three;
  * the on-wire payload cost drops 3x vs the three-array format.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import delays as dl
from repro.core import events as ev
from repro.core import fabric as fb
from repro.core import merge as mg
from repro.core import pulse_comm as pc
from repro.core import routing as rt


# ---------------------------------------------------------------------------
# encode / decode
# ---------------------------------------------------------------------------

def test_word_roundtrip_full_address_range():
    addr = jnp.arange(1 << ev.ADDR_BITS, dtype=jnp.int32)
    for t in (0, 1, 127, 128, 255, 256, 1000003):
        time = jnp.full_like(addr, t)
        w = ev.encode_word(addr, time, jnp.ones_like(addr, dtype=bool))
        a, t8, v = ev.decode_word(w)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(addr))
        assert int(t8[0]) == t % ev.TIME_MOD and bool(np.asarray(v).all())
        # reserved high bits stay clear: validity == sign
        assert int(w.min()) >= 0 and int(w.max()) < (1 << (ev.ADDR_BITS + 8))


def test_word_roundtrip_full_time_range():
    time = jnp.arange(4 * ev.TIME_MOD, dtype=jnp.int32) - ev.TIME_MOD
    for a in (0, 1, 12345, (1 << ev.ADDR_BITS) - 1):
        addr = jnp.full_like(time, a)
        w = ev.encode_word(addr, time, jnp.ones_like(time, dtype=bool))
        aa, t8, v = ev.decode_word(w)
        np.testing.assert_array_equal(np.asarray(aa), np.asarray(addr))
        np.testing.assert_array_equal(np.asarray(t8),
                                      np.asarray(time) % ev.TIME_MOD)


def test_sentinel_word_is_reserved_and_decodes_empty():
    w = ev.encode_word(jnp.asarray([5, 9]), jnp.asarray([3, 7]),
                       jnp.asarray([False, True]))
    assert int(w[0]) == ev.WORD_SENTINEL
    a, t8, v = ev.decode_word(w)
    assert int(a[0]) == ev.ADDR_SENTINEL and int(t8[0]) == 0
    np.testing.assert_array_equal(np.asarray(v), [False, True])
    # the sentinel sorts after every real event at any clock
    for now in (0, 77, 255):
        key = ev.word_sort_key(w, jnp.int32(now))
        assert int(key[0]) == ev.TIME_MOD and int(key[1]) < ev.TIME_MOD


@given(st.integers(0, (1 << ev.ADDR_BITS) - 1), st.integers(0, 2**31 - 1),
       st.booleans())
def test_word_roundtrip_property(addr, time, valid):
    w = ev.encode_word(jnp.asarray([addr]), jnp.asarray([time]),
                       jnp.asarray([valid]))
    a, t8, v = ev.decode_word(w)
    if valid:
        assert int(a[0]) == addr and int(t8[0]) == time % 256 and bool(v[0])
    else:
        assert int(w[0]) == ev.WORD_SENTINEL and not bool(v[0])


@given(st.integers(0, 10**6), st.lists(st.integers(-127, 127), min_size=2,
                                       max_size=20))
def test_word_sort_key_monotone_in_true_deadline(now, deltas):
    """Inside the aggregation window the wrap key orders words exactly like
    their full-width deadlines would."""
    deadlines = [now + d for d in deltas if now + d >= 0]
    if len(deadlines) < 2:
        return
    w = ev.encode_word(jnp.zeros(len(deadlines), jnp.int32),
                       jnp.asarray(deadlines),
                       jnp.ones(len(deadlines), dtype=bool))
    key = np.asarray(ev.word_sort_key(w, jnp.int32(now)))
    order_by_key = np.argsort(key, kind="stable")
    order_by_deadline = np.argsort(np.asarray(deadlines), kind="stable")
    np.testing.assert_array_equal(order_by_key, order_by_deadline)


@given(st.integers(0, 10**6), st.integers(-127, 127))
def test_word_deadline_reconstruction(now, delta):
    if now + delta < 0:
        return
    w = ev.encode_word(jnp.asarray([3]), jnp.asarray([now + delta]),
                       jnp.asarray([True]))
    assert int(ev.word_deadline(w, jnp.int32(now))[0]) == now + delta


# ---------------------------------------------------------------------------
# Wraparound survival through the whole pipeline
# ---------------------------------------------------------------------------

def _wrap_setup(merge_rate, *, t0=253, delay=5, n=8):
    """Events stamped just below the 8-bit wrap whose deadlines land past
    it: t0 + delay = 258 -> on-wire timestamp 2."""
    cfg = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=n, buckets_per_chip=1,
        ring_depth=16, mode="full", merge_rate=merge_rate, merge_depth=64)
    table = rt.feedforward_table(n, src_chip=0, dst_chip=1, delay=delay)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (2,) + x.shape),
                          table)
    spikes = jnp.stack([jnp.ones((n,), bool), jnp.zeros((n,), bool)])
    ebs = jax.vmap(lambda s: ev.from_spikes(s, t0, n)[0])(spikes)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n, now=t0))(
        jnp.arange(2))
    return cfg, ebs, tables, rings, t0 + delay


@pytest.mark.parametrize("merge_rate", [0, 3])
def test_wraparound_deadline_survives_exchange_merge_deposit(merge_rate):
    cfg, ebs, tables, rings, deadline = _wrap_setup(merge_rate)
    n = cfg.neurons_per_chip
    fab = fb.PulseFabric(cfg, transport="local")
    ring, merge = rings, fab.init_merge()
    delivered = 0
    for step in range(6):
        zero = jax.tree.map(jnp.zeros_like, ebs)
        res = fab.step(ebs if step == 0 else zero, tables, ring, None, merge)
        assert int(np.asarray(res.stats.expired).sum()) == 0
        assert int(np.asarray(res.stats.merge_dropped).sum()) == 0
        delivered += int(np.asarray(res.delivered.valid).sum())
        # on-wire timestamps of everything delivered wrapped past 255
        d8 = np.asarray(res.delivered.deadline)[np.asarray(
            res.delivered.valid)]
        assert (d8 == deadline % 256).all()
        ring, merge = res.ring, res.merge
        # advance the clock like the network step protocol does
        ring = jax.vmap(dl.tick)(ring)
        if merge_rate == 0:
            break
    assert delivered == n
    # every event sits in the deadline's ring slot on the destination chip
    ring_np = np.asarray(ring.ring)
    assert ring_np.sum() == n
    assert ring_np[1, deadline % cfg.ring_depth].sum() == n


def test_out_of_window_deadline_expires_instead_of_aliasing():
    """A routing delay past the wrap half-window (e.g. 259) cannot ride the
    8-bit wire timestamp: 259 % 256 = 3 would alias onto ring slot 3 and
    deposit a ghost spike 256 steps early.  The fabric must drop such
    events at the injection boundary with `expired` accounting — the same
    bucket the pre-word path counted them in."""
    n = 4
    cfg = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=n, ring_depth=16)
    table = rt.feedforward_table(n, src_chip=0, dst_chip=1, delay=259)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (2,) + x.shape),
                          table)
    spikes = jnp.stack([jnp.ones((n,), bool), jnp.zeros((n,), bool)])
    ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, n)[0])(spikes)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n))(jnp.arange(2))
    res = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    assert int(np.asarray(res.stats.sent).sum()) == n
    assert int(np.asarray(res.stats.expired).sum()) == n
    assert int(np.asarray(res.ring.ring).sum()) == 0   # no ghost deposits
    assert int(np.asarray(res.delivered.valid).sum()) == 0


def test_stale_events_expire_at_injection_not_in_merge_queue():
    """Events already expired at injection (deadline <= now) must never
    enter the merge queue: a word admitted stale could age past the wrap
    window while queued behind other stale words and re-sort as far-future
    (the sort key wraps at staleness 128), depositing a ghost spike.  They
    are undeliverable regardless, so the fabric counts them expired at the
    source."""
    n = 8
    cfg = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=n, ring_depth=16,
        mode="full", merge_rate=1, merge_depth=64)
    table = rt.feedforward_table(n, src_chip=0, dst_chip=1, delay=1)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (2,) + x.shape),
                          table)
    now = 200
    spikes = jnp.stack([jnp.ones((n,), bool), jnp.zeros((n,), bool)])
    # stamped 128 steps in the past: deadline = now - 127 <= now
    ebs = jax.vmap(lambda s: ev.from_spikes(s, now - 128, n)[0])(spikes)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n, now=now))(
        jnp.arange(2))
    fab = fb.PulseFabric(cfg, transport="local")
    ring, merge = rings, fab.init_merge()
    zero = jax.tree.map(jnp.zeros_like, ebs)
    for step in range(260):
        res = fab.step(ebs if step == 0 else zero, tables, ring, None, merge)
        ring, merge = res.ring, res.merge
        ring = jax.vmap(dl.tick)(ring)
        if step == 0:
            assert int(np.asarray(res.stats.expired).sum()) == n
            assert int(np.asarray(merge.valid).sum()) == 0  # never queued
    assert int(np.asarray(ring.ring).sum()) == 0            # no ghosts, ever


def test_config_rejects_wrap_unsafe_settings():
    """The wire word can only carry what fits it: 14-bit addresses and
    deadlines reconstructible inside the 8-bit wrap window — configs that
    could break either are rejected up front."""
    ok = dict(n_chips=2, neurons_per_chip=16, n_inputs_per_chip=16,
              event_capacity=16, bucket_capacity=4, ring_depth=16)
    pc.PulseCommConfig(**ok)                      # sanity: valid config
    with pytest.raises(ValueError, match="input address"):
        pc.PulseCommConfig(**{**ok, "n_inputs_per_chip": (1 << 14) + 1})
    with pytest.raises(ValueError, match="ring_depth"):
        pc.PulseCommConfig(**{**ok, "ring_depth": 128})
    with pytest.raises(ValueError, match="merge_depth"):
        pc.PulseCommConfig(**{**ok, "mode": "full", "merge_rate": 1,
                              "merge_depth": 129})
    # boundary: depth == 128 * rate is still safe
    pc.PulseCommConfig(**{**ok, "mode": "full", "merge_rate": 2,
                          "merge_depth": 256})


def test_wraparound_matches_unwrapped_reference():
    """The same topology run far from the wrap boundary must produce the
    identical ring occupancy pattern — wrap is invisible to delivery."""
    rings = {}
    for t0 in (3, 253):
        cfg, ebs, tables, r0, deadline = _wrap_setup(0, t0=t0)
        res = fb.PulseFabric(cfg, transport="local").step(ebs, tables, r0)
        assert int(np.asarray(res.stats.expired).sum()) == 0
        rings[t0] = np.asarray(res.ring.ring)
    # slots differ only by the clock offset; roll them into alignment
    shift = ((253 + 5) % 16) - ((3 + 5) % 16)
    np.testing.assert_array_equal(np.roll(rings[3], shift, axis=1),
                                  rings[253])


# ---------------------------------------------------------------------------
# Exactly one collective per step (HLO-verified)
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

    n, N = 4, 16
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("chip",))
    key = jax.random.PRNGKey(0)
    for mode, merge_rate in [("simplified", 0), ("full", 3)]:
        cfg = pc.PulseCommConfig(
            n_chips=n, neurons_per_chip=N, n_inputs_per_chip=N,
            event_capacity=N, bucket_capacity=4, buckets_per_chip=2,
            ring_depth=16, mode=mode, merge_rate=merge_rate, merge_depth=8)
        spikes = jax.random.uniform(key, (n, N)) < 0.6
        ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, N)[0])(spikes)
        table = rt.random_table(key, N, n, max_delay=8)
        tables = jax.tree.map(lambda z: jnp.broadcast_to(z, (n,) + z.shape),
                              table)
        rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, N))(jnp.arange(n))
        shard = fb.PulseFabric(cfg, transport="shard_map")
        merge_b = None
        if merge_rate:
            from repro.core import merge as mg
            merge_b = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                mg.merge_init(cfg.merge_depth))

        def body(e, t, r, m):
            sq = lambda z: jax.tree.map(lambda a: a[0], z)
            opt = lambda z: None if z is None else sq(z)
            out = shard.step(sq(e), sq(t), sq(r), None, opt(m))
            return jax.tree.map(lambda a: a[None] if hasattr(a, "ndim")
                                else a, out)

        f = shard_map(body, mesh=mesh, in_specs=(P("chip"),) * 4,
                      out_specs=P("chip"), check_vma=False)
        compiled = jax.jit(f).lower(ebs, tables, rings, merge_b).compile()
        counts = hlo_stats.count_collectives(compiled)
        count = hlo_stats.count_collectives(compiled, "all-to-all")
        assert count == 1, (mode, merge_rate, counts)
        assert sum(counts.values()) == count, (mode, merge_rate, counts)
        print(f"ONE_ALL_TO_ALL mode={mode} merge={merge_rate}")
    print("SINGLE_COLLECTIVE_OK")
""")


def test_exchange_issues_exactly_one_all_to_all_per_step():
    out = subprocess.run(
        [sys.executable, "-c", _HLO_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "SINGLE_COLLECTIVE_OK" in out.stdout, out.stderr[-3000:]


# ---------------------------------------------------------------------------
# Wire-byte accounting: 3x payload drop vs the SoA format
# ---------------------------------------------------------------------------

def test_wire_bytes_payload_drops_three_x():
    assert pc.SOA_EVENT_BYTES == 3 * pc.EVENT_BYTES
    n_chips, n = 4, 128
    cfg = pc.PulseCommConfig(
        n_chips=n_chips, neurons_per_chip=n, n_inputs_per_chip=n,
        event_capacity=n, bucket_capacity=n, ring_depth=16)
    key = jax.random.PRNGKey(0)
    spikes = jnp.ones((n_chips, n), bool)
    ebs = jax.vmap(lambda s: ev.from_spikes(s, 0, n)[0])(spikes)
    table = rt.random_table(key, n, n_chips, max_delay=8)
    tables = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_chips,) + x.shape),
                          table)
    rings = jax.vmap(lambda _: dl.init(cfg.ring_depth, n))(
        jnp.arange(n_chips))
    res = fb.PulseFabric(cfg, transport="local").step(ebs, tables, rings)
    sent = int(res.stats.sent.sum())
    of = int(res.stats.overflow.sum())
    wire = int(res.stats.wire_bytes.sum())
    n_packets = sum(int((np.asarray(res.stats.traffic)[c] > 0).sum())
                    for c in range(n_chips))
    headers = n_packets * pc.HEADER_BYTES
    payload = wire - headers
    assert payload == (sent - of) * pc.EVENT_BYTES
    wire_soa = headers + (sent - of) * pc.SOA_EVENT_BYTES
    # payload-dominated at this capacity: the full wire cost drops ~3x too
    assert (wire_soa - headers) == 3 * payload
    assert wire_soa / wire > 2.5


# ---------------------------------------------------------------------------
# Word slab consistency through pack and merge
# ---------------------------------------------------------------------------

def test_pack_emits_encoded_words():
    from repro.core import buckets as bk

    bid = jnp.asarray([0, 1, 0, 2], jnp.int32)
    addr = jnp.asarray([7, 8, 9, 10], jnp.int32)
    dead = jnp.asarray([300, 2, 3, 255], jnp.int32)   # 300 wraps to 44
    valid = jnp.asarray([True, True, False, True])
    packed = bk.pack(bid, addr, dead, valid, n_buckets=3, capacity=2)
    w = np.asarray(packed.words)
    assert w[0, 0] == (7 << 8) | (300 % 256)
    assert w[1, 0] == (8 << 8) | 2
    assert w[2, 0] == (10 << 8) | 255
    assert (w[[0, 1, 2], [1, 1, 1]] == ev.WORD_SENTINEL).all()


def test_merge_words_orders_across_wrap():
    now = jnp.int32(250)
    deadlines = [251, 2, 255, 253, 1]      # true order: 251,253,255,(256+)1,2
    w = ev.encode_word(jnp.arange(5, dtype=jnp.int32),
                       jnp.asarray(deadlines), jnp.ones(5, dtype=bool))
    merged = mg.merge_words(w, now)
    got = np.asarray(ev.word_time(merged))
    assert got.tolist() == [251 % 256, 253, 255, 1, 2]


def test_merge_buffer_words_roundtrip_state():
    buf = mg.merge_init(8)
    assert int(buf.occupancy()) == 0
    w = ev.encode_word(jnp.asarray([1, 2]), jnp.asarray([5, 4]),
                       jnp.asarray([True, True]))
    buf, out, dropped = mg.merge_step_words(buf, w, now=jnp.int32(0), rate=1)
    assert int(dropped) == 0
    assert int(ev.word_addr(out)[0]) == 2       # earliest deadline first
    assert int(buf.occupancy()) == 1
    assert int(buf.addr[0]) == 1 and bool(buf.valid[0])
