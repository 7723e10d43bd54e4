"""Per-kernel shape/dtype sweeps: Pallas (interpret on CPU) vs jnp oracle.

The fused megakernel sections at the bottom are hypothesis property
sweeps (auto-skipped when hypothesis is not installed — see conftest):
randomized loads designed around the bitwise edge cases — all-invalid
event blocks, slab overflow, deadlines wrapping 255→0, a full merge
queue, and the B=1 degeneracy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.bucket_pack import bucket_pack
from repro.kernels.bucket_pack.ref import bucket_pack_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.lif_step import lif_step
from repro.kernels.lif_step.ref import lif_step_ref
from repro.kernels.merge_sort import merge_sort
from repro.kernels.merge_sort.ref import merge_sort_ref
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def test_resolve_interpret_off_tpu(monkeypatch):
    monkeypatch.delenv(common.FORCE_INTERPRET_ENV, raising=False)
    assert common.resolve_interpret(None) is True
    assert common.resolve_interpret(False) is False
    monkeypatch.setenv(common.FORCE_INTERPRET_ENV, "1")
    assert common.resolve_interpret(False) is True


@pytest.mark.parametrize("interpret,forced", [(True, ""), (None, "1"),
                                              (False, "1")])
def test_resolve_interpret_never_interprets_on_tpu(monkeypatch, interpret,
                                                   forced):
    monkeypatch.setattr(common, "on_tpu", lambda: True)
    monkeypatch.setenv(common.FORCE_INTERPRET_ENV, forced)
    with pytest.raises(RuntimeError, match="interpret mode"):
        common.resolve_interpret(interpret)
    monkeypatch.setenv(common.FORCE_INTERPRET_ENV, "")
    assert common.resolve_interpret(None) is False


@pytest.mark.parametrize("rows,lanes", [(1, 128), (3, 512), (46, 2048)])
def test_exclusive_cumsum_matches_jnp(rows, lanes):
    x = jax.random.randint(jax.random.PRNGKey(lanes), (rows, lanes), 0, 3)

    def kernel(x_ref, o_ref):
        o_ref[...] = common.exclusive_cumsum(x_ref[...])

    got = pl.pallas_call(kernel, out_shape=x, interpret=True)(x)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.cumsum(x, axis=1) - x))


@pytest.mark.parametrize("e,b,c", [(64, 2, 4), (512, 8, 16), (777, 5, 8),
                                   (1536, 16, 128), (100, 1, 8)])
def test_bucket_pack_matches_ref(e, b, c):
    key = jax.random.PRNGKey(e * b * c)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    bid = jax.random.randint(k1, (e,), 0, b)
    addr = jax.random.randint(k2, (e,), 0, 1 << 14)
    dead = jax.random.randint(k3, (e,), 0, 256)
    valid = jax.random.uniform(k4, (e,)) < 0.6
    got = bucket_pack(bid, addr, dead, valid, n_buckets=b, capacity=c)
    want = bucket_pack_ref(bid, addr, dead, valid, n_buckets=b, capacity=c)
    np.testing.assert_array_equal(np.asarray(got.addr), np.asarray(want.addr))
    np.testing.assert_array_equal(np.asarray(got.deadline),
                                  np.asarray(want.deadline))
    np.testing.assert_array_equal(np.asarray(got.valid),
                                  np.asarray(want.valid))
    np.testing.assert_array_equal(np.asarray(got.counts),
                                  np.asarray(want.counts))
    assert int(got.overflow) == int(want.overflow)


@pytest.mark.parametrize("l,max_dead,density",
                         [(1, 4, 1.0), (7, 3, 0.5), (128, 8, 0.6),
                          (136, 4, 0.3), (500, 2, 0.9), (1024, 64, 0.0)])
def test_merge_sort_matches_ref_bit_exact(l, max_dead, density):
    """The bitonic network must reproduce the stable argsort permutation
    exactly — including heavy deadline ties and invalid lanes."""
    key = jax.random.PRNGKey(l * max_dead + int(density * 10))
    k1, k2, k3 = jax.random.split(key, 3)
    addr = jax.random.randint(k1, (l,), 0, 1 << 14)
    dead = jax.random.randint(k2, (l,), 0, max_dead)
    valid = jax.random.uniform(k3, (l,)) < density
    got = merge_sort(addr, dead, valid)
    want = merge_sort_ref(addr, dead, valid)
    for g, w, name in zip(got, want, ("addr", "deadline", "valid")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


@pytest.mark.parametrize("l,max_ahead,density,now",
                         [(1, 4, 1.0, 0), (7, 3, 0.5, 10), (128, 8, 0.6, 250),
                          (136, 100, 0.3, 200), (500, 2, 0.9, 255),
                          (1024, 64, 0.0, 1000003)])
def test_merge_sort_words_matches_ref_bit_exact(l, max_ahead, density, now):
    """The word-path bitonic network must reproduce the stable wrap-key
    argsort exactly — including deadlines that wrap past 255, heavy ties,
    and invalid (sentinel) lanes."""
    from repro.core import events as ev
    from repro.kernels.merge_sort.ref import merge_sort_words_ref

    key = jax.random.PRNGKey(l * max_ahead + int(density * 10) + now)
    k1, k2, k3 = jax.random.split(key, 3)
    addr = jax.random.randint(k1, (l,), 0, 1 << 14)
    dead = now + jax.random.randint(k2, (l,), -max_ahead, max_ahead + 1)
    valid = jax.random.uniform(k3, (l,)) < density
    words = ev.encode_word(addr, dead, valid)
    from repro.kernels.merge_sort import merge_sort_words

    got = merge_sort_words(words, jnp.int32(now))
    want = merge_sort_words_ref(words, jnp.int32(now))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_merge_sort_words_under_vmap():
    """The fabric's local path runs the word kernel per chip under vmap,
    with a per-chip traced clock."""
    from repro.core import events as ev
    from repro.kernels.merge_sort import merge_sort_words
    from repro.kernels.merge_sort.ref import merge_sort_words_ref

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    addr = jax.random.randint(ks[0], (4, 70), 0, 100)
    dead = jax.random.randint(ks[1], (4, 70), 240, 280)
    valid = jax.random.uniform(ks[2], (4, 70)) < 0.5
    words = ev.encode_word(addr, dead, valid)
    now = jnp.asarray([0, 250, 255, 123], jnp.int32)
    got = jax.vmap(merge_sort_words)(words, now)
    want = jax.vmap(merge_sort_words_ref)(words, now)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_merge_sort_under_vmap():
    """The fabric's local path runs the kernel per chip under vmap."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    addr = jax.random.randint(ks[0], (4, 70), 0, 100)
    dead = jax.random.randint(ks[1], (4, 70), 0, 9)
    valid = jax.random.uniform(ks[2], (4, 70)) < 0.5
    got = jax.vmap(lambda a, d, v: merge_sort(a, d, v))(addr, dead, valid)
    want = jax.vmap(merge_sort_ref)(addr, dead, valid)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_merge_step_pallas_matches_jnp():
    """merge_step with use_pallas=True is bit-identical to the reference,
    across a stateful multi-cycle run."""
    from repro.core import merge as mg

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    addr = jax.random.randint(ks[0], (6, 8), 0, 256)
    dead = jax.random.randint(ks[1], (6, 8), 0, 16)
    valid = jax.random.uniform(ks[2], (6, 8)) < 0.7
    buf_r, buf_p = mg.merge_init(16), mg.merge_init(16)
    for _ in range(4):
        buf_r, out_r, drop_r = mg.merge_step(buf_r, addr, dead, valid, rate=5)
        buf_p, out_p, drop_p = mg.merge_step(buf_p, addr, dead, valid, rate=5,
                                             use_pallas=True)
        for g, w in zip(out_p, out_r):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        for g, w in zip(buf_p, buf_r):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert int(drop_p) == int(drop_r)
        addr = jnp.zeros_like(addr)
        dead = jnp.zeros_like(dead)
        valid = jnp.zeros_like(valid)


@pytest.mark.parametrize("shape", [(64,), (1024,), (3, 333), (2, 5, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_lif_step_matches_ref(shape, dtype):
    key = jax.random.PRNGKey(int(np.prod(shape)))
    ks = jax.random.split(key, 3)
    v = jax.random.normal(ks[0], shape, dtype)
    refrac = jax.random.randint(ks[1], shape, 0, 3)
    cur = jax.random.normal(ks[2], shape, dtype) * 0.5
    args = (v, refrac, cur, jnp.full(shape, 10.0, dtype),
            jnp.full(shape, 1.0, dtype), jnp.zeros(shape, dtype),
            jnp.zeros(shape, dtype), jnp.full(shape, 2, jnp.int32))
    got = lif_step(*args)
    want = lif_step_ref(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), rtol=1e-6)


@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal",
    [
        (1, 4, 4, 128, 128, 64, True),
        (2, 8, 2, 128, 256, 64, True),
        (1, 4, 1, 130, 190, 32, True),    # padding path
        (1, 2, 2, 128, 128, 128, False),
        (2, 4, 2, 256, 128, 64, False),
    ],
)
def test_flash_attention_matches_ref(b, hq, hkv, sq, skv, d, causal):
    key = jax.random.PRNGKey(b * sq * skv)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, hq, sq, d), jnp.float32)
    k = jax.random.normal(k2, (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(k3, (b, hkv, skv, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, force_kernel=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(9)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (1, 2, 128, 64), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 2, 128, 64), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, force_kernel=True)
    want = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)


@pytest.mark.parametrize("b,t,din,n", [(1, 128, 128, 16), (2, 130, 100, 8),
                                       (1, 64, 256, 64)])
def test_ssm_scan_matches_ref(b, t, din, n):
    key = jax.random.PRNGKey(b * t * din)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (b, t, din))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, din)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (din, n)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, t, n))
    Cm = jax.random.normal(ks[4], (b, t, n))
    D = jax.random.normal(ks[5], (din,))
    got = ssm_scan(x, dt, A, Bm, Cm, D, force_kernel=True)
    want = ssm_scan_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Fused inject megakernel: property sweep vs the composed reference
# ---------------------------------------------------------------------------

def _inject_case(seed, B, E, density, tight, fanout):
    """Random event block + routing table of fan-out ``fanout``, skewed
    at the edge cases: density 0.0 is the all-invalid block, ``tight``
    shrinks the bucket capacity to force slab overflow, and t0 near 250
    pushes deadlines across the 255→0 wrap."""
    from repro.core import events as ev
    from repro.core import routing as rt

    rng = np.random.default_rng(seed)
    n = 24
    t0 = int(rng.choice([0, 5, 120, 250, 254]))
    addr = jnp.asarray(rng.integers(0, n, (B, E)), jnp.int32)
    time = jnp.asarray(t0 + rng.integers(0, B + 1, (B, E)), jnp.int32)
    valid = jnp.asarray(rng.random((B, E)) < density)
    events = ev.EventBuffer(addr=addr, time=time, valid=valid)
    table = rt.random_table(jax.random.PRNGKey(seed % 997), n, 4,
                            fanout=fanout, max_delay=12,
                            min_delay=max(2, B))
    reach = (None if rng.random() < 0.5
             else jnp.asarray(rng.random(4) < 0.8))
    cap = 2 if tight else 8
    return events, table, reach, t0, cap


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 4]),
       st.integers(1, 100), st.sampled_from(["simplified", "full"]),
       st.sampled_from([0.0, 0.6, 1.0]), st.booleans(),
       st.sampled_from([1, 4]))
def test_fused_inject_property(seed, B, E, mode, density, tight, fanout):
    from repro.kernels.fused_inject import fused_inject
    from repro.kernels.fused_inject.ref import fused_inject_ref

    events, table, reach, t0, cap = _inject_case(seed, B, E, density,
                                                 tight, fanout)
    kw = dict(n_chips=4, buckets_per_chip=2, capacity=cap, mode=mode,
              time_window=4)
    got = fused_inject(events, table, reach, jnp.int32(t0), **kw)
    want = fused_inject_ref(events, table, reach, jnp.int32(t0), **kw)
    for fld in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, fld)), np.asarray(getattr(want, fld)),
            err_msg=f"{fld} (B={B} E={E} mode={mode} d={density} "
                    f"tight={tight} K={fanout})")


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 4]),
       st.sampled_from([3, 20, 64]), st.sampled_from([1, 4]))
def test_fused_lif_inject_property(seed, B, event_capacity, fanout):
    """The LIF-fronted megakernel (membrane update + spike detect fused
    ahead of the inject path) against lif_step + from_spikes + the
    composed chain — including event_capacity below and above the
    population size (truncation and degenerate B=1)."""
    from repro.core import routing as rt
    from repro.kernels.fused_inject import fused_lif_inject
    from repro.kernels.fused_inject.ref import fused_lif_inject_ref
    from repro.snn.neuron import LIFParams

    rng = np.random.default_rng(seed)
    n = 20
    t0 = int(rng.choice([0, 250]))
    v = jnp.asarray(rng.normal(0, 1, (n,)), jnp.float32)
    refrac = jnp.asarray(rng.integers(0, 3, (n,)), jnp.int32)
    cur = jnp.asarray(rng.normal(0.5, 1.0, (B, n)), jnp.float32)
    params = LIFParams(tau_m=10.0, v_th=1.0, v_reset=0.0, v_rest=0.0,
                       refrac=2)
    table = rt.random_table(jax.random.PRNGKey(seed % 991), n, 4,
                            fanout=fanout, max_delay=12,
                            min_delay=max(2, B))
    kw = dict(event_capacity=event_capacity, n_chips=4,
              buckets_per_chip=2, capacity=4, mode="simplified",
              time_window=1)
    got = fused_lif_inject(v, refrac, cur, params, table, None,
                           jnp.int32(t0), **kw)
    want = fused_lif_inject_ref(v, refrac, cur, params, table, None,
                                jnp.int32(t0), **kw)
    for fld in ("v", "refrac", "spikes", "voltage"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, fld)), np.asarray(getattr(want, fld)),
            err_msg=fld)
    for fld in want.inject._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got.inject, fld)),
            np.asarray(getattr(want.inject, fld)),
            err_msg=f"inject.{fld}")


# ---------------------------------------------------------------------------
# Fused drain megakernel: property sweep vs the composed reference
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 4]),
       st.integers(1, 70),
       st.sampled_from(["passthrough", "sort", "rate"]),
       st.sampled_from([0.0, 0.6, 1.0]), st.booleans(),
       st.sampled_from([None, True, False]))
def test_fused_drain_property(seed, B, L, mode, density, queue_full, gate):
    """Wrap-aware sort + rate-limited merge + ring deposit fused, against
    the composed merge/deposit chain — including the all-sentinel block
    (density 0), a pre-filled merge queue (``queue_full`` → congestion
    drops), deadlines wrapping 255→0, the pipeline gate in all three
    states, and the B=1 degeneracy."""
    from repro.core import delays as dl
    from repro.core import events as ev
    from repro.kernels.fused_drain import fused_drain
    from repro.kernels.fused_drain.ref import fused_drain_ref

    rng = np.random.default_rng(seed)
    D, Nin, depth, rate = 12, 40, 16, 3
    t0 = int(rng.choice([0, 100, 250, 254]))

    def words(shape, spread, p):
        a = jnp.asarray(rng.integers(0, 64, shape))
        d = jnp.asarray(t0 + rng.integers(-6, spread, shape))
        va = jnp.asarray(rng.random(shape) < p)
        return ev.encode_word(a, d, va).astype(jnp.int32)

    delivered = words((B, L), 40, density)
    queue = (words((depth,), 10, 1.0 if queue_full else 0.4)
             if mode == "rate" else None)
    ring = dl.DelayRing(
        ring=jnp.asarray(rng.integers(0, 3, (D, Nin)), jnp.int32),
        now=jnp.int32(t0))
    g = None if gate is None else jnp.asarray(gate)
    kw = dict(mode=mode, rate=rate, extra_ahead=int(rng.choice([0, B])),
              gate=g)
    got = fused_drain(ring, delivered, queue, jnp.int32(t0), **kw)
    want = fused_drain_ref(ring, delivered, queue, jnp.int32(t0), **kw)
    np.testing.assert_array_equal(np.asarray(got.ring.ring),
                                  np.asarray(want.ring.ring),
                                  err_msg="ring")
    for fld in ("words", "dep_expired", "dropped"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, fld)), np.asarray(getattr(want, fld)),
            err_msg=f"{fld} (B={B} L={L} mode={mode} d={density})")
    if mode == "rate":
        np.testing.assert_array_equal(np.asarray(got.queue),
                                      np.asarray(want.queue),
                                      err_msg="queue")


def test_ssm_scan_decode_parity_with_model_path():
    """kernels/ssm_scan oracle == models/ssm.scan_chunked (shared contract)."""
    from repro.models.ssm import scan_chunked

    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 6)
    b, t, din, n = 2, 48, 32, 8
    x = jax.random.normal(ks[0], (b, t, din))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, din)))
    A = -jnp.exp(jax.random.normal(ks[2], (din, n)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, t, n))
    Cm = jax.random.normal(ks[4], (b, t, n))
    D = jax.random.normal(ks[5], (din,))
    want = ssm_scan_ref(x, dt, A, Bm, Cm, D)
    h0 = jnp.zeros((b, din, n), jnp.float32)
    got, _ = scan_chunked(x, dt, A, Bm, Cm, D, h0, unroll=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
