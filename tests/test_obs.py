"""Telemetry subsystem (repro.obs) tests.

  * bitwise invariance — the delivered spike path never reads the
    MetricsCarry, so telemetry on/off runs are bitwise-equal (serial
    superstep and pipelined schedules),
  * the property pin — the in-scan aggregates equal an offline
    reduction of the per-step CommStats records (exact for the int
    totals/histograms/maxima; allclose for the EMAs, whose closed-form
    block fold only differs from the sequential loop by float
    association),
  * the conservation helper, flight-ring last-K semantics, exporters,
    and the monitor CLI smoke.
"""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import pulse_comm as pc
from repro.core import topology as tpo
from repro.obs import metrics as obm
from repro.snn import network as net


# ---------------------------------------------------------------------------
# Network-threaded telemetry: bitwise invariance + offline reduction
# ---------------------------------------------------------------------------

def _net(telemetry=None, pipeline=False, superstep=4, n_chips=4, nn=16,
         ring=False, merge=False):
    # merge: full mode, renamed buckets and a rate-limited merge queue
    # that the offered load keeps occupied.
    full = dict(mode="full", buckets_per_chip=2, merge_rate=2,
                merge_depth=8) if merge else {}
    comm = pc.PulseCommConfig(
        n_chips=n_chips, neurons_per_chip=nn, n_inputs_per_chip=nn,
        event_capacity=nn, bucket_capacity=nn, ring_depth=16,
        superstep=superstep, **full)
    topo = tpo.ring(n_chips, link_latency=1) if (ring or pipeline) else None
    cfg = net.NetworkConfig(comm=comm, topology=topo, pipeline=pipeline,
                            telemetry=telemetry)
    params = net.init_params(jax.random.PRNGKey(3), cfg)
    return cfg, params, net.init_state(cfg, params)


def _ext(cfg, T, key=7):
    c = cfg.comm
    return 1.5 * (jax.random.uniform(
        jax.random.PRNGKey(key),
        (T, c.n_chips, c.n_inputs_per_chip)) < 0.35)


@pytest.mark.parametrize("pipeline,merge", [(False, False), (True, False),
                                            (False, True)],
                         ids=["superstep", "pipelined", "merge"])
def test_telemetry_bitwise_invariant(pipeline, merge):
    """Telemetry on vs off: identical spikes, voltages, and final rings
    (and merge queues); the carry itself aggregates every substep."""
    T = 16
    cfg_off, params, s_off = _net(telemetry=None, pipeline=pipeline,
                                  merge=merge)
    cfg_on, _, s_on = _net(telemetry=True, pipeline=pipeline, merge=merge)
    ext = _ext(cfg_off, T)

    f_off, r_off = jax.jit(lambda s, e: net.run(cfg_off, params, s, e))(
        s_off, ext)
    f_on, r_on = jax.jit(lambda s, e: net.run(cfg_on, params, s, e))(
        s_on, ext)

    np.testing.assert_array_equal(np.asarray(r_off.spikes),
                                  np.asarray(r_on.spikes))
    np.testing.assert_array_equal(np.asarray(r_off.voltage),
                                  np.asarray(r_on.voltage))
    np.testing.assert_array_equal(np.asarray(f_off.ring.ring),
                                  np.asarray(f_on.ring.ring))
    assert f_off.metrics is None
    m = f_on.metrics
    assert m is not None
    # the pipelined scan sees one extra all-zero prologue block
    B = cfg_on.comm.superstep
    assert int(m.steps) == T + (B if pipeline else 0)
    sent_rec = int(np.asarray(r_on.stats.sent).sum())
    assert int(m.totals[obm.SCALAR_FIELDS.index("sent")]) == sent_rec
    assert sent_rec > 0
    if merge:
        np.testing.assert_array_equal(np.asarray(f_off.merge.words),
                                      np.asarray(f_on.merge.words))
        assert int(m.merge_occ_max) > 0
        dropped = int(np.asarray(r_on.stats.merge_dropped).sum())
        assert int(m.totals[obm.SCALAR_FIELDS.index("merge_dropped")]) == dropped


def test_metrics_match_offline_reduction():
    """Property pin: the carry's aggregates equal an offline reduction
    of the recorded per-step CommStats."""
    T = 24
    cfg, params, state = _net(telemetry=True, ring=True)
    mcfg = net._metrics_cfg(cfg)
    final, recs = net.run(cfg, params, state, _ext(cfg, T))
    s = obs.metrics_summary(final.metrics, mcfg)
    assert s["steps"] == T

    edges = np.asarray(obm.HIST_EDGES)
    a = mcfg.ema_alpha
    for fld in obm.SCALAR_FIELDS:
        arr = np.asarray(getattr(recs.stats, fld)).reshape(T, -1)
        fleet = arr.sum(1)
        assert s["totals"][fld] == fleet.sum(), fld
        assert s["max"][fld] == fleet.max(), fld
        assert s["chip_totals"][fld] == arr.sum(0).tolist(), fld
        bucket = (fleet[:, None] >= edges[None, :]).sum(1)
        want_hist = np.bincount(bucket, minlength=obm.N_BUCKETS)
        assert s["hist"][fld] == want_hist.tolist(), fld
        ema = 0.0
        for x in fleet:                      # sequential reference
            ema = a * ema + (1 - a) * float(x)
        np.testing.assert_allclose(s["ema"][fld], ema, rtol=1e-4,
                                   atol=1e-4, err_msg=fld)
    # link word totals equal the per-step link_words reduction
    lw = np.asarray(recs.stats.link_words)
    assert np.asarray(s["link"]["words"]).sum() == lw.sum()


def test_metrics_ride_checkpoint_roundtrip(tmp_path):
    """The carry is ordinary state: it survives save/restore and two
    half-runs aggregate exactly like one full run."""
    from repro import checkpoint as ckpt

    T = 16
    cfg, params, state = _net(telemetry=True)
    ext = _ext(cfg, T)
    full, _ = net.run(cfg, params, state, ext)

    half, _ = net.run(cfg, params, state, ext[: T // 2])
    ckpt.save(half, str(tmp_path), 0)
    restored = ckpt.restore(str(tmp_path), 0,
                            jax.tree.map(jnp.zeros_like, half))
    resumed, _ = net.run(cfg, params, restored, ext[T // 2:])
    for a, b in zip(jax.tree.leaves(full.metrics),
                    jax.tree.leaves(resumed.metrics)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# check_conservation
# ---------------------------------------------------------------------------

def test_check_conservation_closes():
    rep = obs.check_conservation(
        {"sent": 10, "overflow": 2, "expired": 1}, delivered=5, queued=2)
    assert rep.ok and rep.residual == 0
    assert rep.legs["overflow"] == 2
    assert "injected" in rep.render()


def test_check_conservation_strict_raises_with_breakdown():
    with pytest.raises(AssertionError, match="overflow"):
        obs.check_conservation({"sent": 10, "overflow": 2}, delivered=5)
    rep = obs.check_conservation({"sent": 10, "overflow": 2}, delivered=5,
                                 strict=False)
    assert not rep.ok and rep.residual == 3


def test_check_conservation_sums_arrays_and_extras():
    stats = {"sent": np.array([4, 6]), "overflow": np.array([[1], [1]])}
    assert obs.check_conservation(stats, delivered=7, queued=1).ok
    # in-flight carry legs enter via the extra_* terms (pipeline suite)
    rep = obs.check_conservation({"sent": 5}, delivered=3, in_flight=1,
                                 extra_injected=2, extra_accounted=3)
    assert rep.ok


# ---------------------------------------------------------------------------
# Flight ring
# ---------------------------------------------------------------------------

def _fake_stats(n_chips, sent, backlog=0):
    z = jnp.zeros((n_chips,), jnp.int32)
    return types.SimpleNamespace(
        sent=jnp.full((n_chips,), sent, jnp.int32),
        overflow=z, merge_dropped=z, expired=z, stalled=z,
        wire_bytes=z, lost_to_failure=z,
        utilization=jnp.zeros((n_chips,), jnp.float32),
        link_words=jnp.full((n_chips, 1), sent, jnp.int32),
        link_backlog=jnp.full((n_chips, 1), backlog, jnp.int32))


def test_flight_ring_keeps_last_k_blocks():
    mcfg = obs.MetricsConfig(flight_depth=3)
    m = obs.metrics_init(mcfg, 2)
    for b in range(7):
        m = obs.metrics_update(mcfg, m, _fake_stats(2, b + 1))
    rows = obs.flight_rows(m.flight)
    assert [r["seq"] for r in rows] == [4, 5, 6]
    assert [r["t0"] for r in rows] == [4, 5, 6]
    assert [r["fleet"]["sent"] for r in rows] == [10, 12, 14]
    assert rows[-1]["per_chip"]["sent"] == [7, 7]


def test_flight_ring_partial_fill():
    mcfg = obs.MetricsConfig(flight_depth=8)
    m = obs.metrics_init(mcfg, 2)
    m = obs.metrics_update(mcfg, m, _fake_stats(2, 5))
    rows = obs.flight_rows(m.flight)
    assert [r["seq"] for r in rows] == [0]
    assert rows[0]["fleet"]["sent"] == 10


def test_dump_flight_roundtrip(tmp_path):
    from repro.runtime import ChipFailure, RecoveryEvent

    mcfg = obs.MetricsConfig(flight_depth=2)
    m = obs.metrics_init(mcfg, 2)
    for b in range(3):
        m = obs.metrics_update(mcfg, m, _fake_stats(2, b + 1))
    path = str(tmp_path / "flight.jsonl")
    obs.dump_flight(path, m.flight,
                    recoveries=[RecoveryEvent(detected_at=1, resumed_from=0,
                                              healthy=(0,))],
                    failure=ChipFailure(2, (0,)), meta={"extra": 1})
    dump = obs.load_flight(path)
    assert dump["meta"]["depth"] == 2 and dump["meta"]["extra"] == 1
    assert [b["seq"] for b in dump["blocks"]] == [1, 2]
    assert dump["recoveries"][0]["detected_at"] == 1
    assert dump["failure"]["step"] == 2
    assert dump["failure"]["surviving"] == [0]


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_and_logger(tmp_path):
    p = str(tmp_path / "rows.jsonl")
    rows = [{"kind": "meta", "a": 1}, {"kind": "x", "b": [1, 2]}]
    obs.write_jsonl(p, rows)
    assert list(obs.read_jsonl(p)) == rows
    with obs.JsonlLogger(p) as log:        # append mode
        log.emit("evt", n=3)
    assert list(obs.read_jsonl(p)) == rows + [{"kind": "evt", "n": 3}]


def test_prometheus_text_format():
    txt = obs.prometheus_text({"a": 1, "b": 2.5, "skip": "str",
                               "flag": True},
                              prefix="t", labels={"arch": "x"})
    assert '# TYPE t_a gauge' in txt
    assert 't_a{arch="x"} 1' in txt
    assert 't_b{arch="x"} 2.5' in txt
    assert "skip" not in txt and "flag" not in txt


def test_summary_exposition_covers_fields():
    cfg, params, state = _net(telemetry=True, superstep=1)
    final, _ = net.run(cfg, params, state, _ext(cfg, 8))
    txt = obs.summary_exposition(obs.metrics_summary(final.metrics))
    for fld in obm.SCALAR_FIELDS:
        assert f"repro_fabric_{fld}_total" in txt
        assert f"repro_fabric_{fld}_per_step_ema" in txt
    assert "repro_fabric_steps_total 8" in txt


# ---------------------------------------------------------------------------
# Monitor CLI smoke (the CI metrics-smoke driver)
# ---------------------------------------------------------------------------

def test_monitor_demo_and_check(tmp_path, capsys):
    from repro.launch import monitor

    path = str(tmp_path / "dump.jsonl")
    res = monitor.demo(steps=16, n_chips=2, superstep=4, n_neurons=16,
                       jsonl=path)
    assert res["report"].ok
    assert monitor.check_dump(path) == 0
    monitor.render_dump(path)
    out = capsys.readouterr().out
    assert "conservation identity" in out
    assert "drop buckets" in out


# ---------------------------------------------------------------------------
# Phase scopes: names in the compiled program, one annotation per context
# ---------------------------------------------------------------------------

NETWORK_SCOPES = ("snn/ring", "snn/synapse", "snn/neuron", "snn/spikes",
                  "fabric/inject/route", "fabric/inject/pack")


@pytest.mark.parametrize("superstep", [1, 4], ids=["B1", "B4"])
def test_network_scopes_reach_the_optimized_hlo(superstep):
    """Both execution forms (the per-step body and the blocked substep)
    name ring, crossbar, neuron, compaction, route and pack in the op
    metadata that survives XLA's optimization."""
    comm = pc.PulseCommConfig(
        n_chips=2, neurons_per_chip=8, n_inputs_per_chip=8,
        event_capacity=8, bucket_capacity=8, ring_depth=16, fanout=2,
        superstep=superstep)
    cfg = net.NetworkConfig(comm=comm, neuron_model="adex")
    params = net.init_params(jax.random.PRNGKey(3), cfg)
    state = net.init_state(cfg, params)
    ext = jnp.zeros((4, 2, 8))
    hlo = jax.jit(lambda p, s, e: net.run(cfg, p, s, e)).lower(
        params, state, ext).compile().as_text()
    op_names = "\n".join(
        line.split('op_name="', 1)[1].split('"', 1)[0]
        for line in hlo.splitlines() if 'op_name="' in line)
    for scope in NETWORK_SCOPES:
        assert scope in op_names, scope


def _op_names(cfg, T=8):
    """The ``op_name``s of ``net.run``'s optimized HLO."""
    params = net.init_params(jax.random.PRNGKey(3), cfg)
    state = net.init_state(cfg, params)
    c = cfg.comm
    ext = jnp.zeros((T, c.n_chips, c.n_inputs_per_chip))
    hlo = jax.jit(lambda p, s, e: net.run(cfg, p, s, e)).lower(
        params, state, ext).compile().as_text()
    return [line.split('op_name="', 1)[1].split('"', 1)[0]
            for line in hlo.splitlines() if 'op_name="' in line]


@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["superstep", "pipelined"])
def test_merge_scope_reaches_the_optimized_hlo(pipeline):
    """Full mode with a rate-limited merge: the merge runs under its own
    ``fabric/merge`` scope on both schedules, never inside
    ``fabric/drain``."""
    cfg, _, _ = _net(pipeline=pipeline, merge=True)
    names = _op_names(cfg)
    assert any("fabric/merge" in n for n in names)
    assert any("fabric/drain" in n for n in names)
    both = [n for n in names if "fabric/drain" in n and "fabric/merge" in n]
    assert not both, both[:5]


def test_simplified_program_has_no_merge_scope():
    cfg, _, _ = _net()
    names = _op_names(cfg)
    assert any("fabric/drain" in n for n in names)
    assert not [n for n in names if "fabric/merge" in n]


def test_merge_cell_program_names_only_the_merge_beyond_the_phases():
    """The benchmark's full-scheme configuration, shrunk, at the program's
    defaults on the serial schedule: every ``fabric/`` op outside inject,
    exchange and drain is a ``fabric/merge`` op.  The per-layer reading
    ``merge_us_per_step`` (the trace's ``fabric_other`` layer) rests on
    this."""
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "configs" / "bss2-wafer-merge.json")
    config = json.loads(path.read_text())
    comm = dict(config["comm"], n_chips=4, neurons_per_chip=32,
                n_inputs_per_chip=16, event_capacity=24, bucket_capacity=4)
    network = dict(config["network"])
    network.pop("crossbar_precision")
    cfg = net.NetworkConfig(comm=pc.PulseCommConfig(**comm), **network)
    names = _op_names(cfg)
    phases = ("fabric/inject", "fabric/exchange", "fabric/drain")
    other = [n for n in names
             if "fabric/" in n and not any(p in n for p in phases)]
    assert other
    assert all("fabric/merge" in n for n in other), \
        sorted({n for n in other if "fabric/merge" not in n})[:5]


class _Annotations:
    """Counts ``jax.profiler.TraceAnnotation``s made while installed."""

    def __init__(self, monkeypatch):
        self.names = []
        real = jax.profiler.TraceAnnotation

        def record(name, **kwargs):
            self.names.append(name)
            return real(name, **kwargs)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", record)


def test_phase_scope_names_ops_and_leaves_no_host_annotation(monkeypatch):
    seen = _Annotations(monkeypatch)

    @jax.jit
    def f(x):
        with obs.phase_scope("snn/neuron"):
            return jnp.sin(x) * 2.0

    x = jnp.arange(4.0)
    assert "snn/neuron" in f.lower(x).as_text(debug_info=True)
    np.testing.assert_allclose(np.asarray(f(x)), np.sin(np.arange(4.0)) * 2.0,
                               rtol=1e-6)
    assert seen.names == []


def test_span_timer_counts_and_annotates(monkeypatch):
    seen = _Annotations(monkeypatch)
    timer = obs.SpanTimer()
    for _ in range(3):
        with timer.span("trial/dispatch"):
            with timer.span("trial/readback"):
                pass
    summary = timer.summary()
    assert summary["trial/dispatch"]["count"] == 3
    assert summary["trial/readback"]["count"] == 3
    assert summary["trial/dispatch"]["total_ms"] >= summary[
        "trial/readback"]["total_ms"]
    assert seen.names == ["trial/dispatch", "trial/readback"] * 3
