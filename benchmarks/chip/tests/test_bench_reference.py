"""The plain reference against the program, and the comparison against
the faults it must catch: whole runs of the harness on the CPU at the
tiny sizes of ``data/configs``."""

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import control, program, run

CELLS = ("tiny.trials", "tiny-merge.volley")


def one_run(root, workload, seed=2**31 + 11, seconds=0.3, **kw):
    buf = io.StringIO()
    assert run.run_cell(root, workload, seed, seconds, False,
                        require_tpu=False, out=buf, err=io.StringIO(), **kw) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_reference_agrees_with_program(tiny_root, workload, monkeypatch):
    totals = {}
    verify = run.verify

    def counting_verify(cell, arrays, system, drive, chunks, final):
        for _, host in chunks:
            rec = system.neutral_records(host)
            for key in ("sent", "overflow", "merge_dropped", "expired"):
                totals[key] = totals.get(key, 0) + int(rec[key].sum())
        return verify(cell, arrays, system, drive, chunks, final)

    monkeypatch.setattr(run, "verify", counting_verify)
    result = one_run(tiny_root, workload)
    assert result["correct"], result
    assert result["attempted"] > 1 and result["failed"] == 0
    assert result["checks"]["int_mismatches"]["value"] == 0
    # The comparison covered a run with every kind of loss its mode has.
    assert totals["sent"] > 0 and totals["expired"] > 0
    assert totals["overflow"] > 0
    if workload.startswith("tiny-merge"):
        assert totals["merge_dropped"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(tiny_root, workload):
    result = one_run(tiny_root, workload, system_cls=control.SYSTEMS["control"])
    assert not result["correct"]
    assert result["checks"]["v_gap"]["value"] > result["checks"]["v_gap"]["limit"]


def _state_unchanged(run_fn):
    def fn(cfg, params, state, ext):
        _, rec = run_fn(cfg, params, state, ext)
        return state, rec
    return fn


def _half_the_chips(run_fn):
    def fn(cfg, params, state, ext):
        half = ext.shape[1] // 2
        return run_fn(cfg, params, state, ext.at[:, half:].set(0.0))
    return fn


def _spike_altered(run_fn):
    def fn(cfg, params, state, ext):
        state, rec = run_fn(cfg, params, state, ext)
        return state, rec._replace(spikes=rec.spikes.at[-1, 0, 0].add(1.0) % 2)
    return fn


def _no_exchange(monkeypatch):
    from repro.core import transport

    monkeypatch.setattr(transport.ShardMapTransport, "all_to_all",
                        lambda self, x: x)


FAULTS = {
    "state_unchanged": _state_unchanged,
    "half_the_chips": _half_the_chips,
    "spike_altered": _spike_altered,
    "no_exchange": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(tiny_root, workload, fault, monkeypatch):
    from repro.snn import network as net

    if FAULTS[fault] is None:
        _no_exchange(monkeypatch)
    else:
        monkeypatch.setattr(net, "run", FAULTS[fault](net.run))
    result = one_run(tiny_root, workload)
    assert not result["correct"], fault
    assert result["failed"] > 0


def test_high_precision_crossbar_differs():
    """The control's three-pass crossbar is not the float32 product."""
    from benchmarks.chip.reference import network as ref

    key = jax.random.PRNGKey(0)
    x = jnp.round(jax.random.uniform(key, (3, 64)) * 4)
    w = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (3, 64, 32))
    exact = ref.crossbar(x, w, "highest")
    high = ref.crossbar(x, w, "high")
    want = np.einsum("ci,cin->cn", np.asarray(x, np.float64),
                     np.asarray(w, np.float64))
    assert np.abs(np.asarray(exact) - want).max() < 1e-5
    assert np.abs(np.asarray(high) - np.asarray(exact)).max() > 1e-6


def test_program_refuses_implementation_keys(tiny_root):
    cfg = json.loads((tiny_root / "benchmarks/chip/tests/data/configs/tiny.json")
                     .read_text())
    cfg["comm"]["use_pallas"] = True
    with pytest.raises(ValueError, match="implementation choice"):
        program.Program(cfg, {})

