"""The check replays a fixed number of steps from the start of the window,
not the whole window: whole runs of the harness on the CPU at the tiny
sizes of ``data/configs``, with the cap lowered to a few chunks."""

import ast
import io
import json
import re

import jax
import numpy as np
import pytest

from benchmarks.chip import check, program, run

CELLS = ("tiny.trials", "tiny-merge.volley")
CHUNK_STEPS = 8          # both tiny traffic mixes
CAP_CHUNKS = 3


def one_run(root, workload, seconds, **kw):
    buf = io.StringIO()
    assert run.run_cell(root, workload, 2**31 + 23, seconds, False,
                        require_tpu=False, out=buf, err=io.StringIO(), **kw) == 0
    lines = buf.getvalue().strip().splitlines()
    window = next(line for line in lines if line.startswith("window:"))
    n_chunks = int(re.search(r"window: (\d+) chunks", window).group(1))
    totals = ast.literal_eval(re.search(r"event totals (\{.*\})", window).group(1))
    return json.loads(lines[-1]), n_chunks, totals


def capture_verify(monkeypatch):
    """Replace ``run.verify`` with one that records what it is given."""
    seen = {}
    verify = run.verify

    def capturing(cell, arrays, system, drive, chunks, final):
        seen.update(system=system, chunks=list(chunks), final=final)
        return verify(cell, arrays, system, drive, chunks, final)

    monkeypatch.setattr(run, "verify", capturing)
    return seen


class Recording(program.Program):
    """The program, holding on to the records of every chunk it runs (the
    warm-up first)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.records = []

    def run(self, state, ext):
        state, rec = super().run(state, ext)
        self.records.append(rec)
        return state, rec


class StateFaultAtCap(program.Program):
    """The program, with the membrane state returned by the last chunk the
    check compares shifted, and every record intact."""

    calls = 0

    def run(self, state, ext):
        state, rec = super().run(state, ext)
        self.calls += 1
        if self.calls == 1 + CAP_CHUNKS:          # the warm-up, then chunks
            neuron = state.neuron._replace(v=state.neuron.v + 1.0)
            state = state._replace(neuron=neuron)
        return state, rec


@pytest.mark.parametrize("workload", CELLS)
def test_window_longer_than_cap_replays_the_cap(tiny_root, workload,
                                                monkeypatch):
    seen = capture_verify(monkeypatch)
    result, n_chunks, totals = one_run(
        tiny_root, workload, 0.5, system_cls=Recording,
        verify_steps=CAP_CHUNKS * CHUNK_STEPS)
    assert n_chunks > CAP_CHUNKS
    assert result["correct"], result
    assert result["attempted"] == CAP_CHUNKS and result["failed"] == 0

    system = seen["system"]
    window = [jax.device_get(rec) for rec in system.records[1:]]
    assert len(window) == n_chunks
    # verify got the full records of exactly the first chunks ...
    assert len(seen["chunks"]) == CAP_CHUNKS
    for (_, host), want in zip(seen["chunks"], window):
        got, ref = system.neutral_records(host), system.neutral_records(want)
        assert set(got) == set(ref)
        for key in ref:
            np.testing.assert_array_equal(got[key], ref[key])
        assert got["voltage"].shape[0] == CHUNK_STEPS
    # ... and the state after the last of them,
    assert int(seen["final"]["t"]) == CAP_CHUNKS * CHUNK_STEPS
    # while the window line totals every chunk of the window.
    want = dict.fromkeys(program.COUNTERS, 0)
    for rec in window:
        rec = system.neutral_records(rec)
        for key in want:
            want[key] += int(rec[key].sum())
    assert totals == want


@pytest.mark.parametrize("workload", CELLS)
def test_state_fault_at_cap_is_caught(tiny_root, workload):
    result, n_chunks, _ = one_run(
        tiny_root, workload, 0.5, system_cls=StateFaultAtCap,
        verify_steps=CAP_CHUNKS * CHUNK_STEPS)
    assert n_chunks > CAP_CHUNKS
    assert result["attempted"] == CAP_CHUNKS
    assert result["failed"] == 0              # every record matched
    assert not result["correct"]
    assert result["checks"]["v_gap"]["value"] > result["checks"]["v_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_window_shorter_than_cap_replays_every_chunk(tiny_root, workload,
                                                     monkeypatch):
    seen = capture_verify(monkeypatch)
    result, n_chunks, _ = one_run(tiny_root, workload, 0.3)
    assert 1 < n_chunks < check.VERIFY_STEPS // CHUNK_STEPS
    assert result["correct"], result
    assert result["attempted"] == n_chunks and result["failed"] == 0
    assert len(seen["chunks"]) == n_chunks
    assert int(seen["final"]["t"]) == n_chunks * CHUNK_STEPS
