"""The trace reduction, on small traces recorded on a TPU v5e and on
made-up intervals.

``data/trace_toy``: ``record_trace.py`` on a v5e (three calls of an
8-step scan with a ``fabric/inject`` sort and a ``fabric/drain`` matmul);
its HLO is the same program compiled for a described v5e, whose
instruction names match the chip's for every op that carries metadata.
"""

import gzip
from pathlib import Path

import pytest

from benchmarks.chip import trace

DATA = Path(__file__).resolve().parent / "data"


def _reduce(name: str, chunk_steps: int) -> dict:
    hlo = gzip.decompress((DATA / name / "program.hlo.gz").read_bytes())
    return trace.reduce_dir(DATA / name, hlo.decode(), chunk_steps,
                            ("trial/",))


@pytest.fixture(scope="module")
def toy():
    return _reduce("trace_toy", 8)


def test_toy_trace_layers(toy):
    """The sort dominates, under its scope; the matmul is the drain."""
    layers = toy["layer_s"]
    assert set(layers) == {"inject", "drain", "network"}
    assert layers["inject"] > 10 * layers["drain"] > 0
    assert toy["steps"] == 3 * 8
    assert 0 < toy["busy_s"] < 0.1 * toy["window_s"]   # 10 ms host sleeps
    assert sum(layers.values()) <= toy["busy_s"] * 1.0001
    top = toy["breakdown"]["device_ops"][0][0]
    assert top.startswith("inject:") and "sort" in top
    assert toy["breakdown"]["idle_gaps"][0][0] == "trial/readback"


def test_union_and_self_times():
    assert trace.union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    # A while op [0, 10] holding two body ops: its self time is the rest.
    ops = [(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (11, 12, "c")]
    assert trace.self_times(ops) == [3, 3, 4, 1]


def test_op_names_and_layers():
    hlo = "\n".join([
        "HloModule jit_run, entry_computation_layout={()->()}",
        '  %fusion.1 = s32[4]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(run)/while/body/fabric/inject/scatter"}',
        '  ROOT %dot.2 = f32[4]{0} dot(%a, %b), '
        'metadata={op_name="jit(run)/while/body/dot_general"}',
    ])
    module, names = trace.op_names(hlo)
    assert module == "jit_run"
    assert trace.layer_of(names["fusion.1"]) == "inject"
    assert trace.layer_of(names["dot.2"]) == "network"
    assert trace.layer_of("jit(run)/obs/metrics_update/add") == "telemetry"
    assert trace.instruction("%fusion.1 = s32[4]{0} fusion(%p)") == "fusion.1"
    assert trace.module_of("jit_run(1234)") == "jit_run"
