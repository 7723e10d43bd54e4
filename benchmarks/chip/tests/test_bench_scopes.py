"""The scope split (``scopes.py``): inference on made-up HLO, the split
against ``trace.reduce``'s layers on the recorded traces, and the script's
set-up on the CPU.

``data/trace_toy`` is the toy of ``test_bench_trace.py``.
``data/trace_tiny`` is the program itself at the ``tiny`` test
configuration, recorded on a v5e with

    python3 benchmarks/chip/scopes.py --root <scratch checkout> \
        --workload tiny.trials --seed 1 --keep <dir>

from a checkout that ``record_trace.scratch_root`` made.
"""

import gzip
import io
from pathlib import Path

import pytest

from benchmarks.chip import scopes, trace

DATA = Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_run, entry_computation_layout={(s32[4]{0})->s32[4]{0}}

%fused (param_0: s32[4]) -> s32[4] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %neg = s32[4]{0} negate(%param_0), metadata={op_name="jit(run)/fabric/drain/neg"}
}

ENTRY %main (x: s32[4]) -> (s32[4], s32[4], s32[4], s32[4], s32[4]) {
  %x = s32[4]{0} parameter(0)
  %c = s32[] constant(1), metadata={op_name="jit(run)/while/body"}
  %route = s32[4]{0} gather(%x, %c), metadata={op_name="jit(run)/while/body/vmap(fabric/inject)/fabric/inject/route/gather"}
  %rw = s32[4]{0:T(256)} reduce-window(%route, %c), window={size=4}
  %pack = s32[4]{0} fusion(%rw), kind=kLoop, calls=%fused, metadata={op_name="jit(run)/while/body/vmap(fabric/inject)/fabric/inject/pack/scatter"}
  %tail = s32[4]{0} copy(%pack)
  %head = s32[4]{0} copy(%x)
  %bit = s32[4]{0} bitcast(%head)
  %nrn = s32[4]{0} add(%bit, %bit), metadata={op_name="jit(run)/while/body/snn/neuron/add"}
  %mix = s32[4]{0} multiply(%x, %x)
  %spk = s32[4]{0} sort(%mix), metadata={op_name="jit(run)/while/body/snn/spikes/sort"}
  %prog = s32[4]{0} subtract(%x, %x)
  %rec = s32[4]{0} dynamic-update-slice(%mix, %prog, %c), metadata={op_name="jit(run)/while/body/dynamic_update_slice"}
  %orphan = s32[4]{0} copy(%x)
  ROOT %out = (s32[4]{0}, s32[4]{0}, s32[4]{0}, s32[4]{0}, s32[4]{0}) tuple(%tail, %nrn, %spk, %rec, %orphan)
}
"""


def test_graph_keeps_operands_within_a_computation():
    nodes = scopes.graph(HLO)
    assert nodes["pack"]["operands"] == ["rw"]      # not %fused, a computation
    assert nodes["param_0"]["users"] == ["neg"]
    assert nodes["rw"]["opcode"] == "reduce-window"
    assert nodes["c"]["opcode"] == "constant"
    assert nodes["rw"]["op_name"] is None
    assert sorted(nodes["x"]["users"]) == ["head", "mix", "orphan", "prog",
                                           "route"]


@pytest.mark.parametrize("instr, want", [
    # Producers only: its one consumer, the root tuple, carries no name.
    ("tail", ("fabric/inject/pack", "producers")),
    # Consumers only, through an unnamed bitcast; the entry parameter
    # carries no name.
    ("head", ("snn/neuron", "consumers")),
    # Route in, pack out: their common prefix; the constant is no evidence.
    ("rw", ("fabric/inject", "both")),
    # A record write (no scope) and a scoped consumer: the scope wins.
    ("mix", ("snn/spikes", "consumers")),
    # Only the record write: the scan's own bookkeeping.
    ("prog", ("program", "consumers")),
    # No named neighbour on either side.
    ("orphan", ("unscoped", "none")),
], ids=["producers", "consumers", "common-prefix", "scoped-over-program",
        "program", "orphan"])
def test_inference(instr, want):
    assert scopes.infer(scopes.graph(HLO), instr) == want


def test_placer_names_and_infers():
    place = scopes.placer(HLO)
    assert place("route") == ("fabric/inject/route", None)
    assert place("rec") == ("program", None)
    assert place("rw") == ("fabric/inject", "both")
    assert place("not-in-the-hlo") == ("unscoped", "none")


def test_scope_of_takes_the_innermost_listed_scope():
    assert scopes.scope_of("a/fabric/inject/route/gather") == "fabric/inject/route"
    assert scopes.scope_of("a/vmap(fabric/inject)/reduce_sum") == "fabric/inject"
    assert scopes.scope_of("a/fabric/flush/x") == "fabric/"
    assert scopes.scope_of("a/obs/metrics_update/add") == "obs/"
    assert scopes.scope_of("a/while/body/dynamic_update_slice") == "program"


def _reduce(name: str, xplane: str, chunk_steps: int):
    from jax.profiler import ProfileData

    hlo = gzip.decompress((DATA / name / "program.hlo.gz").read_bytes())
    raw = (DATA / name / xplane).read_bytes()
    if xplane.endswith(".gz"):
        raw = gzip.decompress(raw)
    return scopes.reduce(ProfileData.from_serialized_xspace(raw),
                         hlo.decode(), chunk_steps, ("trial/",))


@pytest.fixture(scope="module")
def toy():
    return _reduce("trace_toy", "toy.xplane.pb", 8)


@pytest.fixture(scope="module")
def tiny():
    return _reduce("trace_tiny", "trace.xplane.pb.gz", 8)


def _program_s(summary) -> float:
    return sum(v for k, v in summary["layer_s"].items() if k != "harness")


def test_toy_layers_pinned(toy):
    """``trace.reduce``'s numbers on the toy, as the benchmark's first
    traced runs computed them: an edit that moves them moves every
    existing per-layer metric."""
    exact = lambda v: pytest.approx(v, rel=1e-12, abs=0)
    assert toy["window_s"] == exact(0.027809247)
    assert toy["busy_s"] == exact(0.000744947)
    assert toy["steps"] == 24
    assert toy["layer_s"] == {"network": exact(3.3662e-05),
                              "inject": exact(0.000689753),
                              "drain": exact(2.1532e-05)}
    assert toy["breakdown"]["device_ops"] == [
        ["inject: fabric/inject/jit(sort)/sort", exact(0.000658873)],
        ["inject: fabric/inject/add", exact(3.088e-05)],
        ["drain: fabric/drain/dot_general", exact(2.1532e-05)],
        ["network: dynamic_update_slice", exact(1.1626e-05)],
        ["network: copy.6", exact(8.691e-06)],
        ["network: add", exact(4.419e-06)],
        ["network: copy.20", exact(3.54e-06)],
        ["network: copy-done", exact(2.723e-06)],
        ["network: iota.clone.2", exact(2.11e-06)],
        ["network: while", exact(5.06e-07)]]
    gaps = toy["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps[:3]] == ["trial/readback"] * 3
    assert [g[1] for g in gaps[:3]] == [exact(0.012667863),
                                        exact(0.012561466),
                                        exact(0.001834951)]


@pytest.mark.parametrize("fixture", ["toy", "tiny"])
def test_scopes_partition_the_program_ops(fixture, request):
    summary = request.getfixturevalue(fixture)
    assert sum(summary["scope_s"].values()) == pytest.approx(
        _program_s(summary), rel=1e-9)
    for instr, scope, side, seconds in summary["inferred"]:
        assert scope in scopes.SCOPES + (scopes.PROGRAM, scopes.UNSCOPED)
        assert side in ("producers", "consumers", "both", "none")
        assert seconds > 0


def test_toy_split_and_clock(toy):
    """The sort and its unnamed copy go to ``fabric/inject``; the clock
    pairs each of the three calls with its spans."""
    split = toy["scope_s"]
    assert set(split) == {"fabric/inject", "fabric/drain", "program"}
    assert split["fabric/inject"] > toy["layer_s"]["inject"]
    assert split["fabric/drain"] == toy["layer_s"]["drain"]
    [clock] = toy["clock"]
    assert clock["pairs"] == 3
    lo, hi = clock["offset_ms"]
    assert lo <= hi
    assert clock["align_shift_ms"] >= 0


def test_tiny_program_names_its_time(tiny):
    """The real program: every new scope holds time, and what inference
    cannot place is under a tenth of the program's device time."""
    split = tiny["scope_s"]
    for scope in ("snn/ring", "snn/synapse", "snn/neuron", "snn/spikes",
                  "fabric/inject/route", "fabric/inject/pack"):
        assert split.get(scope, 0) > 0, scope
    assert split.get("unscoped", 0) < 0.1 * _program_s(tiny)
    assert tiny["steps"] > 0
    lines = scopes.report(tiny)
    assert lines[0].startswith("inferred") and lines[1].startswith("clock")


def test_readers(tiny, tiny_root):
    """Each reader of the split reads a number from a split, 0 for a scope
    with no ops, and None where there is no split."""
    from benchmarks.chip import spec

    cell = spec.Cell(tiny_root, "tiny.trials")
    values = {m: cell.reader(m).read({"trace": tiny}) for m in scopes.METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["route_us_per_step"] > 0
    no_ops = dict(tiny, scope_s={})
    assert cell.reader("ring_us_per_step").read({"trace": no_ops}) == 0.0
    without = {k: v for k, v in tiny.items() if k != "scope_s"}
    for m in scopes.METRICS:
        assert cell.reader(m).read({"trace": without}) is None
        assert cell.reader(m).read({"trace": None}) is None


def test_script_sets_up_and_keeps_the_trace(tiny_root, tmp_path):
    """The script's path on the CPU: the set-up, a traced window and the
    fixture files; the CPU trace has no TPU plane, so no split."""
    buf = io.StringIO()
    result = scopes.trace_cell(tiny_root, "tiny.trials", 2**31 + 5, 0.2,
                               require_tpu=False, keep=tmp_path / "keep",
                               out=buf)
    assert result["device"]["platform"] == "cpu"
    assert result["scope_us_per_step"] is None and result["metrics"] == {}
    hlo = gzip.decompress((tmp_path / "keep" / "program.hlo.gz").read_bytes())
    assert trace.op_names(hlo.decode())[0]
    assert (tmp_path / "keep" / "trace.xplane.pb.gz").is_file()
    assert not (tiny_root / ".bench_trace").exists()
